"""The program's own marks in a traced run: its ``repro.obs`` spans and the
name scopes of its device operations.

``repro.obs.Telemetry.span`` puts each span of host work into the profiler
trace as a ``repro.<kind>.<name>`` annotation, and the funnel names its
layers with ``jax.named_scope``, which XLA keeps in each instruction's
``op_name``.  The TPU trace holds that path as the ``tf_op`` statistic of
the instruction's event metadata, which ``jax.profiler.ProfileData`` does
not expose, so ``read_trace`` decodes the device planes of the
``.xplane.pb`` itself (the protobuf wire format, a few message fields).

``read_trace`` gives one small dict:
``{"window": (start_ns, end_ns) or None,
   "spans": [[label, start_ns, dur_ns], ...],
   "devices": {plane: [[name, start_ns, dur_ns, scopes], ...]}}``,
where ``label`` is the annotation without ``repro.`` and ``scopes`` is the
tuple of the named scopes around the operation (the components of its
``op_name`` path before the primitive's own name).  Everything after that
works on the dict, so a recorded dict is enough to test it.  ``load``
finds this run's file and checks that it is the traced window the harness
read.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

from bench import devtrace

BENCH = os.path.dirname(os.path.abspath(__file__))
SPAN_PREFIX = "repro."
# every name scope the program puts on its device operations
PROGRAM_SCOPES = (r"motion|detect|integral|vj\d+|gather|nn|scatter|scorer|"
                  r"capacity")
_MEMO: dict = {}

# -- the protobuf wire format, as far as XSpace needs it ----------------------


def _varint(buf, pos):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _fields(buf, pos: int, end: int):
    """(field number, value) of one message: an int for a varint, a
    (start, end) pair for a length-delimited field."""
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, pos = _varint(buf, pos)
            yield num, v
        elif wire == 2:
            n, pos = _varint(buf, pos)
            yield num, (pos, pos + n)
            pos += n
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key, val = 0, None
    for num, v in _fields(buf, *span):
        if num == 1:
            key = _signed(v)
        elif num == 2:
            val = v
    return key, val


def _device_ops(buf, plane_span) -> list:
    """[[name, start_ns, dur_ns, op_name], ...] of one device plane's "XLA
    Ops" line: XPlane{lines=3, event_metadata=4, stat_metadata=5},
    XLine{name=2, timestamp_ns=3, events=4}, XEvent{metadata_id=1,
    offset_ps=2, duration_ps=3}, XEventMetadata{name=2, stats=5},
    XStat{metadata_id=1, str_value=5, ref_value=7}."""
    stat_names, metas, lines = {}, {}, []
    for num, v in _fields(buf, *plane_span):
        if num == 5:
            key, val = _map_entry(buf, v)
            for n2, v2 in _fields(buf, *val):
                if n2 == 2:
                    stat_names[key] = _text(buf, v2)
        elif num == 4:
            key, val = _map_entry(buf, v)
            metas[key] = val
        elif num == 3:
            lines.append(v)
    tf_op = next((k for k, s in stat_names.items() if s == "tf_op"), None)

    def meta(mid):
        name, op = "", ""
        span = metas.get(mid)
        if span is None:
            return name, op
        for num, v in _fields(buf, *span):
            if num == 2:
                name = _text(buf, v)
            elif num == 5:
                sid, value = None, ""
                for n2, v2 in _fields(buf, *v):
                    if n2 == 1:
                        sid = _signed(v2)
                    elif n2 == 5:
                        value = _text(buf, v2)
                    elif n2 == 7:
                        value = stat_names.get(_signed(v2), "")
                if sid == tf_op:
                    op = value
        return name, op

    seen: dict = {}
    out = []
    for line in lines:
        name, t0, events = "", 0, []
        for num, v in _fields(buf, *line):
            if num == 2:
                name = _text(buf, v)
            elif num == 3:
                t0 = _signed(v)
            elif num == 4:
                events.append(v)
        if name != "XLA Ops":
            continue
        for ev in events:
            mid = off = dur = 0
            for num, v in _fields(buf, *ev):
                if num == 1:
                    mid = _signed(v)
                elif num == 2:
                    off = _signed(v)
                elif num == 3:
                    dur = _signed(v)
            if mid not in seen:
                seen[mid] = meta(mid)
            op_name, op = seen[mid]
            # whole nanoseconds, as ``jax.profiler.ProfileData`` gives them
            out.append([op_name, float((t0 * 1000 + off) // 1000),
                        float(dur // 1000), op])
    return out


_WRAPPED = re.compile(r"([\w-]+)\((.*)\)")
# wrappers that name a function, not a scope
_FUNCTIONS = ("jit", "pjit", "pmap", "xla_pmap", "shard_map", "custom_jvp",
              "custom_vjp")


def scopes_of(op_name: str) -> tuple:
    """The named scopes of an ``op_name`` path: its components without the
    primitive's own name (the last one) and without the functions a
    transformation names (``jit(f)``); a transformed scope
    (``vmap(detect)``) counts as itself, an empty one (``vmap()``) not at
    all.  ``tf_op`` appends ``:<type>``, which is cut, and joins the names
    of a fused instruction with ``;``, of which the first is taken."""
    out = []
    for part in op_name.split(";", 1)[0].split(":", 1)[0].split("/")[:-1]:
        m = _WRAPPED.fullmatch(part)
        while m and m.group(1) not in _FUNCTIONS:
            part = m.group(2)
            m = _WRAPPED.fullmatch(part)
        if not m and part:
            out.append(part)
    return tuple(out)


def read_trace(path: str) -> dict:
    """The dict described above, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        buf = f.read()
    devices = {}
    for num, v in _fields(buf, 0, len(buf)):
        if num != 1:                              # XSpace.planes
            continue
        name = next((_text(buf, v2) for n2, v2 in _fields(buf, *v)
                     if n2 == 2), "")
        if devtrace._device_plane(name):
            ops = [[n, s, d, scopes_of(op)]
                   for n, s, d, op in _device_ops(buf, v)]
            if ops:
                devices[name] = sorted(ops, key=lambda o: o[1])
    spans, window = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append([e.name[len(SPAN_PREFIX):], e.start_ns,
                                  e.duration_ns])
                elif e.name == devtrace.HOST_PREFIX + "window":
                    window = (e.start_ns, e.start_ns + e.duration_ns)
    spans.sort(key=lambda s: s[1])
    return {"window": window, "spans": spans, "devices": devices}


def newest(root: str = os.path.join(BENCH, ".out")):
    """The newest ``.xplane.pb`` under ``<root>/*/trace/``, or None."""
    paths = glob.glob(os.path.join(root, "*", "trace", "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(r):
    """This run's dict (read once per file), or None where there is no
    trace or the newest one is not the window ``r`` holds."""
    path = newest()
    if path is None:
        return None
    key = (path, os.path.getmtime(path), os.path.getsize(path))
    if key not in _MEMO:
        _MEMO.clear()
        try:
            _MEMO[key] = read_trace(path)
        except (ValueError, IndexError):
            # a file this decoder does not understand: nothing to read,
            # and the run's other metrics still stand
            _MEMO[key] = None
    pt = _MEMO[key]
    return pt if pt and pt["window"] == (r.w0, r.w1) else None


# -- reductions ---------------------------------------------------------------


def spans(pt, label: str, w0, w1) -> list:
    """[start, end] of the spans named ``label`` (``label.`` as a prefix
    where it ends in a dot) that begin in [w0, w1)."""
    hit = ((lambda s: s.startswith(label)) if label.endswith(".")
           else (lambda s: s == label))
    return [[s, s + d] for name, s, d in pt["spans"]
            if hit(name) and w0 <= s < w1]


def inside(spans_in, outer) -> list:
    """The spans that lie within one of ``outer``."""
    return [s for s in spans_in
            if any(o0 <= s[0] and s[1] <= o1 for o0, o1 in outer)]


def own(pt, w0, w1) -> list:
    """[name, start_ns, own ns, scopes] of every device operation that
    begins in [w0, w1): its duration less the part that operations nested
    inside it (a loop's body) cover."""
    out = []
    for ops in pt["devices"].values():
        for i, s, d in devtrace.self_ns([i, o[1], o[2]]
                                        for i, o in enumerate(ops)):
            if w0 <= s < w1:
                out.append([ops[i][0], s, d, ops[i][3]])
    return out


def under(pattern: str):
    """A test of an operation's scopes: does one of them match
    ``pattern`` whole?"""
    rx = re.compile(pattern)
    return lambda scopes: any(rx.fullmatch(sc) for sc in scopes)


def scoped_per_dispatch(pt, pattern: str, w0, w1):
    """(ms, dispatches): device time of the operations under a scope that
    matches ``pattern`` (own time) and begin inside a ``dispatch`` span,
    averaged over the dispatch spans that begin in [w0, w1); None without
    such a span or such an operation."""
    disp = spans(pt, "dispatch.", w0, w1)
    if not disp:
        return None
    hit = under(pattern)
    starts = [a for a, _b in disp]

    def in_dispatch(s):
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and s < disp[i][1]

    mine = [d for _n, s, d, sc in own(pt, disp[0][0], disp[-1][1])
            if hit(sc) and in_dispatch(s)]
    if not mine:
        return None
    return sum(mine) * 1e-6 / len(disp), len(disp)


def device_ops(pt) -> list:
    """Every device's operations, one list."""
    return [o for ops in pt["devices"].values() for o in ops]


def idle_in(pt, labels, w0, w1):
    """(ms, ticks): device idle time inside the spans named in ``labels``
    that lie within a ``serve.tick`` beginning in [w0, w1), per tick; None
    without a tick."""
    ticks = spans(pt, "serve.tick", w0, w1)
    if not ticks:
        return None
    within = inside([s for lab in labels
                     for s in spans(pt, lab, w0, float("inf"))], ticks)
    idle = 0.0
    for g0, g1 in devtrace.gaps(device_ops(pt), w0, w1):
        for s0, s1 in within:
            idle += max(0.0, min(g1, s1) - max(g0, s0))
    return idle * 1e-6 / len(ticks), len(ticks)


def host_in(pt, labels, w0, w1):
    """(ms, ticks): host time in the spans named in ``labels`` within the
    ``serve.tick`` spans beginning in [w0, w1), per tick; None without a
    tick."""
    ticks = spans(pt, "serve.tick", w0, w1)
    if not ticks:
        return None
    within = inside([s for lab in labels
                     for s in spans(pt, lab, w0, float("inf"))], ticks)
    return sum(b - a for a, b in within) * 1e-6 / len(ticks), len(ticks)


def scoped_share(pt, pattern: str, w0, w1) -> tuple:
    """(share of the device's busy time in [w0, w1) spent in operations
    under a scope that matches ``pattern``, [[name, seconds], ...] of the
    five largest that are not), own time."""
    hit = under(pattern)
    on = off = 0.0
    rest: dict = {}
    for name, _s, d, sc in own(pt, w0, w1):
        if hit(sc):
            on += d
        else:
            off += d
            key = devtrace.short(name)
            rest[key] = rest.get(key, 0.0) + d
    top = sorted(rest.items(), key=lambda kv: -kv[1])[:5]
    return (on / (on + off) if on + off else 0.0,
            [[k, v * 1e-9] for k, v in top])
