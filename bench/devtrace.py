"""From a profiler trace to device busy time, kernel time and idle gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
small dict: the device operations of each chip (the "XLA Ops" line, whose
events are HLO instructions, a loop's event enclosing its body's) and the
harness's own host spans (``bench.<label>`` annotations), all in
nanoseconds on the trace's clock.  Everything after that works on the
dict, so a recorded excerpt is enough to test it.

A Pallas kernel shows up as a ``tpu_custom_call`` instruction that often
carries no name of its own, so ``KERNELS`` tells the program's kernels
apart by their operands and results.
"""

from __future__ import annotations

import glob
import os
import re

HOST_PREFIX = "bench."
_CUSTOM = r'custom_call_target="tpu_custom_call"'
# the program's Pallas kernels, by the signature of their instruction
KERNELS = {
    # a stack of float32 frames in, their prefix sums out
    "integral_image": (r"= f32\[\d+(,\d+){2,3}\]", r"custom-call\(f32\[",
                       _CUSTOM),
    # int8 operands
    "quant_matmul": (r"custom-call\(s8\[", _CUSTOM),
    # a value and a weight grid out
    "bilateral_blur": (r"= \(f32\[", _CUSTOM),
}


def profile_options():
    """Host annotations on, Python function tracing off (it slows the host
    by far more than the annotations and fills the trace)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "Core" not in name


def extract(trace_dir: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[label, start_ns, dur_ns], ...]} of the newest trace."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if _device_plane(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([e.name, e.start_ns, e.duration_ns]
                               for e in line.events)
            if ops:
                devices[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name[len(HOST_PREFIX):], e.start_ns,
                                     e.duration_ns])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def window(tr: dict, label: str = "window") -> tuple:
    """(start_ns, end_ns) of the harness's window span."""
    spans = [e for e in tr["host"] if e[0] == label]
    if not spans:
        raise ValueError(f"the trace holds no {HOST_PREFIX}{label} span")
    s = spans[-1]
    return s[1], s[1] + s[2]


def merge(intervals) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, w0, w1) -> list:
    return [[max(s, w0), min(e, w1)] for s, e in intervals
            if e > w0 and s < w1]


def busy_ns(ops, w0, w1) -> float:
    """Time in [w0, w1) in which some operation ran on the device."""
    return float(sum(e - s for s, e in clip(
        merge([o[1], o[1] + o[2]] for o in ops), w0, w1)))


def gaps(ops, w0, w1) -> list:
    """Idle [start, end) intervals of the device inside [w0, w1)."""
    out, t = [], w0
    for s, e in clip(merge([o[1], o[1] + o[2]] for o in ops), w0, w1):
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if t < w1:
        out.append([t, w1])
    return out


def label_gap(gap, host) -> str:
    """What the host was doing in an idle gap: the span that overlaps it
    most, or "other"."""
    best, label = 0, "other"
    for name, s, d in host:
        if name == "window":
            continue
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best:
            best, label = ov, name
    return label


def kernel_ns(ops, kernel: str, w0, w1) -> tuple:
    """(total device ns, events) of one of ``KERNELS`` in [w0, w1)."""
    rxs = [re.compile(p) for p in KERNELS[kernel]]
    hits = [o for o in ops if w0 <= o[1] < w1
            and all(rx.search(o[0]) for rx in rxs)]
    return float(sum(o[2] for o in hits)), len(hits)


def self_ns(ops) -> list:
    """[name, start, self ns] per operation: its duration less the part
    that the operations nested inside it (a loop's body) cover."""
    out, stack = [], []              # stack: [index, end, covered ns]
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][1]:
            i, _e, cov = stack.pop()
            out[i][2] -= cov
        if stack:
            stack[-1][2] += min(d, stack[-1][1] - s)
        out.append([name, s, d])
        stack.append([len(out) - 1, s + d, 0])
    for i, _e, cov in stack:
        out[i][2] -= cov
    return out


def short(name: str, n: int = 160) -> str:
    """An instruction's text without its layouts and attributes."""
    while True:
        cut = re.sub(r"\{[^{}]*\}", "", name)
        if cut == name:
            return name[:n]
        name = cut


def top_ops(ops, w0, w1, n: int = 10) -> list:
    """[[name, seconds], ...] of the operations that took most time of
    their own in [w0, w1)."""
    tot = {}
    for name, s, d in self_ns(ops):
        if w0 <= s < w1:
            key = short(name)
            tot[key] = tot.get(key, 0) + d
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(ops, host, w0, w1, n: int = 10) -> list:
    """[[host activity, seconds], ...] of the longest idle gaps."""
    gs = sorted(gaps(ops, w0, w1), key=lambda g: g[0] - g[1])[:n]
    return [[label_gap(g, host), (g[1] - g[0]) * 1e-9] for g in gs]
