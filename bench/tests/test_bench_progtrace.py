"""The program's spans and name scopes in a traced run: the decoding of a
``.xplane.pb`` written here by hand in the protobuf wire format, the
reductions on a hand-made dict, and each reader that uses them, against
arithmetic done by hand."""

import os
import types

import pytest

from bench import devtrace, progtrace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- a hand-made trace: two ticks, one dispatch each, times in ns -------------

SPANS = [
    ["serve.tick", 100, 400], ["serve.gather", 100, 20],
    ["dispatch.group:local", 120, 280], ["serve.stage", 125, 15],
    ["serve.cascade", 140, 60], ["serve.readback", 200, 190],
    ["serve.settle", 400, 50], ["serve.admission", 450, 30],
    ["serve.tick", 600, 300], ["serve.gather", 600, 10],
    ["dispatch.group:local", 610, 240], ["serve.stage", 612, 8],
    ["serve.cascade", 620, 40], ["serve.readback", 660, 180],
    ["serve.settle", 850, 30], ["serve.admission", 880, 15],
]
OPS = [
    ["%a = motion", 150, 10, ("motion",)],
    ["%b = stage 0", 160, 140, ("detect", "vj0")],
    ["%c = stage 1", 300, 20, ("detect", "vj1")],
    ["%d = gather", 320, 20, ("gather",)],
    ["%e = nn", 340, 20, ("nn",)],
    ["%f = copy", 360, 10, ()],
    ["%g = motion outside a dispatch", 455, 5, ("motion",)],
    ["%h = stage 0", 630, 170, ("detect", "vj0")],
    ["%w = while", 800, 30, ("detect", "vj1")],      # holds the next one
    ["%i = body", 805, 10, ("detect", "vj1")],
    ["%j = past the window", 1000, 10, ("nn",)],
]
HAND = {"window": (0.0, 1000.0), "spans": SPANS,
        "devices": {"/device:TPU:0": OPS}}
# busy [150, 370) [455, 460) [630, 830); idle [0, 150) [370, 455)
# [460, 630) [830, 1000)
R = types.SimpleNamespace(w0=0.0, w1=1000.0)


def test_scopes_are_the_path_without_functions_and_primitive():
    assert progtrace.scopes_of(
        "jit(step_core)/vmap(detect)/vmap(vj0)/while/body/jit(_take)/"
        "gather:") == ("detect", "vj0", "while", "body")
    assert progtrace.scopes_of("pmap(step)/vmap(vmap(nn))/dot_general") \
        == ("nn",)
    # an eager operation is compiled alone: no scope survives
    assert progtrace.scopes_of("jit(subtract)/sub") == ()
    # a primitive named like a scope is not one
    assert progtrace.scopes_of("jit(f)/detect/gather:") == ("detect",)
    assert progtrace.scopes_of(
        "jit(f)/vmap(nn)/reshape;jit(f)/vmap(nn)/dot_general:") == ("nn",)
    assert progtrace.scopes_of("jit(f)/vmap(gather)/vmap()/add:") == (
        "gather",)


def test_device_time_per_dispatch_counts_own_time_inside_dispatches():
    ms = 1e-6
    assert progtrace.scoped_per_dispatch(HAND, "vj0", 0, 1000) == (
        pytest.approx((140 + 170) / 2 * ms), 2)
    # the loop's own 20 plus its body's 10, and stage 1's 20
    assert progtrace.scoped_per_dispatch(HAND, r"vj[1-9]\d*", 0, 1000)[0] \
        == pytest.approx((20 + 20 + 10) / 2 * ms)
    # %g runs while the host is in admission, outside any dispatch
    assert progtrace.scoped_per_dispatch(HAND, "motion|scorer", 0, 1000)[0] \
        == pytest.approx(10 / 2 * ms)
    assert progtrace.scoped_per_dispatch(HAND, "capacity", 0, 1000) is None
    assert progtrace.scoped_per_dispatch(HAND, "vj0", 2000, 3000) is None


def test_idle_and_host_time_inside_the_tick_phases():
    ms = 1e-6
    # stage: 15 + 8, cascade: 10 + 10
    assert progtrace.idle_in(HAND, ("serve.stage", "serve.cascade"),
                             0, 1000) == (pytest.approx(43 / 2 * ms), 2)
    # [370, 390) in tick 1, [830, 840) in tick 2
    assert progtrace.idle_in(HAND, ("serve.readback",), 0, 1000)[0] \
        == pytest.approx(30 / 2 * ms)
    # gather 20 + 10, settle 50 + 30, admission 5 + 20 + 15
    assert progtrace.idle_in(HAND, ("serve.gather", "serve.settle",
                                    "serve.admission"), 0, 1000)[0] \
        == pytest.approx(150 / 2 * ms)
    assert progtrace.host_in(HAND, ("serve.gather", "serve.settle",
                                    "serve.admission"), 0, 1000) == (
        pytest.approx((30 + 80 + 45) / 2 * ms), 2)
    assert progtrace.idle_in(HAND, ("serve.stage",), 2000, 3000) is None


def test_share_of_busy_time_under_the_program_scopes():
    share, rest = progtrace.scoped_share(HAND, progtrace.PROGRAM_SCOPES,
                                         0, 1000)
    # own time in the window: 425 ns, of which the copy's 10 is unscoped
    assert share == pytest.approx(415 / 425)
    assert rest == [["%f = copy", pytest.approx(10e-9)]]


def reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def hand_trace(monkeypatch):
    monkeypatch.setattr(progtrace, "load", lambda r: HAND)


@pytest.mark.parametrize("name, want", [
    ("vj_stage0_ms.fleet", 155e-6),
    ("vj_survivors_ms.fleet", 25e-6),
    ("motion_gate_ms.fleet", 5e-6),
    ("window_gather_ms.fleet", 10e-6),
    ("nn_tail_ms.fleet", 10e-6),
    ("sched_host_ms.fleet", 77.5e-6),
    ("tick_idle_eager_ms.fleet", 21.5e-6),
    ("tick_idle_readback_ms.fleet", 15e-6),
    ("sched_idle_ms.fleet", 75e-6),
])
def test_each_reader_agrees_with_the_hand_arithmetic(hand_trace, name,
                                                     want):
    got = reader(name).read(R)
    value = got[0] if isinstance(got, tuple) else got
    assert value == pytest.approx(want)


def test_stage0_reports_the_share_of_busy_time_under_scopes(hand_trace):
    _v, extra = reader("vj_stage0_ms.fleet").read(R)
    assert extra == {"dispatches": 2,
                     "scoped_busy": pytest.approx(100 * 415 / 425),
                     "largest_unscoped": "%f = copy"}


@pytest.mark.parametrize("name", [
    "vj_stage0_ms.fleet", "vj_survivors_ms.fleet", "motion_gate_ms.fleet",
    "window_gather_ms.fleet", "nn_tail_ms.fleet", "sched_host_ms.fleet",
    "tick_idle_eager_ms.fleet", "tick_idle_readback_ms.fleet",
    "sched_idle_ms.fleet"])
def test_readers_find_nothing_in_a_program_without_spans(monkeypatch,
                                                         name):
    """A program without the spans and scopes (an older commit) reads as
    nothing, not as zero, and raises nothing."""
    bare = {"window": (0.0, 1000.0), "spans": [],
            "devices": {"/device:TPU:0": [[o[0], o[1], o[2], ()]
                                          for o in OPS]}}
    monkeypatch.setattr(progtrace, "load", lambda r: bare)
    assert reader(name).read(R) is None
    monkeypatch.setattr(progtrace, "load", lambda r: None)
    assert reader(name).read(R) is None


# -- the wire format: an XSpace written by hand --------------------------------


def _varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        b, v = v & 0x7F, v >> 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


def _plane(pid, name, line_name, t0_ns, events, metas, stats=()):
    """XPlane{id=1, name=2, lines=3, event_metadata=4, stat_metadata=5};
    ``events``: (metadata id, offset ps, duration ps); ``metas``: id ->
    (name, [(stat id, str or ("ref", id))])."""
    line = _msg((1, 1), (2, line_name), (3, t0_ns),
                *[(4, _msg((1, m), (2, off), (3, dur)))
                  for m, off, dur in events])
    out = [(1, pid), (2, name), (3, line)]
    for mid, (mname, mstats) in metas.items():
        st = [(5, _msg((1, sid), (7, v[1]) if isinstance(v, tuple)
                       else (5, v))) for sid, v in mstats]
        out.append((4, _msg((1, mid), (2, _msg((1, mid), (2, mname),
                                                *st)))))
    for sid, sname in stats:
        out.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    return _msg(*out)


def test_an_xplane_written_by_hand_decodes_as_the_profiler_reads_it(
        tmp_path):
    device = _plane(
        1, "/device:TPU:0", "XLA Ops", 5000,
        [(11, 250, 6_250), (12, 7_000, 1_000_500), (13, 2_000_000, 4_000),
         (11, 3_000_000, 6_250)],
        {11: ("%copy-start.1 = copy-start()",
              [(2, "jit(subtract)/sub:")]),
         12: ("%fusion.142 = fusion()",
              [(2, "jit(step_core)/vmap(detect)/vmap(vj0)/jit(_take)/"
                   "gather:"), (3, "loop fusion")]),
         13: ("%fusion.7 = fusion()", [(2, ("ref", 4))])},
        stats=[(2, "tf_op"), (3, "hlo_category"),
               (4, "jit(step_core)/vmap(nn)/dot_general")])
    host = _plane(
        2, "/host:CPU", "python3", 1000,
        [(21, 0, 10_000_000), (22, 100_000, 3_000_000),
         (23, 200_000, 500_000), (24, 50_000, 10)],
        {21: ("bench.window", []), 22: ("repro.serve.tick", []),
         23: ("repro.serve.cascade", []), 24: ("PjitFunction(f)", [])})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))

    pt = progtrace.read_trace(str(path))
    assert pt["window"] == (1000.0, 11000.0)
    assert pt["spans"] == [["serve.tick", 1100.0, 3000.0],
                           ["serve.cascade", 1200.0, 500.0]]
    ops = pt["devices"]["/device:TPU:0"]
    assert [o[3] for o in ops] == [(), ("detect", "vj0"), ("nn",), ()]
    # the same names and whole nanoseconds as the profiler's own reader
    tr = devtrace.extract(str(tmp_path))
    assert [o[:3] for o in ops] == tr["devices"]["/device:TPU:0"]
    assert ops[1][1:3] == [5007.0, 1000.0]


def test_load_takes_the_newest_trace_and_checks_the_window(tmp_path,
                                                          monkeypatch):
    pt = {"window": (1.0, 2.0), "spans": [], "devices": {}}
    assert progtrace.newest(str(tmp_path)) is None
    d = tmp_path / "fleet_busy" / "trace" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "a.xplane.pb").write_bytes(b"")
    assert progtrace.newest(str(tmp_path)) == str(d / "a.xplane.pb")
    monkeypatch.setattr(progtrace, "newest", lambda: str(d / "a.xplane.pb"))
    monkeypatch.setattr(progtrace, "read_trace", lambda p: pt)
    assert progtrace.load(types.SimpleNamespace(w0=1.0, w1=2.0)) is pt
    assert progtrace.load(types.SimpleNamespace(w0=1.0, w1=3.0)) is None


def test_a_file_the_decoder_cannot_read_gives_nothing(tmp_path,
                                                      monkeypatch):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"\x0b\x01")                 # wire type 3: not XSpace
    monkeypatch.setattr(progtrace, "newest", lambda: str(path))
    assert progtrace.load(types.SimpleNamespace(w0=1.0, w1=2.0)) is None
