"""The host watch: ticks timed by wall and CPU clocks, and the JIT events
and collections that fall inside a window."""

import gc
import time

from bench import hostwatch


class Server:
    def __init__(self):
        self.calls = []

    def tick(self, t):
        self.calls.append(t)
        return "report"

    def enqueue(self, sid, frame, t):
        return 7


def test_timed_server_passes_calls_through_and_times_ticks():
    srv = Server()
    timed = hostwatch.TimedServer(srv)
    assert timed.tick(1.5) == "report" and srv.calls == [1.5]
    assert timed.enqueue("s0", None, t=0.0) == 7
    (t, wall, cpu, switched), = timed.ticks
    assert t == 1.5 and wall >= 0 and cpu >= 0 and switched >= 0


def test_report_counts_only_the_window(monkeypatch):
    t0 = 1000.0
    monkeypatch.setattr(hostwatch, "_EVENTS", [
        (t0 + 1.0, "/jax/core/compile/backend_compile_duration", 0.5),
        (t0 + 2.0, "/jax/compilation_cache/cache_retrieval_time_sec", 0.1),
        (t0 + 2.5, "gc2", 0.3),
        (t0 + 9.0, "/jax/core/compile/backend_compile_duration", 4.0),
        (t0 + 3.0, "/jax/some/other_duration", 1.0),
    ])
    timed = hostwatch.TimedServer(Server())
    timed.ticks = [(0.5, 9.0, 9.0, 0), (1.2, 1.4, 0.2, 0), (2.2, 1.9, 0.1, 3)]
    rep = hostwatch.report(timed, t0, 1.0, 5.0)
    assert rep["jit_events"] == 2 and abs(rep["jit_s"] - 0.6) < 1e-12
    assert rep["collections"] == 1 and rep["collection_max_s"] == 0.3
    assert (rep["slowest_tick_s"], rep["slowest_tick_cpu_s"],
            rep["slowest_tick_switched_out"]) == (1.9, 0.1, 3)
    assert abs(rep["slowest_tick_at_s"] - 1.2) < 1e-12


def test_install_records_a_collection_once_per_process():
    hostwatch.install()
    hostwatch.install()
    assert gc.callbacks.count(hostwatch._on_gc) == 1
    t = time.perf_counter()
    gc.collect()
    assert any(n == "gc2" and s >= t for s, n, _d in hostwatch._EVENTS)
