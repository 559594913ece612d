"""What the host did inside a run's window that the device trace cannot
explain: tracing, compiling or loading a compiled program, garbage
collections, and the slowest server tick with the CPU time the process
spent in it.  A run prints it on standard error, so a run whose latency
reads far off can be told apart from the others (a compile or a
collection in the window, or a tick in which the process was not
running)."""

from __future__ import annotations

import gc
import resource
import time

_EVENTS: list = []            # (perf_counter, name, seconds)
_installed = False


def _on_duration(name, seconds, **_kw):
    _EVENTS.append((time.perf_counter(), name, seconds))


_gc_start = [0.0]


def _on_gc(phase, info):
    if phase == "start":
        _gc_start[0] = time.perf_counter()
    else:
        t = _gc_start[0]
        _EVENTS.append((t, f"gc{info['generation']}",
                        time.perf_counter() - t))


def install():
    """Listen to JAX's tracing, compile and compile-cache durations and to
    Python's collections, once per process."""
    global _installed
    if _installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    gc.callbacks.append(_on_gc)
    _installed = True


class TimedServer:
    """A server whose ticks are timed by the wall clock and by the
    process's CPU time, with the times the process was switched out
    against its will (another process took its core)."""

    def __init__(self, server):
        self._server = server
        self.ticks = []       # (t, wall s, cpu s, involuntary switches)

    def __getattr__(self, name):
        return getattr(self._server, name)

    def tick(self, t):
        w, c = time.perf_counter(), time.process_time()
        n = resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
        rep = self._server.tick(t)
        self.ticks.append((t, time.perf_counter() - w,
                           time.process_time() - c,
                           resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
                           - n))
        return rep


def report(timed: TimedServer, t0: float, w0: float, w1: float) -> dict:
    """JIT events and collections in the window ``[w0, w1)`` (seconds
    after ``t0`` on ``time.perf_counter``) and its slowest tick."""
    evs = [(t - t0, n, s) for t, n, s in _EVENTS if w0 <= t - t0 < w1]
    comp = [s for _t, n, s in evs
            if n.startswith(("/jax/core/compile", "/jax/compilation_cache"))]
    gcs = [s for _t, n, s in evs if n.startswith("gc")]
    ticks = [tk for tk in timed.ticks if w0 <= tk[0] < w1]
    slow = max(ticks, key=lambda tk: tk[1], default=(0.0, 0.0, 0.0, 0))
    return {"jit_events": len(comp), "jit_s": sum(comp),
            "collections": len(gcs), "collection_max_s": max(gcs, default=0.0),
            "slowest_tick_s": slow[1], "slowest_tick_cpu_s": slow[2],
            "slowest_tick_switched_out": slow[3],
            "slowest_tick_at_s": slow[0] - w0}
