"""Device idle time inside the serving ticks, in ms per tick: the part of
each harness span around ``StreamingServer.tick`` in which no operation
ran on the chip.  It holds the host's side of a dispatch (the eager ops of
``serve/engine.cascade_serve``, the frames' transfer, result slicing) and
any pause of the host, which ``tick_host_ms.fleet`` cannot see because the
dispatch span covers them.  Moves ``frame_p95_s``."""

from bench import devtrace


def read(r):
    ticks = [(s, s + d) for label, s, d in r.host
             if label == "tick" and r.w0 <= s < r.w1]
    if not ticks:
        return None
    idle = 0
    for g0, g1 in devtrace.gaps(r.ops(), r.w0, r.w1):
        for t0, t1 in ticks:
            idle += max(0, min(g1, t1) - max(g0, t0))
    return idle * 1e-6 / len(ticks)
