"""Host time of the serving scheduler per tick, in ms: the harness's span
around ``StreamingServer.tick`` minus the ``dispatch`` events' durations
that ``repro.obs.Telemetry`` records inside it (gathering, DRR, admission,
result slicing, bookkeeping).  Moves ``frame_p95_s``."""


def read(r):
    s = r.system
    if not s["ticks"]:
        return None
    return 1e3 * (s["tick_s"] - s["dispatch_s"]) / s["ticks"]
