"""Host time of the serving scheduler per tick, in ms, read inside the
program: the ``serve.gather``, ``serve.settle`` and ``serve.admission``
spans of ``repro.obs`` within each ``serve.tick``, that is the tick's
time outside its ``dispatch`` spans.  The inside twin of
``tick_host_ms.fleet``.  Moves ``frame_p95_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.host_in(
        pt, ("serve.gather", "serve.settle", "serve.admission"), r.w0, r.w1)
    return got[0] if got else None
