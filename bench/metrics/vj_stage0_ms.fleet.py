"""Device time of the Haar cascade's first stage per dispatch of the
funnel step, in ms: the operations under the ``vj0`` name scope
(``core.cascade.compacting_cascade``), which scores every scan window of
every frame slot.  Extras: the dispatches counted, and the share of the
device's busy time in the window that falls on operations carrying one of
the program's scopes, with the largest operation that carries none.
Moves ``frame_p95_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.scoped_per_dispatch(pt, "vj0", r.w0, r.w1)
    if not got:
        return None
    share, rest = progtrace.scoped_share(pt, progtrace.PROGRAM_SCOPES,
                                         r.w0, r.w1)
    return got[0], {"dispatches": got[1], "scoped_busy": 100.0 * share,
                    "largest_unscoped": rest[0][0] if rest else None}
