"""Device time of the motion gate per dispatch, in ms: the operations
under the funnel step's ``motion`` name scope, and under ``scorer`` (the
serving tick's chunk scorer in ``cascade_serve``) where it runs inside a
jit; run eagerly, as the tick runs it, its operations carry no scope.
Moves ``frame_p95_s``."""

from bench import progtrace


def read(r):
    pt = progtrace.load(r)
    got = pt and progtrace.scoped_per_dispatch(pt, "motion|scorer", r.w0,
                                               r.w1)
    return got[0] if got else None
