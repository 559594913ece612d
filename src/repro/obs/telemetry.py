"""The Telemetry facade handed to executors, sessions, and the server.

One object bundles the three planes — counters, trace, SLO ledger —
behind a single ``enabled`` switch.  Instrumented call sites hold an
``Optional[Telemetry]`` and gate on ``telemetry_on(tel)`` at
*construction* time wherever the instrumentation would change a traced
graph, so disabled telemetry is not "cheap", it is *absent*: the
jaxpr, dispatch count, and outputs are bit-identical to an
uninstrumented build (pinned by tests/test_obs.py).

``span`` is the one way to time a phase of host work: a record in the
trace with its parent span, and the same interval as a profiler
annotation (``repro.<kind>.<name>``) on the device trace's clock.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

from repro.obs.counters import CounterPanel
from repro.obs.ledger import SLOLedger
from repro.obs.trace import TraceRecorder


PROFILER_PREFIX = "repro."
# what a disabled Telemetry's span() returns: no record, no annotation
NULL_SPAN = contextlib.nullcontext()


class _Span:
    """One open span of ``Telemetry.span``; its record is emitted on exit,
    under the eid reserved on entry.  ``args`` may be added to while it is
    open."""

    __slots__ = ("tel", "kind", "name", "t", "tick", "sid", "args", "eid",
                 "parent", "_t0", "_note")

    def __init__(self, tel, kind, name, t, tick, sid, args):
        self.tel, self.kind, self.name = tel, str(kind), str(name)
        self.t, self.tick, self.sid, self.args = float(t), tick, sid, args

    def __enter__(self):
        import jax

        tel = self.tel
        now = time.perf_counter()
        if tel._open:
            self.parent = tel._open[-1].eid
            self.t += now - tel._origin
        else:
            self.parent = -1
            tel._origin = now
        self.eid = tel.trace.reserve()
        tel._open.append(self)
        self._t0 = now
        self._note = jax.profiler.TraceAnnotation(
            f"{PROFILER_PREFIX}{self.kind}.{self.name}")
        self._note.__enter__()
        return self

    def __exit__(self, *exc):
        self._note.__exit__(*exc)
        dur = time.perf_counter() - self._t0
        self.tel._open.pop()
        self.tel.trace.emit(self.kind, self.name, t=self.t, dur=dur,
                            tick=self.tick, sid=self.sid, parent=self.parent,
                            eid=self.eid, **self.args)
        return False


class Telemetry:
    def __init__(self, enabled: bool = True, *, trace_capacity: int = 65536,
                 run_id: Optional[str] = None, slo_s: Optional[float] = None):
        self.enabled = bool(enabled)
        self.counters = CounterPanel(enabled=self.enabled)
        self.trace = TraceRecorder(capacity=trace_capacity, run_id=run_id)
        self.ledger = SLOLedger(slo_s=slo_s)
        self._open: list = []          # the open spans, outermost first
        self._origin = 0.0             # perf_counter when the outermost began

    @property
    def run_id(self) -> str:
        return self.trace.run_id

    def emit(self, *args, **kwargs) -> int:
        """Trace passthrough (no-op returning -1 when disabled)."""
        if not self.enabled:
            return -1
        return self.trace.emit(*args, **kwargs)

    def span(self, kind: str, name: str, *, t: float, tick: int = -1,
             sid: str = "", **args):
        """A context manager that records a span of host work.

        The record's ``parent`` is the eid of the enclosing open span (-1
        if none), and its ``t`` is the caller's ``t`` plus the wall time
        since the outermost open span began, so children started inside a
        span called with one ``t`` get their real starts.  While open it
        holds ``jax.profiler.TraceAnnotation("repro.<kind>.<name>")``.
        The handle's ``args`` dict may be extended before the span closes.
        Disabled: the shared ``NULL_SPAN``, which records nothing and
        enters no annotation.
        """
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, kind, name, t, int(tick), str(sid), args)

    # ---- checkpoint plumbing ----------------------------------------------
    def state_dict(self) -> dict:
        return {"run_id": self.run_id,
                "counters": self.counters.state_dict(),
                "ledger": self.ledger.state_dict(),
                "trace_next_eid": self.trace._next_eid}

    def load_state(self, state: dict) -> None:
        state = state or {}
        self.counters.load_state(state.get("counters", {}))
        self.ledger.load_state(state.get("ledger", {}))
        # a restored run keeps its own run_id (it IS a new run) but
        # remembers the ancestry for cross-run correlation
        self.trace.emit("ckpt", "restore",
                        parent_run=state.get("run_id", ""))


def null_span(*_args, **_kwargs):
    """``Telemetry.span``'s stand-in where there is no enabled Telemetry."""
    return NULL_SPAN


def telemetry_on(tel: Optional[Telemetry]) -> bool:
    """The one construction-time gate every instrumented site uses."""
    return tel is not None and tel.enabled
