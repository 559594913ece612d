"""Open-loop camera traffic: when each frame falls due, the loop that
offers frames to a server at those times, and the latency arithmetic.

Stream ``k`` of ``n`` sends one frame every ``1 / fps`` seconds, starting
``k * spread_s / n`` seconds after the loop starts: cameras that joined at
independent times, spread evenly, and the same schedule for every run
seed.  A frame's latency is
the wall time from when it was due to the end of the server tick that
returned its decision.  The loop keeps offering frames after the window
closes until every frame due in the window has a decision, so the last
frames of the window see the same load as the first.  Frames the server
sheds, or that never come back, are failed; they enter the percentile as
the longest wait in the run.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Schedule:
    n_streams: int
    fps: float
    lead_s: float             # open loop before the window opens
    seconds: float            # the measured window
    spread_s: float = 1.0     # the streams' first frames spread over this

    @property
    def window(self) -> tuple:
        return self.lead_s, self.lead_s + self.seconds

    def first(self, k: int) -> float:
        return k * self.spread_s / self.n_streams

    def due(self, k: int, j: int) -> float:
        """Seconds after the loop start at which stream k's frame j is due."""
        return self.first(k) + j / self.fps

    def n_due(self, k: int, t: float) -> int:
        """How many of stream k's frames are due at or before ``t``."""
        first = self.first(k)
        if t < first:
            return 0
        return int(math.floor((t - first) * self.fps + 1e-9)) + 1


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 100]) of a non-empty list."""
    xs = sorted(values)
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


@dataclasses.dataclass
class Outcome:
    due: dict                 # (sid, seq) -> due time
    decided: dict             # (sid, seq) -> decision time
    failed: set               # (sid, seq) shed or never decided
    shed: set                 # (sid, seq) the server shed
    completions: list         # (decision time, Completion) in order
    spans: list               # (label, start, end) host spans
    t_end: float              # when the loop stopped

    def window_frames(self, w0: float, w1: float) -> list:
        return [key for key, d in self.due.items() if w0 <= d < w1]

    def summary(self, w0: float, w1: float) -> dict:
        """attempted, failed, p95 latency and decision rate of the frames
        due in [w0, w1)."""
        keys = self.window_frames(w0, w1)
        lat = []
        n_failed = 0
        for key in keys:
            if key in self.decided and key not in self.failed:
                lat.append(self.decided[key] - self.due[key])
            else:
                n_failed += 1
                lat.append(self.t_end - self.due[key])
        n_done = sum(1 for t in self.decided.values() if w0 <= t < w1)
        return {"attempted": len(keys), "failed": n_failed,
                "frame_p95_s": percentile(lat, 95.0) if lat else float("nan"),
                "frames_per_s": n_done / (w1 - w0)}


def drive(server, sids, frame_of, sched: Schedule, clock, sleep, *,
          tick_s: float, drain_limit_s: float, on_window=None,
          annotate=None) -> Outcome:
    """Offer ``sched``'s frames to ``server`` and tick it every ``tick_s``.

    ``sids[k]`` names stream k, ``frame_of(k, j)`` is its frame j.
    ``server`` has ``enqueue(sid, frame, t)`` (returns the frame's sequence
    number), ``tick(t)`` (returns a report with ``completions`` and
    ``shed``) and ``unregister(sid)``; times handed to it are seconds after
    the loop start.  ``clock`` and ``sleep`` are the wall clock and its
    sleep, so a test can run the loop on a fake clock.  ``on_window(open)``
    is called as the window opens and closes; ``annotate(label)`` returns
    a context manager put around each host span (the profiler's
    annotation in a traced run).
    """
    annotate = annotate or (lambda label: contextlib.nullcontext())
    w0, w1 = sched.window
    t0 = clock()
    now = lambda: clock() - t0                       # noqa: E731
    due, decided, failed, shed = {}, {}, set(), set()
    completions, spans = [], []
    sent = [0] * len(sids)
    outstanding = 0               # window frames with no decision yet
    feeding, opened, closed = True, False, False
    next_tick = 0.0

    def settle(rep, t):
        nonlocal outstanding
        for comp in rep.completions:
            completions.append((t, comp))
            for seq in comp.seqs:
                key = (comp.sid, seq)
                if key not in decided and key not in failed:
                    decided[key] = t
                    if w0 <= due[key] < w1:
                        outstanding -= 1
        for rec in rep.shed:
            for seq in rec.seqs:
                key = (rec.sid, seq)
                if key not in decided and key not in failed:
                    failed.add(key)
                    shed.add(key)
                    if w0 <= due[key] < w1:
                        outstanding -= 1

    while True:
        t = now()
        if not opened and t >= w0:
            opened = True
            if on_window:
                on_window(True)
        if opened and not closed and t >= w1:
            closed = True
            if on_window:
                on_window(False)
        if feeding:
            s = now()
            with annotate("enqueue"):
                for k, sid in enumerate(sids):
                    for j in range(sent[k], sched.n_due(k, t)):
                        d = sched.due(k, j)
                        seq = server.enqueue(sid, frame_of(k, j), t=d)
                        due[(sid, seq)] = d
                        if w0 <= d < w1:
                            outstanding += 1
                    sent[k] = max(sent[k], sched.n_due(k, t))
            spans.append(("enqueue", s, now()))
            if closed and outstanding == 0:
                # every window frame is decided: drain what is left
                feeding = False
                for sid in sids:
                    server.unregister(sid)
        if not feeding and len(decided) + len(failed) == len(due):
            break
        if t - w1 > drain_limit_s:
            break
        if t < next_tick:
            s = now()
            with annotate("wait"):
                sleep(next_tick - t)
            spans.append(("wait", s, now()))
            continue
        s = now()
        with annotate("tick"):
            rep = server.tick(s)
        e = now()
        spans.append(("tick", s, e))
        settle(rep, e)
        next_tick = s + tick_s
    t_end = now()
    for key in due:
        if key not in decided:
            failed.add(key)
    if opened and not closed and on_window:
        on_window(False)
    return Outcome(due, decided, failed, shed, completions, spans, t_end)
