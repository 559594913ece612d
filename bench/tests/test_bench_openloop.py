"""The open loop on a fake clock: due times, the drain, the 95th
percentile over every frame due in the window, shed frames as failed."""

import types

import pytest

from bench import openloop


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, d):
        self.t += d


class FakeServer:
    """Decides one full chunk per stream per tick after ``service`` seconds;
    a drained stream's partial chunk goes too.  ``shed_at`` sheds one
    queued frame at a given tick time."""

    def __init__(self, clock, chunk=2, service=0.5, shed_at=None):
        self.clock, self.chunk, self.service = clock, chunk, service
        self.queues, self.seq, self.draining = {}, {}, set()
        self.shed_at = shed_at

    def enqueue(self, sid, frame, t):
        s = self.seq.get(sid, 0)
        self.seq[sid] = s + 1
        self.queues.setdefault(sid, []).append(s)
        return s

    def unregister(self, sid):
        self.draining.add(sid)

    def tick(self, t):
        self.clock.t += self.service
        comps, shed = [], []
        if self.shed_at is not None and t == self.shed_at[0]:
            sid = self.shed_at[1]
            shed.append(types.SimpleNamespace(sid=sid,
                                              seqs=(self.queues[sid].pop(0),)))
        for sid, q in self.queues.items():
            n = self.chunk if len(q) >= self.chunk else (
                len(q) if sid in self.draining else 0)
            if n:
                comps.append(types.SimpleNamespace(sid=sid,
                                                   seqs=tuple(q[:n])))
                del q[:n]
        return types.SimpleNamespace(completions=comps, shed=shed)


def drive(clock, server, **kw):
    sched = openloop.Schedule(n_streams=2, fps=1.0, lead_s=2.0, seconds=4.0)
    windows = []
    out = openloop.drive(server, ["a", "b"], lambda k, j: None, sched, clock,
                         clock.sleep, tick_s=1.0, drain_limit_s=30.0,
                         on_window=windows.append, **kw)
    return sched, out, windows


def test_schedule_spreads_streams_over_a_period():
    s = openloop.Schedule(n_streams=4, fps=2.0, lead_s=1.0, seconds=3.0,
                          spread_s=0.5)
    assert s.window == (1.0, 4.0)
    assert s.due(1, 0) == 0.125 and s.due(3, 2) == 1.375
    assert [s.n_due(1, t) for t in (0.1, 0.125, 0.6, 0.625)] == [0, 1, 1, 2]


def test_percentile_is_nearest_rank():
    assert openloop.percentile([3, 1, 2, 4], 95) == 4
    assert openloop.percentile(list(range(1, 101)), 95) == 95


def test_latency_counts_from_due_time_and_drains_every_frame():
    clock = FakeClock()
    sched, out, windows = drive(clock, FakeServer(clock))
    assert windows == [True, False]
    # every frame offered, including those due after the window, is
    # decided before the loop ends
    assert set(out.decided) == set(out.due) and not out.failed
    # stream a: frames due j, decided at j + 1.5 (j even) or j + 0.5;
    # stream b: due j + 0.5, decided at j + 2.5 (j even) or j + 1.5
    lat = {key: out.decided[key] - d for key, d in out.due.items()}
    assert [lat[("a", j)] for j in range(2, 6)] == [1.5, 0.5, 1.5, 0.5]
    assert [lat[("b", j)] for j in range(2, 6)] == [2.0, 1.0, 2.0, 1.0]
    s = out.summary(*sched.window)
    assert s == {"attempted": 8, "failed": 0, "frame_p95_s": 2.0,
                 "frames_per_s": 2.0}


def test_shed_frames_are_failed_and_enter_the_tail():
    clock = FakeClock()
    sched, out, _ = drive(clock, FakeServer(clock, shed_at=(3.0, "b")))
    s = out.summary(*sched.window)
    assert out.shed == {("b", 2)} and ("b", 2) in out.failed
    assert s["failed"] == 1 and s["attempted"] == 8
    # a failed frame waits until the loop ends, the longest wait of all
    assert s["frame_p95_s"] == pytest.approx(out.t_end - out.due[("b", 2)])
