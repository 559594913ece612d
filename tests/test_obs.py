"""Fleet telemetry plane (DESIGN.md §15).

Pins the three contracts the ISSUE names verbatim plus the satellite-2
ordering fix:

* **counters-off bit-identity** — a telemetry-disabled (or absent)
  build is the uninstrumented build: same jaxpr, bit-identical outputs
  at every offload cut, and an enabled build never perturbs the real
  outputs either (counters are *extra* aux, never a rewrite);
* **counter conservation across checkpoint/restore** — CounterPanel /
  Telemetry state round-trips exactly, and a StreamingServer restored
  mid-drive carries its counter totals and SLO ledger forward;
* **trace ids unique per run** — eids are unique and monotone within a
  recorder, run_ids are distinct across recorders, and both survive the
  JSONL round trip;
* **sorted-sid shed/audit order** (satellite 2 regression) —
  ``TickReport.shed`` and ``seq_audit`` walk streams in sorted-sid
  order regardless of registration order.

Unit coverage for the obs primitives (rung_key, SLOLedger attribution,
bench.v1 diffing, Perfetto export, kill-chain reconstruction) rides in
the same file so the whole §15 surface lives in one place.
"""

import json
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.obs import (CounterPanel, SLOLedger, Telemetry, TraceRecorder,
                       rung_key)
from repro.obs.bench import (bench_record, diff_bench, format_diff,
                             load_bench, write_bench)
from repro.obs.counters import (ALLOWED_DTYPES, TELEMETRY_AUX, graph_counter,
                                graph_counters, telemetry_decl)
from repro.obs.trace import TraceRecord, kind_counts, perfetto_events
from repro.obs.telemetry import telemetry_on


# ---------------------------------------------------------------------------
# shared FA workload (mirrors tests/test_serving_chaos.py's fixture)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fa_setup():
    from benchmarks.workloads import fa_cascade, fa_scan
    from repro.camera.face_nn import train_face_nn
    from repro.camera.pipelines import FaceAuthExecutor
    from repro.camera.synthetic import face_dataset, security_video

    frames, _truth = security_video(n_frames=10, motion_frames=5, seed=1)
    casc = fa_cascade(smoke=True)
    X, y, _ = face_dataset(n_per_class=80, seed=3)
    nn = train_face_nn(X, y, steps=60)
    sf, st, ad = fa_scan(True)

    def make(telemetry=None):
        ex = FaceAuthExecutor(casc, nn, frames.shape[1], frames.shape[2],
                              scale_factor=sf, step=st, adaptive=ad,
                              telemetry=telemetry)
        ex.calibrate(frames)
        return ex

    ex = make()
    return make, ex, frames, ex(jnp.asarray(frames))


def _server(ex, *, chunk=2, capacity=2, chaos=None, telemetry=None, **kw):
    from repro.camera.serve import ServeConfig, StreamingServer

    kw.setdefault("max_queue_s", 100.0)
    cfg = ServeConfig(chunk=chunk, capacity=capacity, tick_s=1.0, **kw)
    return StreamingServer(ex, config=cfg, chaos=chaos, telemetry=telemetry)


FA_FIELDS = ("motion", "n_windows", "n_auth", "scores", "window_id",
             "window_valid", "auth", "windows_dropped", "motion_dropped",
             "cascade_dropped")


def _same_result(a, b):
    return all(bool(np.array_equal(np.asarray(getattr(a, f)),
                                   np.asarray(getattr(b, f))))
               for f in FA_FIELDS)


# ---------------------------------------------------------------------------
# ISSUE contract 1: counters-off path is bit-identical at every cut
# ---------------------------------------------------------------------------


class TestCountersOffBitIdentity:
    def test_disabled_executor_traces_identical_jaxpr(self, fa_setup):
        make, ex, frames, base = fa_setup
        off = make(telemetry=Telemetry(enabled=False))
        fj = jnp.asarray(frames)
        jx_plain = jax.make_jaxpr(ex._funnel)(fj, *ex._consts)
        jx_off = jax.make_jaxpr(off._funnel)(fj, *off._consts)
        assert str(jx_plain) == str(jx_off)
        # enabled builds do add aux outputs (the counters are real) ...
        on = make(telemetry=Telemetry(enabled=True))
        jx_on = jax.make_jaxpr(on._funnel)(fj, *on._consts)
        assert str(jx_on) != str(jx_plain)
        # ... but never perturb the real outputs
        assert _same_result(base, off(fj))
        assert _same_result(base, on(fj))

    def test_session_bit_identical_at_every_cut(self, fa_setup):
        from repro.camera.offload import (BACKSCATTER,
                                          FaceAuthOffloadExecutor,
                                          OffloadSession)

        make, ex, frames, base = fa_setup
        fj = jnp.asarray(frames)
        for cut in FaceAuthOffloadExecutor.CUTS:
            off = FaceAuthOffloadExecutor(ex, cut, bits=8)
            want, _ = off(fj)
            for tel in (None, Telemetry(enabled=False),
                        Telemetry(enabled=True)):
                got, rec = OffloadSession(off, link=BACKSCATTER,
                                          telemetry=tel,
                                          sid="cam0").send(fj)
                assert rec.delivered
                assert _same_result(want, got), (cut, tel)

    def test_enabled_session_counts_enabled_only(self, fa_setup):
        from repro.camera.offload import (BACKSCATTER,
                                          FaceAuthOffloadExecutor,
                                          OffloadSession)

        make, ex, frames, base = fa_setup
        fj = jnp.asarray(frames)
        off = FaceAuthOffloadExecutor(ex, "nn", bits=8)
        tel_off = Telemetry(enabled=False)
        OffloadSession(off, link=BACKSCATTER, telemetry=tel_off).send(fj)
        assert tel_off.counters.totals() == {}
        assert len(tel_off.trace) == 0
        tel = Telemetry(enabled=True)
        OffloadSession(off, link=BACKSCATTER, telemetry=tel,
                       sid="cam0").send(fj)
        tot = tel.counters.totals()
        assert tot["offload.sends"] == 1
        assert tot["offload.delivered"] == 1
        assert tot["offload.attempts"] == 1
        assert tot["offload.bytes_on_air"] > 0
        (link_ev,) = tel.trace.records("link")
        assert link_ev.sid == "cam0" and link_ev.args["delivered"]
        assert tel.ledger.keys() == [("cam0", "nn@8")]

    def test_funnel_counters_match_real_outputs(self, fa_setup):
        make, ex, frames, base = fa_setup
        tel = Telemetry(enabled=True)
        on = make(telemetry=tel)
        res = on(jnp.asarray(frames))
        tot = tel.counters.totals()
        assert tot["fa.windows"] == int(np.sum(np.asarray(res.n_windows)))
        assert tot["fa.auth"] == int(np.sum(np.asarray(res.n_auth)))
        assert tot["fa.motion_dropped"] == int(res.motion_dropped)
        assert tot["fa.cascade_dropped"] == int(
            np.sum(np.asarray(res.cascade_dropped)))


# ---------------------------------------------------------------------------
# ISSUE contract 2: counter totals conserve across checkpoint/restore
# ---------------------------------------------------------------------------


class TestCounterConservation:
    def test_panel_state_roundtrip_exact(self):
        p = CounterPanel()
        p.bump("a", 3)
        p.add("a", jnp.asarray(4, jnp.int32))      # device-lazy path
        p.add("b", jnp.asarray(7, jnp.int32))
        before = p.totals()
        assert before == {"a": 7, "b": 7}
        q = CounterPanel()
        q.load_state(p.state_dict())
        assert q.totals() == before
        q.bump("a")                                 # keeps accumulating
        assert q.totals()["a"] == 8

    def test_panel_merge_conserves_sum(self):
        a, b = CounterPanel(), CounterPanel()
        a.bump("x", 2)
        b.bump("x", 5)
        b.bump("y", 1)
        a.merge(b)
        assert a.totals() == {"x": 7, "y": 1}

    def test_disabled_panel_stays_empty(self):
        p = CounterPanel(enabled=False)
        p.bump("a")
        p.add("b", jnp.asarray(1, jnp.int32))
        out = p.consume({"tel_c": jnp.asarray(2, jnp.int32), "real": 9})
        assert out == {"real": 9}                  # tel_ keys still popped
        assert p.totals() == {}

    def test_telemetry_state_roundtrip(self):
        tel = Telemetry(enabled=True)
        tel.counters.bump("serve.dispatches", 11)
        tel.ledger.observe_latency("a", ("nn", 8), 0.5)
        tel.ledger.observe_auth("a", ("nn", 8), np.array([1, 0, 1]),
                                np.array([1, 1, 1]))
        tel2 = Telemetry(enabled=True)
        tel2.load_state(tel.state_dict())
        assert tel2.counters.totals() == tel.counters.totals()
        assert tel2.ledger.flip_counts() == (1, 3)
        assert tel2.ledger.keys() == tel.ledger.keys()
        # the restored run records its ancestry but keeps its own run_id
        (rst,) = tel2.trace.records("ckpt")
        assert rst.args["parent_run"] == tel.run_id
        assert tel2.run_id != tel.run_id

    def test_server_counters_survive_restore(self, fa_setup, tmp_path):
        make, ex, frames, base = fa_setup
        tel = Telemetry(enabled=True)
        srv = _server(ex, telemetry=tel)
        srv.register("a", fps=1.0)
        for i in range(3):
            srv.enqueue("a", frames[i], t=float(i) * 0.1)
        srv.tick(1.0)
        before = tel.counters.totals()
        assert before.get("serve.dispatches", 0) >= 1
        srv.checkpoint(str(tmp_path))

        from repro.camera.serve import StreamingServer

        tel2 = Telemetry(enabled=True)
        srv2 = StreamingServer.restore(str(tmp_path), ex,
                                       config=srv.cfg, telemetry=tel2)
        assert tel2.counters.totals() == before
        srv2.enqueue("a", frames[3], t=1.5)
        srv2.tick(2.0)
        after = tel2.counters.totals()
        # totals continue from the restored baseline, never reset
        assert after["serve.dispatches"] > before["serve.dispatches"]
        assert srv2.seq_audit()["ok"]

    def test_restore_without_telemetry_key_is_fine(self, fa_setup, tmp_path):
        # pre-PR-10 checkpoints carry no "telemetry" extra; restoring
        # with telemetry enabled must start from zero, not crash
        make, ex, frames, base = fa_setup
        srv = _server(ex)                          # no telemetry recorded
        srv.register("a", fps=1.0)
        srv.enqueue("a", frames[0], t=0.0)
        srv.tick(1.0)
        srv.checkpoint(str(tmp_path))

        from repro.camera.serve import StreamingServer

        tel = Telemetry(enabled=True)
        srv2 = StreamingServer.restore(str(tmp_path), ex,
                                       config=srv.cfg, telemetry=tel)
        srv2.enqueue("a", frames[1], t=1.5)
        srv2.tick(2.0)
        assert tel.counters.totals().get("serve.dispatches", 0) >= 1


# ---------------------------------------------------------------------------
# ISSUE contract 3: trace ids unique per run
# ---------------------------------------------------------------------------


class TestTraceIds:
    def test_eids_unique_and_monotone(self):
        tr = TraceRecorder()
        eids = [tr.emit("tick", f"t{i}", t=float(i)) for i in range(50)]
        assert eids == sorted(eids) == list(range(50))
        assert len({r.eid for r in tr.records()}) == 50
        assert all(r.run_id == tr.run_id for r in tr.records())

    def test_run_ids_distinct_across_recorders(self):
        ids = {TraceRecorder().run_id for _ in range(8)}
        assert len(ids) == 8

    def test_jsonl_roundtrip_preserves_ids(self, tmp_path):
        tr = TraceRecorder()
        tr.emit("tick", "t0", t=0.0, dur=1.0, tick=0, sid="a", n=3)
        tr.emit("link", "send[nn@8]", t=0.5, sid="a", attempts=2)
        path = str(tmp_path / "trace.jsonl")
        assert tr.to_jsonl(path) == 2
        back = TraceRecorder.load_jsonl(path)
        assert back == tr.records()
        assert kind_counts(back) == {"link": 1, "tick": 1}

    def test_ring_keeps_newest_and_counts_drops(self):
        tr = TraceRecorder(capacity=4)
        for i in range(10):
            tr.emit("tick", f"t{i}")
        assert len(tr) == 4
        assert tr.dropped == 6
        assert [r.eid for r in tr.records()] == [6, 7, 8, 9]

    def test_perfetto_export_well_formed(self, tmp_path):
        tr = TraceRecorder()
        tr.emit("tick", "t0", t=1.0, dur=0.5, tick=0)
        tr.emit("chaos", "device_kill", t=1.25, tick=0, device=1)
        path = str(tmp_path / "trace.json")
        assert tr.export_perfetto(path) == 2
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        assert doc["otherData"]["run_id"] == tr.run_id
        span = next(e for e in evs if e["cat"] == "tick")
        assert span["ph"] == "X" and span["dur"] == pytest.approx(0.5e6)
        assert span["ts"] == pytest.approx(1e6)
        inst = next(e for e in evs if e["cat"] == "chaos")
        assert inst["ph"] == "i"
        # distinct kinds land on distinct tid lanes, one pid per run
        assert span["tid"] != inst["tid"]
        assert {e["pid"] for e in evs} == {1}
        assert all("eid" in e["args"] for e in evs)

    def test_disabled_telemetry_emit_is_noop(self):
        tel = Telemetry(enabled=False)
        assert tel.emit("tick", "t0") == -1
        assert len(tel.trace) == 0
        assert not telemetry_on(tel) and not telemetry_on(None)
        assert telemetry_on(Telemetry(enabled=True))


# ---------------------------------------------------------------------------
# nested spans (Telemetry.span) and the serving tick's phases
# ---------------------------------------------------------------------------

TICK_CHILDREN = {"gather", "stage", "cascade", "readback", "settle",
                 "admission"}


def _drive(ex, frames, telemetry):
    """Two streams, three ticks; the completions' results per tick."""
    srv = _server(ex, telemetry=telemetry)
    for sid in ("a", "b"):
        srv.register(sid, fps=1.0)
    out = []
    for k in range(3):
        for j, sid in enumerate(("a", "b")):
            for i in range(2):
                srv.enqueue(sid, frames[(4 * k + 2 * j + i) % len(frames)],
                            t=float(k) + 0.1 * i)
        rep = srv.tick(float(k) + 1.0)
        out.append([(c.sid, c.seqs, c.kind,
                     {key: np.asarray(v) for key, v in c.result.items()})
                    for c in rep.completions])
    return out


class TestSpans:
    def test_nested_spans_name_parents_and_real_starts(self, monkeypatch):
        import time as _time

        clock = iter([10.0, 10.5, 11.0, 12.0, 14.0, 14.25])
        monkeypatch.setattr(_time, "perf_counter", lambda: next(clock))
        tel = Telemetry(enabled=True)
        with tel.span("serve", "tick", t=100.0, tick=3) as outer:
            with tel.span("serve", "gather", t=100.0, tick=3):
                pass
            with tel.span("dispatch", "group:local", t=100.0,
                          tick=3) as d:
                d.args.update(n_chunks=2)
        recs = {r.name: r for r in tel.trace.records()}
        assert recs["tick"].eid == outer.eid and recs["tick"].parent == -1
        assert recs["gather"].parent == outer.eid
        assert recs["group:local"].parent == outer.eid
        # starts: the caller's t plus the time since the outermost began
        assert recs["tick"].t == 100.0 and recs["tick"].dur == 4.25
        assert recs["gather"].t == 100.5 and recs["gather"].dur == 0.5
        assert recs["group:local"].t == 102.0
        assert recs["group:local"].args == {"n_chunks": 2}
        assert recs["tick"].tick == 3
        # an eid is taken when the span opens: parents before children
        assert recs["tick"].eid < recs["gather"].eid < recs["group:local"].eid

    def test_disabled_span_is_the_shared_null_context(self, monkeypatch):
        from repro.obs.telemetry import NULL_SPAN

        def boom(*a, **k):
            raise AssertionError("TraceAnnotation with telemetry off")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
        tel = Telemetry(enabled=False)
        span = tel.span("serve", "tick", t=0.0)
        assert span is NULL_SPAN
        with span as handle:
            assert handle is None
        assert len(tel.trace) == 0

    def test_record_without_parent_loads_as_top_level(self, tmp_path):
        d = {"eid": 4, "run_id": "r", "kind": "tick", "name": "t0",
             "t": 1.0, "dur": 0.5, "tick": 0, "sid": "", "args": {}}
        assert TraceRecord.from_json(d).parent == -1
        tr = TraceRecorder()
        tr.emit("serve", "gather", t=0.0, parent=7)
        path = str(tmp_path / "t.jsonl")
        tr.to_jsonl(path)
        assert TraceRecorder.load_jsonl(path)[0].parent == 7
        (ev,) = perfetto_events(tr.records())
        assert ev["args"]["parent"] == 7

    def test_disabled_tick_makes_no_annotation_and_matches_enabled(
            self, fa_setup, monkeypatch):
        make, ex, frames, base = fa_setup
        on = _drive(ex, frames, Telemetry(enabled=True))

        def boom(*a, **k):
            raise AssertionError("TraceAnnotation with telemetry off")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
        for tel in (None, Telemetry(enabled=False)):
            off = _drive(ex, frames, tel)
            assert len(off) == len(on)
            for got, want in zip(off, on):
                assert [c[:3] for c in got] == [c[:3] for c in want]
                for (_s, _q, _k, r1), (_s2, _q2, _k2, r2) in zip(got, want):
                    assert r1.keys() == r2.keys()
                    assert all(np.array_equal(r1[k], r2[k]) for k in r1)
            if tel is not None:
                assert len(tel.trace) == 0

    def test_enabled_tick_records_its_phases_as_children(self, fa_setup):
        make, ex, frames, base = fa_setup
        tel = Telemetry(enabled=True)
        _drive(ex, frames, tel)
        recs = tel.trace.records()
        by_eid = {r.eid: r for r in recs}
        ticks = [r for r in recs if (r.kind, r.name) == ("serve", "tick")]
        assert len(ticks) == 3
        for tick in ticks:
            kids = [r for r in recs if r.parent == tick.eid]
            disp = [r for r in kids if r.kind == "dispatch"]
            assert len(disp) == 1 and disp[0].name == "group:local"
            grand = [r for r in recs if r.parent == disp[0].eid]
            assert [r.name for r in grand] == ["stage", "cascade",
                                               "readback"]
            names = {r.name for r in kids if r.kind == "serve"}
            assert names | {r.name for r in grand} == TICK_CHILDREN
            assert all(r.tick == tick.tick for r in kids + grand)
            for parent, children in ((tick, kids), (disp[0], grand)):
                assert sum(r.dur for r in children) <= parent.dur
                assert all(parent.t <= r.t and r.t + r.dur
                           <= parent.t + parent.dur + 1e-9
                           for r in children)
            assert all(by_eid[r.parent] is tick for r in kids)
        # the dispatch record keeps its fields, and the tick record stays
        (d0,) = [r for r in recs if r.kind == "dispatch"
                 and r.parent == ticks[0].eid]
        assert {"n_chunks", "bucket", "n_served",
                "n_capacity_dropped"} <= set(d0.args)
        assert len(tel.trace.records("tick")) == 3

    def test_lowered_batch_step_names_the_funnel_layers(self, fa_setup):
        import re

        make, ex, frames, base = fa_setup
        step = ex.batch_step(2, 2)
        fr = jnp.asarray(np.stack([frames[:2], frames[2:4]]))
        text = jax.jit(step._core).lower(
            fr, jnp.ones((2,), bool), *step._consts).as_text(
                debug_info=True)
        scopes = set()
        for path in re.findall(r'loc\("([^"]+)"', text):
            # vmap marks a scope inside it as vmap(<scope>)
            scopes.update(re.sub(r"^(?:vmap\()+(\w+)\)+$", r"\1", part)
                          for part in path.split("/")[:-1])
        assert {"motion", "detect", "integral", "vj0", "gather", "nn",
                "scatter"} <= scopes
        stages = int(len(ex.det.tables.stage_sizes))
        assert {f"vj{i}" for i in range(stages)} <= scopes


# ---------------------------------------------------------------------------
# satellite 2 regression: sorted-sid shed + seq_audit order
# ---------------------------------------------------------------------------


class TestSortedSidOrder:
    def test_shed_and_audit_sorted_regardless_of_registration(self,
                                                              fa_setup):
        make, ex, frames, base = fa_setup
        srv = _server(ex, max_queue_frames=2)
        for sid in ("zeta", "alpha", "mid"):       # non-sorted insertion
            srv.register(sid, fps=1.0)
        for k in range(5):
            for sid in ("zeta", "alpha", "mid"):
                srv.enqueue(sid, frames[k % len(frames)], t=float(k))
        rep = srv.tick(1.0)
        shed_sids = [s.sid for s in rep.shed]
        assert shed_sids == sorted(shed_sids) == ["alpha", "mid", "zeta"]
        assert all(s.seqs == tuple(sorted(s.seqs)) for s in rep.shed)
        audit = srv.seq_audit()
        assert audit["ok"]
        assert list(audit["streams"]) == sorted(audit["streams"])

    def test_order_stable_after_churn_reregister(self, fa_setup):
        # the pre-PR-10 bug: dict insertion order diverges from audit
        # order once a stream is unregistered, reaped, and re-registered
        make, ex, frames, base = fa_setup
        srv = _server(ex, max_queue_frames=2)
        for sid in ("a", "b"):
            srv.register(sid, fps=1.0)
        srv.enqueue("a", frames[0], t=0.0)
        srv.unregister("a")
        srv.tick(1.0)                              # drains + reaps "a"
        srv.register("a", fps=1.0)                 # now inserted AFTER "b"
        for k in range(5):
            for sid in ("a", "b"):
                srv.enqueue(sid, frames[k % len(frames)], t=1.0 + k)
        rep = srv.tick(2.0)
        assert [s.sid for s in rep.shed] == ["a", "b"]
        assert srv.seq_audit()["ok"]


# ---------------------------------------------------------------------------
# SLO ledger: rung keys + flip attribution
# ---------------------------------------------------------------------------


class TestLedger:
    def test_rung_key_canonicalization(self):
        assert rung_key(("nn", 16)) == "nn@16"
        assert rung_key(("vj", None)) == "vj@raw"
        assert rung_key(("on_node", None)) == "on_node"
        assert rung_key("on_node") == "on_node"
        assert rung_key((None, None)) == "local"
        assert rung_key(None) == "none"

    def test_flip_attribution_by_rung(self):
        led = SLOLedger()
        ref = np.array([1, 1, 0, 1])
        led.observe_auth("a", ("nn", 16), ref, ref)          # clean rung
        led.observe_auth("a", ("nn", 8), np.array([1, 0, 0, 0]), ref)
        assert led.flip_counts(rung=("nn", 16)) == (0, 4)
        assert led.flip_counts(rung=("nn", 8)) == (2, 4)
        assert led.flip_counts(sid="a") == (2, 8)
        assert led.flip_rate(rung=("nn", 8)) == pytest.approx(0.5)

    def test_dropped_frame_counts_all_units_flipped(self):
        led = SLOLedger()
        led.observe_auth("a", "on_node", None, np.zeros(6, bool))
        assert led.flip_counts() == (6, 6)
        assert led.flip_rate() == 1.0

    def test_latency_percentiles_and_slo(self):
        led = SLOLedger(slo_s=0.1)
        for i in range(10):
            led.observe_latency("a", ("nn", 8), 0.01 * (i + 1))
        pct = led.latency_percentiles(sid="a")
        assert pct["p50"] == pytest.approx(0.055)
        assert led.slo_violations() == 0
        led.observe_latency("a", "on_node", 0.5)
        assert led.slo_violations() == 1
        assert math.isnan(led.latency_percentiles(sid="ghost")["p50"])

    def test_report_rows_and_state_roundtrip(self):
        led = SLOLedger(slo_s=0.2)
        led.observe_latency("a", ("nn", 8), 0.05)
        led.observe_auth("a", ("nn", 8), np.array([1]), np.array([0]))
        led2 = SLOLedger()
        led2.load_state(led.state_dict())
        assert led2.slo_s == 0.2
        (row,) = led2.report()
        assert row["sid"] == "a" and row["rung"] == "nn@8"
        assert row["flipped"] == 1 and row["compared"] == 1
        assert row["p50"] == pytest.approx(0.05)


# ---------------------------------------------------------------------------
# counters registry + dtype law (analyzer O001/O003 ground truth)
# ---------------------------------------------------------------------------


class TestCounterPrimitives:
    def test_graph_counter_dtype_law(self):
        assert graph_counter(3).dtype == jnp.int32
        assert graph_counter(3, "uint32").dtype == jnp.uint32
        with pytest.raises(ValueError, match="int32"):
            graph_counter(3, "int64")
        with pytest.raises(ValueError):
            graph_counter(3, "float32")

    def test_graph_counters_prefix_and_shape(self):
        aux = graph_counters(windows=jnp.arange(3).sum(), auth=2)
        assert set(aux) == {"tel_windows", "tel_auth"}
        assert all(v.shape == () for v in aux.values())

    def test_telemetry_decl_parameterized_names(self):
        assert telemetry_decl("face_auth.funnel") == \
            TELEMETRY_AUX["face_auth.funnel"]
        assert telemetry_decl("fa_offload[nn,8].node") == ()
        assert telemetry_decl("serve.batch_step[3x4]") == \
            TELEMETRY_AUX["serve.batch_step"]
        assert telemetry_decl("codec.roundtrip[b8]") == ()
        assert telemetry_decl("rogue.target") is None

    def test_registry_dtypes_all_legal(self):
        for stem, decl in TELEMETRY_AUX.items():
            for cname, dtype in decl:
                assert dtype in ALLOWED_DTYPES, (stem, cname)


# ---------------------------------------------------------------------------
# bench.v1 schema + machine diff
# ---------------------------------------------------------------------------


class TestBenchSchema:
    ROWS = [("fa", "speedup", "3.2", "vs loop"),
            ("fa", "parity", "identical", "")]

    def test_record_shape(self):
        rec = bench_record("fa", self.ROWS, 1.5, smoke=True)
        assert rec["schema"] == "bench.v1"
        assert rec["section"] == "fa" and rec["smoke"] is True
        assert rec["wall_s"] == 1.5
        assert all(isinstance(c, str) for row in rec["rows"] for c in row)

    def test_diff_ignores_volatile_keys(self):
        a = bench_record("fa", self.ROWS, 1.5, smoke=True, generated_at=1.0)
        b = bench_record("fa", self.ROWS, 9.9, smoke=False, generated_at=2.0)
        d = diff_bench(a, b)
        assert d["identical"]
        assert "identical" in format_diff(d)

    def test_diff_flags_changed_added_removed(self):
        a = bench_record("fa", self.ROWS, 1.0)
        b = bench_record("fa", [("fa", "speedup", "2.9", "vs loop"),
                                ("fa", "new_metric", "1", "")], 1.0)
        d = diff_bench(a, b)
        assert not d["identical"]
        assert d["changed"] == [{"key": ["fa", "speedup"],
                                 "a": "3.2", "b": "2.9"}]
        assert d["added"] == [["fa", "new_metric"]]
        assert d["removed"] == [["fa", "parity"]]
        txt = format_diff(d)
        assert "~ fa/speedup: 3.2 -> 2.9" in txt

    def test_load_upgrades_legacy_files(self, tmp_path):
        legacy = tmp_path / "BENCH_old.json"
        legacy.write_text(json.dumps(
            {"section": "fa", "wall_s": 2.0, "rows": self.ROWS}))
        rec = load_bench(str(legacy))
        assert rec["schema"] == "legacy"
        fresh = bench_record("fa", self.ROWS, 1.0)
        write_bench(str(tmp_path / "BENCH_new.json"), fresh)
        assert diff_bench(rec, load_bench(
            str(tmp_path / "BENCH_new.json")))["identical"]


# ---------------------------------------------------------------------------
# kill-chain reconstruction from records alone (§15 acceptance shape)
# ---------------------------------------------------------------------------


def _chain_records(with_failover=True):
    recs = [
        dict(kind="tick", name="tick", tick=1, args={"n_served": 2}),
        dict(kind="chaos", name="device_kill", tick=2, args={"device": 1}),
        dict(kind="failover", name="reshard", tick=2,
             args={}) if with_failover else None,
        dict(kind="ladder", name="descend", tick=3, args={}),
        dict(kind="chaos", name="device_restore", tick=5,
             args={"device": 1}),
        dict(kind="tick", name="tick", tick=6, args={"n_served": 2}),
    ]
    return [r for r in recs if r is not None]


class TestKillChain:
    def test_full_chain_detected(self):
        from benchmarks.serving_chaos import kill_chain

        chain = kill_chain(_chain_records())
        assert chain["ok"]
        assert chain["kill_tick"] == 2 and chain["failover_tick"] == 2
        assert chain["descend_tick"] == 3 and chain["restore_tick"] == 5
        assert chain["recovered_tick"] == 6

    def test_missing_link_breaks_chain(self):
        from benchmarks.serving_chaos import kill_chain

        assert not kill_chain(_chain_records(with_failover=False))["ok"]
        assert not kill_chain([])["ok"]

    def test_accepts_trace_records(self):
        from benchmarks.serving_chaos import kill_chain

        recs = [TraceRecord(eid=i, run_id="r", kind=d["kind"],
                            name=d["name"], t=float(d["tick"]), dur=0.0,
                            tick=d["tick"], sid="", args=d["args"])
                for i, d in enumerate(_chain_records())]
        assert kill_chain(recs)["ok"]


# ---------------------------------------------------------------------------
# dashboard + CLI render without a server in the loop
# ---------------------------------------------------------------------------


class TestReporting:
    def _tel(self):
        tel = Telemetry(enabled=True, slo_s=0.2)
        tel.counters.bump("serve.dispatches", 4)
        tel.emit("tick", "tick", t=0.0, dur=1.0, tick=0)
        tel.ledger.observe_latency("a", ("nn", 8), 0.05)
        tel.ledger.observe_auth("a", ("nn", 8), np.array([1]),
                                np.array([1]))
        return tel

    def test_fleet_dashboard_renders(self):
        from repro.obs import fleet_dashboard

        tel = self._tel()
        txt = fleet_dashboard(counters=tel.counters.totals(),
                              ledger=tel.ledger,
                              records=tel.trace.records(),
                              run_id=tel.run_id)
        assert "serve.dispatches" in txt
        assert "nn@8" in txt
        assert tel.run_id in txt

    def test_cli_summary_and_trace(self, tmp_path, capsys):
        from repro.obs.cli import main

        tel = self._tel()
        jl = str(tmp_path / "t.jsonl")
        tel.trace.to_jsonl(jl)
        assert main(["trace", jl]) == 0
        assert "tick" in capsys.readouterr().out
        pf = str(tmp_path / "t.perfetto.json")
        assert main(["trace", jl, "--perfetto", pf]) == 0
        capsys.readouterr()
        assert json.load(open(pf))["traceEvents"]

        bench = str(tmp_path / "BENCH_fa.json")
        write_bench(bench, bench_record("fa", TestBenchSchema.ROWS, 1.0))
        assert main(["summary", bench]) == 0
        assert "speedup" in capsys.readouterr().out
        assert main(["diff", bench, bench]) == 0
        assert "identical" in capsys.readouterr().out
