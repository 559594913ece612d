"""Fused detection front-end vs golden oracle + compaction semantics.

Golden-equivalence policy: the fused gather path and the per-window
reference compute the same math from differently-associated f32 sums
(frame-level vs per-window integral image), so a window whose stump
response lands within fp noise of a trained threshold can legitimately
flip.  The equivalence tests therefore demand *identical* detection sets
except for windows that are provably fp-borderline (some stump margin
below 1e-4 of the normalized response), and that those are rare.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.camera.integral import integral_image
from repro.camera.synthetic import face_dataset, security_video
from repro.camera.viola_jones import (
    BASE,
    CORNER_SLOTS,
    Cascade,
    FusedDetector,
    HaarFeature,
    _haar_response,
    build_gather_tables,
    build_scan_grid,
    detect_faces,
    detect_faces_batch,
    eval_features,
    eval_features_scaled,
    feature_corners,
    grid_inv_norm,
    grid_stage_scores,
    haar_stage_scores,
    make_feature_pool,
    scale_feature,
    train_cascade,
)
from repro.core.cascade import (
    Stage,
    capacities_from_counts,
    compacting_cascade,
    compaction_work,
)

SCAN = dict(scale_factor=1.4, step=4.0, adaptive=False)   # coarse: fast oracle


@pytest.fixture(scope="module")
def cascade():
    X, y, _ = face_dataset(n_per_class=250, seed=0)
    pool = make_feature_pool(n=200)
    return train_cascade(X, y, pool, n_stages=6, per_stage=20, seed=0)


@pytest.fixture(scope="module")
def video():
    frames, truth = security_video(n_frames=6, motion_frames=4, seed=1)
    return frames, truth


class TestFeatureGeometry:
    def test_scale_identity_at_base(self):
        for f in make_feature_pool(n=60):
            assert scale_feature(f, BASE) == f

    def test_scaled_features_stay_inside_and_divisible(self):
        for f in make_feature_pool(n=60, seed=2):
            for win in (20, 25, 31, 49, 95, 119):
                g = scale_feature(f, win)
                assert 0 <= g.y and g.y + g.h <= win
                assert 0 <= g.x and g.x + g.w <= win
                split = g.w if g.kind in (0, 2) else g.h
                assert split % (2 if g.kind < 2 else 3) == 0

    def test_corner_decomposition_matches_rect_sums(self):
        """<= 8 corner taps reproduce the 2-/3-rect window-sum arithmetic."""
        rng = np.random.default_rng(0)
        for win in (BASE, 31):
            patches = jnp.asarray(rng.random((4, win, win), np.float32))
            ii = integral_image(patches)                  # (4, win+1, win+1)
            iif = np.asarray(ii).reshape(4, -1)
            stride = win + 1
            for f in make_feature_pool(n=40, seed=3):
                g = scale_feature(f, win)
                want = np.asarray(_haar_response(ii, g))
                taps = feature_corners(g)
                assert len(taps) <= CORNER_SLOTS
                got = sum(wv * iif[:, dy * stride + dx] for dy, dx, wv in taps)
                np.testing.assert_allclose(got, want, atol=2e-3)

    def test_eval_features_scaled_identity_at_base(self):
        rng = np.random.default_rng(1)
        wins = jnp.asarray(rng.random((16, BASE, BASE), np.float32))
        feats = make_feature_pool(n=30, seed=4)
        a = eval_features(wins, feats)
        b = eval_features_scaled(wins, BASE, feats)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _borderline(cascade, frame, pos, tol=1e-4):
    """True if the window's cascade decision is fp-ambiguous: some stump
    response or stage score within ``tol`` of its threshold."""
    y, x, win = pos
    patch = jnp.asarray(frame[y:y + win, x:x + win][None])
    F = np.asarray(eval_features_scaled(patch, win, cascade.feats))[0]
    if np.min(np.abs(F - cascade.thresholds)) < tol:
        return True
    pred = cascade.polarity * np.sign(F - cascade.thresholds)
    pred[pred == 0] = 1.0
    weighted = cascade.alphas * pred
    off = 0
    for si, size in enumerate(cascade.stage_sizes):
        score = weighted[off:off + size].sum()
        if abs(score - cascade.stage_thresholds[si]) < tol:
            return True
        if score < cascade.stage_thresholds[si]:
            break
        off += size
    return False


class TestGoldenEquivalence:
    def test_fused_matches_reference_detections(self, cascade, video):
        frames, _ = video
        det = FusedDetector(cascade, frames.shape[1], frames.shape[2], **SCAN)
        det.calibrate(frames[:2])
        dets, stats = det.detect(frames)
        assert stats["dropped"] == 0
        n_diff = 0
        for i in range(len(frames)):
            ref, n_inv, _ = detect_faces(cascade, frames[i], SCAN["scale_factor"],
                                         SCAN["step"], SCAN["adaptive"])
            assert n_inv == stats["n_windows"]
            diff = set(ref) ^ set(dets[i])
            for pos in diff:
                assert _borderline(cascade, frames[i], pos), (
                    f"frame {i}: non-borderline mismatch at {pos}")
            n_diff += len(diff)
        assert n_diff <= 2   # borderline flips must stay rare

    def test_detect_faces_batch_convenience(self, cascade, video):
        frames, _ = video
        dets, stats = detect_faces_batch(cascade, frames[:3], **{
            "scale_factor": SCAN["scale_factor"], "step": SCAN["step"],
            "adaptive": SCAN["adaptive"]})
        assert len(dets) == 3
        assert stats["dropped"] == 0
        # cached detector: second call must not rebuild (same object results)
        dets2, _ = detect_faces_batch(cascade, frames[:3], **{
            "scale_factor": SCAN["scale_factor"], "step": SCAN["step"],
            "adaptive": SCAN["adaptive"]})
        assert dets == dets2


class TestCompaction:
    def test_compacting_matches_masked_at_ample_capacity(self, cascade, video):
        """compacting_cascade with generous capacities == the masked oracle
        (full-capacity pass), on the real detector stages."""
        frames, _ = video
        h, w = frames.shape[1:]
        masked = FusedDetector(cascade, h, w, **SCAN)          # full caps
        n = masked.n_windows
        caps = [n] + [max(512, n // 8)] * (masked.n_stages - 1)
        compacted = FusedDetector(cascade, h, w, capacities=caps, **SCAN)
        m_mask, m_surv, m_drop = (np.asarray(a) for a in masked(frames[:3]))
        c_mask, c_surv, c_drop = (np.asarray(a) for a in compacted(frames[:3]))
        assert int(c_drop.sum()) == 0
        np.testing.assert_array_equal(m_mask, c_mask)
        np.testing.assert_array_equal(m_surv, c_surv)

    def test_capacity_overflow_drops_are_counted(self, cascade, video):
        frames, _ = video
        h, w = frames.shape[1:]
        masked = FusedDetector(cascade, h, w, **SCAN)
        _, surv, _ = (np.asarray(a) for a in masked(frames[:2]))
        if surv[:, 0].max() < 2:
            pytest.skip("stage 0 rejects everything on this workload")
        tight = [masked.n_windows] + [1] * (masked.n_stages - 1)
        det = FusedDetector(cascade, h, w, capacities=tight, **SCAN)
        mask, _, dropped = (np.asarray(a) for a in det(frames[:2]))
        assert int(dropped.sum()) > 0
        assert mask.sum() <= surv[:, -1].sum()

    def test_calibrated_capacities_cover_workload(self, cascade, video):
        frames, _ = video
        det = FusedDetector(cascade, frames.shape[1], frames.shape[2], **SCAN)
        caps = det.calibrate(frames[:2])
        assert caps[0] == det.n_windows
        assert all(c <= det.n_windows for c in caps)
        _, _, dropped = det(frames)
        assert int(np.asarray(dropped).sum()) == 0

    def test_capacities_from_counts_helper(self):
        caps = capacities_from_counts(10000, [900, 40, 7], margin=1.5,
                                      quantum=128)
        assert caps[0] == 10000
        assert caps[1] >= int(900 * 1.5) and caps[1] % 128 == 0
        assert caps[2] >= 128
        masked, compacted = compaction_work([330, 330, 330], 10000, caps)
        assert compacted < masked


class TestSyntheticRegression:
    def test_security_video_clamps_motion_frames(self):
        frames, truth = security_video(n_frames=3, motion_frames=12, seed=0)
        assert len(frames) == 3
        assert sum(t["moving"] for t in truth) <= 2

    def test_feature_pool_splits_divisible(self):
        for f in make_feature_pool(n=120, seed=7):
            split = f.w if f.kind in (0, 2) else f.h
            assert split % (2 if f.kind < 2 else 3) == 0


# ---------------------------------------------------------------------------
# Stage 0 over the whole scan grid: strided slices against the gather
# ---------------------------------------------------------------------------

TOY_DETECTOR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench", "tests", "data", "toy_detector.npz")

FLEET_SCAN = dict(scale_factor=1.25, step=0.025, adaptive=True)


@pytest.fixture(scope="module")
def toy_cascade():
    """The benchmark's frozen toy cascade (2 stages of 6 stumps)."""
    z = np.load(TOY_DETECTOR)
    return Cascade(feats=[HaarFeature(*map(int, r)) for r in z["feats"]],
                   thresholds=z["thresholds"], polarity=z["polarity"],
                   alphas=z["alphas"],
                   stage_sizes=[int(s) for s in z["stage_sizes"]],
                   stage_thresholds=z["stage_thresholds"])


def _gather_normalizer(grid, areas, iif, ii2f):
    """Each window's 1 / (sd * area) from its four window-sum corners,
    gathered by flat index as the detector once did."""
    stride = grid.w + 1
    n_off = np.array([[win * stride + win, win, win * stride, 0]
                      for win in grid.scales], np.int32)
    nidx = jnp.asarray(grid.bases[:, None] + n_off[grid.scale_id])
    norm_w = jnp.asarray([1.0, -1.0, -1.0, 1.0], jnp.float32)
    s1 = jnp.sum(jnp.take(iif, nidx.reshape(-1)).reshape(nidx.shape)
                 * norm_w, -1)
    s2 = jnp.sum(jnp.take(ii2f, nidx.reshape(-1)).reshape(nidx.shape)
                 * norm_w, -1)
    area = jnp.asarray(areas)[grid.scale_id]
    mu = s1 / area
    var = s2 / area - mu * mu
    return 1.0 / (jnp.sqrt(jnp.maximum(var, 1e-6)) * area)


def _gather_detect(det, frames):
    """(mask, survivors, drops) with every stage, stage 0 included, scored
    by haar_stage_scores on gathered taps: the reference the slice path
    must reproduce bit for bit."""
    g, t = det.grid, det.tables
    bases = jnp.asarray(g.bases)
    sids = jnp.asarray(g.scale_id)
    thr = [float(v) + det.strictness for v in t.stage_thresholds]

    def one_frame(iif, ii2f):
        inv = _gather_normalizer(g, t.areas, iif, ii2f)
        items = jnp.stack([bases.astype(jnp.float32),
                           sids.astype(jnp.float32), inv], axis=1)

        def stage_fn(lo, hi):
            params = (t.offsets[:, lo:hi], t.weights[lo:hi],
                      t.thresholds[lo:hi], t.polarity[lo:hi], t.alphas[lo:hi])
            return lambda it: haar_stage_scores(
                iif, jnp.clip(it[:, 0], 0, iif.shape[0] - 1).astype(jnp.int32),
                jnp.clip(it[:, 1], 0, len(g.scales) - 1).astype(jnp.int32),
                it[:, 2], *map(jnp.asarray, params))

        stages, lo = [], 0
        for si, sz in enumerate(t.stage_sizes):
            stages.append(Stage(stage_fn(lo, lo + sz), thr[si], f"vj{si}"))
            lo += sz
        res = compacting_cascade(stages, items, list(det.capacities))
        return res.mask, res.n_survivors, res.dropped

    @jax.jit
    def run(frames):
        frames = frames.astype(jnp.float32)
        b = frames.shape[0]
        ii = integral_image(frames).reshape(b, -1)
        ii2 = integral_image(frames * frames).reshape(b, -1)
        return jax.vmap(one_frame)(ii, ii2)

    return [np.asarray(a) for a in run(jnp.asarray(frames))]


GRID_CASES = {
    "fleet_176x144": ((144, 176), FLEET_SCAN),
    "non_adaptive": ((144, 176), SCAN),
    "small_frame": ((37, 45), FLEET_SCAN),
}


class TestGridStage0:
    @pytest.mark.parametrize("case", list(GRID_CASES))
    def test_scan_grid_rects_cover_positions(self, case):
        (h, w), scan = GRID_CASES[case]
        g = build_scan_grid(h, w, **scan)
        pos = [(y0 + i * sy, x0 + j * sx, win)
               for (y0, x0, ny, nx, sy, sx), win in zip(g.rects, g.scales)
               for i in range(ny) for j in range(nx)]
        assert pos == list(g.positions)

    def test_scan_grid_rejects_a_repeated_window_size(self):
        with pytest.raises(ValueError, match="row-major grid"):
            build_scan_grid(40, 40, scale_factor=1.01)

    @pytest.mark.parametrize("case", list(GRID_CASES))
    def test_slices_match_gather_bit_for_bit(self, toy_cascade, case):
        """Normalizer and every stage's scores over the full grid: the
        strided-slice reads give exactly the gather's numbers."""
        (h, w), scan = GRID_CASES[case]
        g = build_scan_grid(h, w, **scan)
        t = build_gather_tables(toy_cascade, g)
        frames = np.random.default_rng(7).random((3, h, w), np.float32)
        bases, sids = jnp.asarray(g.bases), jnp.asarray(g.scale_id)

        def one_frame(ii, ii2):
            iif, ii2f = ii.reshape(-1), ii2.reshape(-1)
            inv_gather = _gather_normalizer(g, t.areas, iif, ii2f)
            inv = grid_inv_norm(ii, ii2, g.rects, g.scales,
                                jnp.asarray(t.areas))
            pairs, lo = [], 0
            for sz in t.stage_sizes:
                hi = lo + sz
                stumps = (jnp.asarray(t.weights[lo:hi]),
                          jnp.asarray(t.thresholds[lo:hi]),
                          jnp.asarray(t.polarity[lo:hi]),
                          jnp.asarray(t.alphas[lo:hi]))
                pairs.append((
                    grid_stage_scores(ii, inv, g.rects, t.offsets[:, lo:hi],
                                      *stumps),
                    haar_stage_scores(iif, bases, sids, inv,
                                      jnp.asarray(t.offsets[:, lo:hi]),
                                      *stumps)))
                lo = hi
            return inv, inv_gather, pairs

        fr = jnp.asarray(frames)
        out = jax.jit(jax.vmap(one_frame))(integral_image(fr),
                                           integral_image(fr * fr))
        inv, inv_gather, pairs = jax.tree.map(np.asarray, out)
        assert inv.shape == (3, len(g.positions))
        np.testing.assert_array_equal(inv, inv_gather)
        for sliced, gathered in pairs:
            np.testing.assert_array_equal(sliced, gathered)

    @pytest.mark.parametrize("calibrated", [False, True])
    def test_detector_matches_gather_path(self, cascade, video, calibrated):
        """FusedDetector's mask, survivors and drops on the video are the
        ones the all-gather path gives, at full and calibrated capacities."""
        frames, _ = video
        det = FusedDetector(cascade, frames.shape[1], frames.shape[2], **SCAN)
        if calibrated:
            det.calibrate(frames[:2])
        got = [np.asarray(a) for a in det(frames)]
        want = _gather_detect(det, frames)
        for g_, w_ in zip(got, want):
            np.testing.assert_array_equal(g_, w_)
        assert got[1][:, 0].sum() > 0      # stage 0 let some windows through
