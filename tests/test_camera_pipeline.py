"""Camera substrate tests: funnel behaviour, calibration constraints,
per-block correctness, BSSA quality direction."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis_compat import given, settings, st

from repro.camera.bssa import (
    GridSpec, _grid_coords, blur_121, bssa_depth, bssa_depth_ref, ms_ssim,
    refine, rough_disparity, rough_disparity_ref, slice_grid, slice_grid_ref,
    splat)
from repro.camera.face_nn import (
    classification_error, forward_float, forward_lut, forward_quantized,
    make_sigmoid_lut, nn_power, train_face_nn)
from repro.camera.integral import integral_image, streaming_integral_rows, window_sum
from repro.camera.motion import motion_mask
from repro.camera.pipelines import (
    FAWorkloadStats, FaceAuthExecutor, calibrate_fa, fa_pipeline, fa_profiles)
from repro.camera.synthetic import face_dataset, security_video, stereo_pair
from repro.core.costmodel import energy_cost


class TestIntegral:
    def test_streaming_equals_cumsum(self):
        img = jnp.asarray(np.random.default_rng(0).random((31, 47), np.float32))
        np.testing.assert_allclose(np.asarray(integral_image(img)),
                                   np.asarray(streaming_integral_rows(img)),
                                   rtol=1e-5, atol=1e-4)

    def test_window_sum(self):
        img = jnp.arange(20.0).reshape(4, 5)
        ii = integral_image(img)
        assert float(window_sum(ii, 1, 1, 2, 3)) == pytest.approx(
            float(jnp.sum(img[1:3, 1:4])))

    def test_two_row_buffer_claim(self):
        """Paper: streaming uses <1 kB (two rows) vs 57 kB full frame —
        the WISPCam numbers."""
        w = 176
        assert 2 * w * 2 < 1024            # two 16-bit rows < 1 kB
        assert 176 * 144 * 2 > 45 * 1024   # full-frame integral buffer ~50-57 kB


class TestMotion:
    def test_static_scene_passes_nothing(self):
        frames = np.ones((10, 32, 32), np.float32) * 0.5
        mask, _ = motion_mask(jnp.asarray(frames), threshold=0.004)
        assert int(mask.sum()) == 0

    def test_moving_scene_detected(self):
        frames, truth = security_video(seed=5)
        mask, _ = motion_mask(jnp.asarray(frames), threshold=0.004)
        moving = np.array([t["moving"] for t in truth])
        # every true motion frame must pass (filters must not drop signal)
        assert int((moving & ~np.asarray(mask)).sum()) == 0


class TestFaceNN:
    @pytest.fixture(scope="class")
    def trained(self):
        X, y, _ = face_dataset(n_per_class=250, seed=1)
        ntr = int(0.9 * len(X))
        nn = train_face_nn(X[:ntr], y[:ntr], steps=1500)
        return nn, X[ntr:], y[ntr:]

    def test_topology_is_400_8_1(self, trained):
        nn, _, _ = trained
        assert nn.topology == (400, 8, 1)

    def test_lut_negligible(self, trained):
        nn, Xte, yte = trained
        lut, meta = make_sigmoid_lut()
        e_f = classification_error(forward_float(nn, jnp.asarray(Xte)), yte)
        e_l = classification_error(forward_lut(nn, jnp.asarray(Xte), lut, meta), yte)
        assert abs(e_f - e_l) <= 0.01     # paper: negligible

    def test_bit_knee(self, trained):
        nn, Xte, yte = trained
        lut, meta = make_sigmoid_lut()
        errs = {b: classification_error(
            forward_quantized(nn, jnp.asarray(Xte), b, lut, meta), yte)
            for b in (16, 8, 4)}
        e_f = classification_error(forward_float(nn, jnp.asarray(Xte)), yte)
        assert errs[8] - e_f <= 0.015     # paper: ~0.4% loss at 8-bit
        assert errs[4] >= errs[8]         # 4-bit at/past the knee

    def test_power_anchor(self):
        assert nn_power(8) == pytest.approx(393e-6, rel=1e-6)
        assert 1 - nn_power(8) / nn_power(16) == pytest.approx(0.41, abs=0.02)


class TestCalibration:
    def test_constraints_hold(self):
        stats = FAWorkloadStats()
        cal = calibrate_fa(stats)
        pipe = fa_pipeline(stats)
        profiles = fa_profiles()
        profiles["nn"] = cal.nn_profile()
        duties = {"sensor": 1.0, "motion": 1.0, "vj": 0.0, "nn": 1.0}
        a = energy_cost(pipe.configure(("motion", "vj")), profiles,
                        cal.rf_link(), "vj", duties=duties).total_w
        b = energy_cost(pipe.configure(("motion", "vj")), profiles,
                        cal.rf_link(), "nn", duties=duties).total_w
        assert b / a == pytest.approx(1.28, abs=0.02)   # paper's +28%

    def test_ladder_ordering(self):
        """raw > motion-only > motion+vj (the Fig. 8 shape)."""
        stats = FAWorkloadStats()
        cal = calibrate_fa(stats)
        pipe = fa_pipeline(stats)
        profiles = fa_profiles()
        profiles["nn"] = cal.nn_profile()
        duties = {"sensor": 1.0, "motion": 1.0, "vj": 0.0, "nn": 1.0}
        raw = energy_cost(pipe.configure(()), profiles, cal.rf_link(),
                          "sensor", duties=duties).total_w
        mo = energy_cost(pipe.configure(("motion",)), profiles, cal.rf_link(),
                         "motion", duties=duties).total_w
        mv = energy_cost(pipe.configure(("motion", "vj")), profiles,
                         cal.rf_link(), "vj", duties=duties).total_w
        assert raw > mo > mv


class TestFaceAuthExecutor:
    """The §III streaming executor vs the per-motion-frame host loop
    (golden oracle): identical motion/window/auth counts on the security
    workload, scores bit-identical to the same int8 datapath run on host
    crops, and the capacity-padding contract (DESIGN.md §9)."""

    SCAN = dict(scale_factor=1.4, step=4.0, adaptive=False)

    @pytest.fixture(scope="class")
    def setup(self):
        from repro.camera.face_nn import train_face_nn
        from repro.camera.viola_jones import make_feature_pool, train_cascade
        X, y, _ = face_dataset(n_per_class=250, seed=0)
        casc = train_cascade(X, y, make_feature_pool(n=200), n_stages=6,
                             per_stage=20, seed=0)
        nn = train_face_nn(X, y, steps=300)
        frames, _ = security_video(n_frames=14, motion_frames=6, seed=1)
        ex = FaceAuthExecutor(casc, nn, frames.shape[1], frames.shape[2],
                              **self.SCAN)
        ex.calibrate(frames)
        return casc, nn, frames, ex, ex(frames)

    def _host_loop(self, ex, nn, frames, nn_fn):
        """The golden-oracle funnel — the SAME implementation the benchmark
        pins parity against (benchmarks/workloads.py), so test and
        benchmark cannot drift onto different contracts."""
        from benchmarks.workloads import host_loop_funnel
        mask, n_win, n_auth, scores, _ = host_loop_funnel(
            ex, frames, nn_fn)
        return mask, n_win, n_auth, scores

    def test_funnel_parity_vs_host_loop(self, setup):
        from repro.kernels.quant_matmul.ops import nn_forward_quantized
        casc, nn, frames, ex, res = setup
        mask, n_win, n_auth, scores = self._host_loop(
            ex, nn, frames,
            lambda x: nn_forward_quantized(ex.qnn, jnp.asarray(x), ex.lut,
                                           ex.lut_meta, use_pallas=False))
        np.testing.assert_array_equal(np.asarray(res.motion), mask)
        np.testing.assert_array_equal(np.asarray(res.n_windows), n_win)
        np.testing.assert_array_equal(np.asarray(res.n_auth), n_auth)
        assert res.total_dropped() == 0
        # the in-graph gather must replicate extract_windows exactly, so
        # scores (same int8 datapath) are identical, window-for-window
        for i, s in scores.items():
            v = np.asarray(res.window_valid[i])
            np.testing.assert_array_equal(np.asarray(res.scores[i])[v], s)

    def test_scores_match_fake_quant_oracle(self, setup):
        """Against forward_quantized (the seed's float fake-quantization):
        same scores up to the quantization-scheme gap, and identical
        decisions for every window that is not threshold-borderline."""
        from repro.camera.face_nn import forward_quantized
        casc, nn, frames, ex, res = setup
        _, _, _, scores = self._host_loop(
            ex, nn, frames,
            lambda x: forward_quantized(nn, jnp.asarray(x), 8, ex.lut,
                                        ex.lut_meta))
        checked = 0
        for i, s_fq in scores.items():
            v = np.asarray(res.window_valid[i])
            s_ex = np.asarray(res.scores[i])[v]
            assert np.abs(s_ex - s_fq).max() < 0.08
            clear = np.abs(s_fq - ex.auth_threshold) > 0.1
            np.testing.assert_array_equal(
                (s_ex > ex.auth_threshold)[clear],
                (s_fq > ex.auth_threshold)[clear])
            checked += int(clear.sum())
        assert checked > 0

    def test_capacity_contract(self, setup):
        """Overflow never corrupts results: excess detections/motion frames
        are dropped and COUNTED, survivors keep original window order."""
        casc, nn, frames, ex, res = setup
        tight = FaceAuthExecutor(casc, nn, frames.shape[1], frames.shape[2],
                                 window_capacity=2, frame_capacity=3,
                                 **self.SCAN)
        # detector-internal cascade drops must surface too (not just the
        # executor's own two capacities)
        starved = FaceAuthExecutor(
            casc, nn, frames.shape[1], frames.shape[2],
            capacities=[ex.det.n_windows] + [1] * (ex.det.n_stages - 1),
            **self.SCAN)
        rs = starved(frames)
        lost = np.asarray(res.n_windows).sum() - np.asarray(rs.n_windows).sum()
        if lost:
            assert int(np.asarray(rs.cascade_dropped).sum()) > 0
            assert rs.total_dropped() > 0
        r = tight(frames)
        n_det = np.asarray(res.n_windows)
        n_mot = int(np.asarray(res.motion).sum())
        assert int(np.asarray(r.motion_dropped)) == max(n_mot - 3, 0)
        v = np.asarray(r.window_valid)
        assert v.sum(axis=1).max() <= 2
        # processed frames report exact pre-capacity counts and the drops
        proc = np.asarray(r.n_windows) > 0
        np.testing.assert_array_equal(np.asarray(r.n_windows)[proc],
                                      n_det[proc])
        drops = np.asarray(r.windows_dropped)
        np.testing.assert_array_equal(
            drops[proc], np.maximum(n_det[proc] - 2, 0))
        for i in np.where(proc)[0]:
            ids = np.asarray(r.window_id[i])[np.asarray(r.window_valid[i])]
            assert list(ids) == sorted(ids)       # stable, original order

    def test_multi_stream_vmap_matches_single(self, setup):
        casc, nn, frames, ex, res = setup
        streams = jnp.stack([jnp.asarray(frames),
                             jnp.asarray(np.roll(frames, 3, axis=0))])
        r = ex.run_streams(streams)
        np.testing.assert_array_equal(np.asarray(r.n_windows[0]),
                                      np.asarray(res.n_windows))
        np.testing.assert_array_equal(np.asarray(r.scores[0]),
                                      np.asarray(res.scores))
        assert np.asarray(r.n_windows).shape[0] == 2


class TestBSSA:
    def test_splat_slice_roundtrip_smooth_field(self):
        """Splatting a smooth field and slicing it back preserves it."""
        left, _, _ = stereo_pair(h=64, w=80, seed=1)
        field = jnp.asarray(np.tile(np.linspace(0, 10, 80), (64, 1)).astype(np.float32))
        spec = GridSpec(sigma_spatial=8)
        gv, gw = splat(jnp.asarray(left), field, spec)
        out = slice_grid(gv, gw, jnp.asarray(left), spec)
        assert float(jnp.mean(jnp.abs(out - field))) < 1.0

    def test_blur_is_smoothing(self):
        g = jax.random.normal(jax.random.PRNGKey(0), (16, 16, 9))
        blurred = blur_121(g)
        assert float(jnp.var(blurred)) < float(jnp.var(g))

    def test_refinement_improves_depth(self):
        left, right, gt = stereo_pair(h=96, w=128, seed=3)
        rough = rough_disparity(jnp.asarray(left), jnp.asarray(right), 12)
        refined = bssa_depth(jnp.asarray(left), jnp.asarray(right),
                             GridSpec(sigma_spatial=8), max_disp=12, n_iters=8)
        def nerr(d):
            d = np.asarray(d)
            dn = (d - d.min()) / (np.ptp(d) + 1e-9)
            gn = (gt - gt.min()) / (np.ptp(gt) + 1e-9)
            return float(np.mean(np.abs(dn - gn)))
        assert nerr(refined) < nerr(rough)  # edge-aware smoothing helps

    def test_msssim_identity(self):
        a = jnp.asarray(np.random.default_rng(0).random((64, 64), np.float32))
        assert ms_ssim(a, a) > 0.99

    @pytest.mark.parametrize("s", [8, 12, 16])
    @pytest.mark.parametrize("whole", [True, False],
                             ids=["whole_blocks", "ragged"])
    def test_slice_matches_gather_oracle(self, s, whole):
        """The gather-free slice against the 16-gather oracle, both jitted
        and vmapped over a batch of 2: frames that are whole s x s blocks
        and frames that are not, 8-bit intensities with 0 and 1 among them
        and a few outside [0, 1], which clip to the end bins."""
        h, w = (4 * s, 6 * s) if whole else (4 * s + 5, 6 * s - 3)
        spec = GridSpec(sigma_spatial=s)
        gy, gx, gr = spec.dims(h, w)
        rng = np.random.default_rng(100 * s + h)
        gv = rng.random((2, gy, gx, gr), np.float32) * 30
        gw = rng.random((2, gy, gx, gr), np.float32) + 0.5
        img = rng.integers(0, 256, (2, h, w)).astype(np.float32) / 255
        img[:, 0, :6] = [0.0, 1.0, -0.2, 1.3, -3.0, 2.0]
        args = [jnp.asarray(a) for a in (gv, gw, img)]
        got, want = (np.asarray(jax.jit(jax.vmap(
            lambda v, wt, im, f=f: f(v, wt, im, spec)))(*args))
            for f in (slice_grid, slice_grid_ref))
        assert got.shape == (2, h, w)
        np.testing.assert_allclose(got, want, rtol=1e-5)


def _views8(h, w, max_disp, seed):
    """A seeded stereo pair at 8 bits per pixel (k / 256), as the rig's
    cameras deliver it."""
    left, right, _ = stereo_pair(h=h, w=w, max_disp=max_disp, seed=seed)
    q = lambda v: jnp.asarray(np.minimum(np.floor(v * 256), 255) / 256,
                              jnp.float32)
    return q(left), q(right)


def _summed_area_winners(left, right, max_disp, patch=5):
    """The cost volume as it was once computed: each hypothesis' SAD from
    four corners of one float32 summed-area table of the whole padded
    frame."""
    h, w = left.shape
    costs = []
    for d in range(max_disp + 1):
        xs = jnp.clip(jnp.arange(w) - d, 0, w - 1)
        dp = jnp.pad(jnp.abs(left - right[:, xs]), patch // 2, mode="edge")
        ii = jnp.pad(jnp.cumsum(jnp.cumsum(dp, 0), 1), ((1, 0), (1, 0)))
        costs.append(ii[patch:, patch:] - ii[:-patch, patch:]
                     - ii[patch:, :-patch] + ii[:-patch, :-patch])
    return np.asarray(jnp.argmin(jnp.stack(costs), axis=0))


def _reference_winners(left, right, max_disp):
    from bench.reference import bssa as plain

    return np.asarray(jax.jit(plain.rough, static_argnums=2)(left, right,
                                                             max_disp))


class TestBSSAFusedParity:
    """The chunked cost volume and the end-to-end depth step against the
    plain reference (bench/reference/bssa.py) and the seed loop oracles:
    the same winners bit for bit, the depth within float32 rounding."""

    def test_rough_fused_equals_seed_loop(self):
        left, right = _views8(72, 96, 12, seed=4)
        a = np.asarray(rough_disparity(left, right, 12))
        b = np.asarray(rough_disparity_ref(left, right, 12))
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, _reference_winners(left, right, 12))

    @pytest.mark.parametrize("chunk", [1, 4, 64])
    def test_rough_chunk_sizes_agree(self, chunk):
        """chunk=1 (one hypothesis per scan step) through chunk>=D+1 (one
        step, a ragged tail of hypotheses past D that never win) give the
        reference's winners."""
        left, right = _views8(48, 64, 12, seed=5)
        a = np.asarray(rough_disparity(left, right, 12,
                                       hypothesis_chunk=chunk))
        np.testing.assert_array_equal(a, _reference_winners(left, right, 12))

    def test_rough_pallas_integral_matches(self):
        """Where the integral kernel's whole-frame table flips winners, the
        separable sums do not: on a 256x3840 strip of 8-bit views the
        table's float32 prefix sums pass 2**16 and round away 1/256 steps
        (986 winners of 983,040 differ from the reference on the CPU),
        while rough_disparity equals the reference at every pixel."""
        left, right = _views8(256, 3840, 6, seed=(5, 256))
        want = _reference_winners(left, right, 8)
        assert (_summed_area_winners(left, right, 8) != want).sum() > 100
        np.testing.assert_array_equal(
            np.asarray(jax.jit(rough_disparity, static_argnums=2)(
                left, right, 8)), want)

    def test_depth_maps_agree_with_the_plain_reference(self):
        """VRRigExecutor's depth, 8-bit views, full camera width: the same
        winners, so only the blur kernel's and the slice's float32
        rounding separate it from the reference's depth."""
        from bench.reference import bssa as plain
        from repro.camera.pipelines import VRRigExecutor

        left, right = _views8(24, 3840, 6, seed=9)
        ex = VRRigExecutor(GridSpec(sigma_spatial=16), max_disp=8,
                           n_iters=2)
        got = np.asarray(ex.depth_maps(left[None], right[None]))[0]
        want = np.asarray(plain.depth(left, right, max_disp=8, n_iters=2,
                                      sigma_spatial=16, sigma_range=16.0))
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)

    def test_bssa_depth_fused_matches_oracle(self):
        left, right, _ = stereo_pair(h=64, w=80, seed=6)
        spec = GridSpec(sigma_spatial=8)
        a = bssa_depth(jnp.asarray(left), jnp.asarray(right), spec,
                       max_disp=10, n_iters=6)
        b = bssa_depth_ref(jnp.asarray(left), jnp.asarray(right), spec,
                           max_disp=10, n_iters=6)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


class TestDeviceSplit:
    """Independent items (rig pairs, camera streams) split over devices:
    one per device when they fit, else every device with the batch padded
    to a multiple of the device count."""

    @pytest.mark.parametrize("n,ndev", [(8, 4), (6, 4), (3, 4), (9, 2),
                                        (5, 3)])
    def test_group_pads_and_ungroup_drops(self, n, ndev):
        from repro.camera.pipelines import _device_groups, _group, _ungroup
        groups = _device_groups(n, ndev)
        assert groups == min(n, ndev)
        x = jnp.arange(1, 2 * n + 1, dtype=jnp.float32).reshape(n, 2)
        g = _group(x, groups)
        assert g.shape == (groups, -(-n // groups), 2)
        assert float(jnp.abs(g).sum()) == float(x.sum())   # pads are zero
        np.testing.assert_array_equal(np.asarray(_ungroup({"x": g}, n)["x"]),
                                      np.asarray(x))

    def test_one_device_does_not_pmap(self):
        from repro.camera.pipelines import _device_groups
        assert _device_groups(8, 1) == 0

    def test_uneven_rig_split_matches_vmap(self, subproc):
        """6 pairs on 4 devices: pmapped 2-per-device with two zero pad
        pairs, equal to the frame vmapped on one device."""
        out = subproc("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.camera.bssa import GridSpec
            from repro.camera.pipelines import VRRigExecutor
            from repro.camera.synthetic import stereo_pair
            pairs = [stereo_pair(h=24, w=32, seed=s) for s in range(6)]
            ls = jnp.stack([jnp.asarray(p[0]) for p in pairs])
            rs = jnp.stack([jnp.asarray(p[1]) for p in pairs])
            kw = dict(spec=GridSpec(sigma_spatial=8), max_disp=4, n_iters=2)
            par = VRRigExecutor(rig_parallel=True, **kw).depth_maps(ls, rs)
            one = VRRigExecutor(rig_parallel=False, **kw).depth_maps(ls, rs)
            assert par.shape == one.shape == (6, 24, 32)
            print("DIFF", float(jnp.abs(par - one).max()))
        """, n_devices=4)
        assert float(out.split("DIFF")[1]) <= 1e-5


class TestBSSAProperties:
    """Property tests for the bilateral-grid operators (satellite: splat/
    slice adjointness + mass conservation, blur normalization, rough
    disparity shift recovery)."""

    @given(st.integers(24, 56), st.integers(24, 56))
    @settings(max_examples=6, deadline=None)
    def test_splat_mass_conservation(self, h, w):
        rng = np.random.default_rng(100 * h + w)
        img = jnp.asarray(rng.random((h, w), np.float32))
        vals = jnp.asarray(rng.random((h, w), np.float32))
        gv, gw = splat(img, vals, GridSpec(sigma_spatial=8))
        assert float(gw.sum()) == pytest.approx(h * w, rel=1e-5)
        assert float(gv.sum()) == pytest.approx(float(vals.sum()), rel=1e-4)

    @given(st.integers(24, 48), st.integers(24, 48))
    @settings(max_examples=4, deadline=None)
    def test_splat_nearest_slice_adjoint(self, h, w):
        """<splat(v), G> == <v, G[nearest vertex]> for any grid field G:
        splat is exactly the adjoint of nearest-vertex sampling."""
        rng = np.random.default_rng(37 * h + w)
        spec = GridSpec(sigma_spatial=8)
        img = jnp.asarray(rng.random((h, w), np.float32))
        vals = jnp.asarray(rng.random((h, w), np.float32))
        gv, _ = splat(img, vals, spec)
        G = jnp.asarray(rng.random(gv.shape, np.float32))
        gy, gx, gr = gv.shape
        cy, cx, cr = _grid_coords(img, spec)
        iy = jnp.clip(jnp.round(cy).astype(jnp.int32), 0, gy - 1)
        ix = jnp.clip(jnp.round(cx).astype(jnp.int32), 0, gx - 1)
        ir = jnp.clip(jnp.round(cr).astype(jnp.int32), 0, gr - 1)
        lhs = float(jnp.sum(gv * G))
        rhs = float(jnp.sum(vals.reshape(-1) * G[iy, ix, ir]))
        assert lhs == pytest.approx(rhs, rel=1e-4)

    def test_slice_partition_of_unity(self):
        """Slicing a constant grid returns the constant everywhere — the
        trilinear weights normalize out."""
        spec = GridSpec(sigma_spatial=8)
        img = jnp.asarray(np.random.default_rng(0).random((48, 64), np.float32))
        gy, gx, gr = spec.dims(48, 64)
        gw = jnp.ones((gy, gx, gr))
        out = slice_grid(3.5 * gw, gw, img, spec)
        np.testing.assert_allclose(np.asarray(out), 3.5, atol=1e-5)

    @given(st.integers(6, 24), st.integers(6, 24))
    @settings(max_examples=6, deadline=None)
    def test_blur_121_weight_normalization(self, gy, gx):
        """DC gain 1 at every vertex (weights sum to 1, edges included) and
        exact mass conservation for interior-supported fields."""
        ones = jnp.ones((gy, gx, 9))
        np.testing.assert_allclose(np.asarray(blur_121(ones)), 1.0, atol=1e-6)
        rng = np.random.default_rng(13 * gy + gx)
        core = np.zeros((gy, gx, 9), np.float32)
        core[1:-1, 1:-1, 1:-1] = rng.random((gy - 2, gx - 2, 7))
        blurred = blur_121(jnp.asarray(core))
        assert float(blurred.sum()) == pytest.approx(float(core.sum()), rel=1e-5)

    @given(st.integers(2, 9))
    @settings(max_examples=6, deadline=None)
    def test_rough_disparity_recovers_injected_shift(self, s):
        """A pair built with right[x] = left[x+s] (the module's disparity
        convention) is recovered exactly away from the borders."""
        rng = np.random.default_rng(s)
        h, w, max_disp, patch = 40, 120, 12, 5
        base = rng.random((h, w + 16)).astype(np.float32)
        k = np.ones(7) / 7          # smooth so neighboring lags separate
        full = np.stack([np.convolve(row, k, "same") for row in base])
        left = jnp.asarray(full[:, :w])
        right = jnp.asarray(full[:, s:s + w])
        d = np.asarray(rough_disparity(left, right, max_disp, patch))
        pad = patch // 2
        inner = d[pad:-pad, max_disp + pad:-(max_disp + pad)]
        assert (inner == s).mean() >= 0.98
