"""The paper's two pipelines as Pipeline objects + calibrated cost profiles.

This is where the faithful reproduction meets the cost model
(core/costmodel): block work descriptors come from the *measured* synthetic
workload (funnel statistics), device profiles from Table I, and the two
under-determined constants — RF joules/byte and the NN ASIC's standby
leakage — are **calibrated** so the paper's two stated headline relations
hold exactly:

  (1) adding the NN in-camera raises total power by +28% (Fig. 9), and
  (2) the offload-vs-in-camera decision flips at 2.68x comm energy.

Everything else (config ordering in Fig. 8, the 8 MP crossover direction,
filter funnel, 265x/442,146x accelerator gains, the VR Fig. 14 ladder)
must then *emerge* — benchmarks/fa_system.py and vr_system.py check that
they do.  See DESIGN.md §5 and EXPERIMENTS.md for the argument.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core.costmodel import (
    ARM_A9,
    ETH_25G,
    ETH_400G,
    HardwareProfile,
    IMAGE_SENSOR,
    MOTION_ASIC,
    MSP430,
    NN_ASIC,
    QUADRO_GPU,
    RF_LINK,
    VIRTEX_FPGA,
    VJ_ASIC,
    ZYNQ_FPGA,
)
from repro.core.pipeline import Block, BlockKind, Pipeline

# ---------------------------------------------------------------------------
# §III face authentication pipeline (WISPCam: 176x144 @ 1 FPS)
# ---------------------------------------------------------------------------

FRAME_H, FRAME_W = 144, 176
FRAME_BYTES = FRAME_H * FRAME_W          # 8-bit pixels
WINDOW_PIXELS = 400                      # 20x20 window to the NN
NN_MACS = 400 * 8 + 8                    # 400-8-1 topology


@dataclasses.dataclass(frozen=True)
class FAWorkloadStats:
    """Funnel statistics measured on the (synthetic) security workload.

    Paper §III-D: 62 frames -> 12 pass motion -> 40 windows to the NN
    (≈3.33 windows per motion frame), ~7.9k scan positions per frame at
    fine parameters.
    """

    n_frames: int = 62
    motion_frames: int = 12
    windows_to_nn: int = 40
    scan_windows_per_frame: float = 7900.0
    vj_stage_evals_per_frame: float = 11000.0   # masked-cascade measurement hook

    @property
    def motion_sel(self) -> float:
        return self.motion_frames / self.n_frames

    @property
    def windows_per_motion_frame(self) -> float:
        return self.windows_to_nn / self.motion_frames

    @property
    def nn_windows_per_second(self) -> float:     # at 1 FPS source rate
        return self.windows_to_nn / self.n_frames


def fa_pipeline(stats: FAWorkloadStats, with_cpu_nn: bool = False) -> Pipeline:
    """Block pipeline of Fig. 2.  Work is per *source frame* (1 FPS); the
    selectivity chain scales downstream blocks exactly like the paper's
    duty-cycling argument."""
    wpf = stats.windows_per_motion_frame
    blocks = (
        Block("sensor", flops=0.0, bytes_in=0.0, bytes_out=FRAME_BYTES,
              kind=BlockKind.SOURCE),
        Block("motion", flops=3 * FRAME_BYTES, bytes_in=FRAME_BYTES,
              bytes_out=FRAME_BYTES, kind=BlockKind.OPTIONAL,
              selectivity=stats.motion_sel),
        # VJ on a motion-passed frame: integral image + cascade stages;
        # output = detected windows (de-integral-ized 20x20 crops).
        # selectivity = fraction of motion frames with >=1 detection (every
        # motion frame in the measured workload); bytes_out = windows per
        # surviving frame — the 40-windows/62-s payload the paper charges.
        Block("vj", flops=2 * FRAME_BYTES + 9 * stats.vj_stage_evals_per_frame,
              bytes_in=FRAME_BYTES,
              bytes_out=wpf * WINDOW_PIXELS, kind=BlockKind.OPTIONAL,
              selectivity=1.0),
        Block("nn", flops=2 * NN_MACS * wpf, bytes_in=wpf * WINDOW_PIXELS,
              bytes_out=1.0 / 8.0,       # 1-bit decision
              requires=("vj",)),         # NN input = FD's 20x20 windows
    )
    return Pipeline("face_auth", blocks)


def fa_profiles(nn_on_cpu: bool = False) -> dict:
    nn = MSP430 if nn_on_cpu else NN_ASIC
    return {"sensor": IMAGE_SENSOR, "motion": MOTION_ASIC,
            "vj": VJ_ASIC, "nn": nn}


# -- calibration --------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FACalibration:
    rf_joules_per_byte: float
    nn_effective_w: float         # leakage+duty effective power of the NN block
    base_compute_w: float         # sensor+motion+vj through-VJ compute power

    def rf_link(self) -> HardwareProfile:
        return HardwareProfile(name="rf_link",
                               joules_per_byte=self.rf_joules_per_byte)

    def nn_profile(self) -> HardwareProfile:
        # the calibrated value IS the block's average power (leakage-dominated
        # + duty-scaled dynamic); both rails set so duty drops out
        return dataclasses.replace(
            NN_ASIC, p_active_w=self.nn_effective_w,
            p_leak_w=self.nn_effective_w)


def calibrate_fa(stats: FAWorkloadStats,
                 sensor_w: float = IMAGE_SENSOR.p_active_w,
                 motion_w: float = MOTION_ASIC.p_active_w,
                 vj_eff_w: float = VJ_ASIC.p_leak_w,
                 plus_pct: float = 0.28,
                 crossover: float = 2.68) -> FACalibration:
    """Solve the two paper constraints for (e_c, P_nn_eff).

    Let C = compute power through VJ, B = bytes/s after VJ.  Then
      (1)  C + P_nn + e_c*B_nn = (1 + plus_pct) * (C + e_c*B)
      (2)  P_nn = crossover * e_c * (B - B_nn)              [tie at k*e_c]
    With B_nn ~ 0:  e_c*B = C * plus_pct / (crossover - 1 - plus_pct)
                    P_nn  = crossover * e_c * B.
    """
    C = sensor_w + motion_w + vj_eff_w
    B = stats.nn_windows_per_second * WINDOW_PIXELS      # bytes/s after VJ
    # Post-NN uplink traffic: one 1-bit authentication decision per source
    # frame at the 1 FPS source rate = 1/8 byte/s.  This tiny residual is
    # what keeps the crossover equation (2) exactly solvable rather than
    # assuming B_nn = 0; it feeds the e_c denominator below.
    B_nn = 1.0 / 8.0
    ec_B = C * plus_pct / (crossover - 1.0 - plus_pct)
    e_c = ec_B / (B - B_nn * crossover / (crossover - 1.0 - plus_pct))
    p_nn = crossover * e_c * (B - B_nn)
    return FACalibration(rf_joules_per_byte=e_c, nn_effective_w=p_nn,
                         base_compute_w=C)


# ---------------------------------------------------------------------------
# §IV VR pipeline (16x 4K cameras @ 30 FPS target)
# ---------------------------------------------------------------------------

VR_CAMS = 16
VR_W, VR_H = 3840, 2160                   # 4K per camera
VR_FPS_TARGET = 30.0


@dataclasses.dataclass(frozen=True)
class VRWorkloadStats:
    """Per-frame work for the 2-camera pipeline slice of Fig. 13 (x8 pairs
    gives the 16-camera rig; the paper plots 2 of 16 cameras)."""

    grid_sigma: int = 16                  # pixels per grid vertex
    disp_range: int = 32
    refine_iters: int = 8

    @property
    def pixels(self) -> float:
        return 2 * VR_W * VR_H            # a camera pair

    def grid_vertices(self) -> float:
        gy = VR_H / self.grid_sigma
        gx = VR_W / self.grid_sigma
        return gy * gx * 17.0             # 16 intensity bins + 1

    def rough_flops(self) -> float:       # SAD block matching
        return self.pixels / 2 * self.disp_range * 8

    def refine_flops(self) -> float:      # iterated 3-axis [1,2,1] blurs, v+w
        return self.grid_vertices() * self.refine_iters * 3 * 4 * 2


def vr_pipeline(stats: VRWorkloadStats) -> Pipeline:
    """B1 capture -> B2 ISP/rectify -> B3 grid construction (data expands)
    -> B4 depth refinement (dominant) -> B5 stitch/compose.  Bytes from
    Fig. 13's shape: biggest intermediate into the depth block; small depth
    maps after."""
    px = stats.pixels
    raw = px * 1.0                         # 8-bit Bayer off the sensor
    rgb = px * 3.0
    grid = stats.grid_vertices() * 8.0     # f32 (value, weight) per vertex
    depth = px / 2 * 2.0                   # 16-bit depth map per pair
    # stitch output = encoded stereo panorama slice (the paper's only
    # uploadable intermediate; video-rate panoramas ship compressed)
    pano = 2 * 8192 * 4096 * 3.0 / 8 / 50.0
    blocks = (
        Block("capture", flops=0.0, bytes_in=0.0, bytes_out=raw,
              kind=BlockKind.SOURCE),
        Block("isp", flops=20 * px, bytes_in=raw, bytes_out=rgb),
        # grid construction = splatting (cheap, bandwidth-ish); the rough
        # disparity estimate belongs to the stereo solve itself and moves
        # with it onto the accelerator
        Block("grid", flops=2 * px, bytes_in=rgb, bytes_out=rgb + grid),
        Block("depth",
              flops=stats.rough_flops() / 16 + stats.refine_flops() * 420,
              bytes_in=rgb + grid, bytes_out=depth),
        Block("stitch", flops=2 * px, bytes_in=depth + rgb, bytes_out=pano),
    )
    return Pipeline("vr_video", blocks)


def vr_profiles(depth_device: HardwareProfile) -> dict:
    """depth_device is the knob (CPU/GPU/FPGA); Fig. 14's passing "FPGA"
    configuration uses the Table II production target (VIRTEX_FPGA)."""
    return {"capture": IMAGE_SENSOR, "isp": ZYNQ_FPGA, "grid": ARM_A9,
            "depth": depth_device, "stitch": ARM_A9}


# ---------------------------------------------------------------------------
# Splitting a batch over local devices
# ---------------------------------------------------------------------------


def _device_groups(n: int, n_devices: int) -> int:
    """Number of pmap shards for ``n`` independent items on ``n_devices``:
    one item per device when they fit, every device when there are more
    items than devices (:func:`_group` pads the batch to a multiple), and
    0 (do not pmap) when only one device exists."""
    if n_devices < 2 or n < 1:
        return 0
    return min(n, n_devices)


def _group(x, groups: int):
    """(n, ...) -> (groups, ceil(n / groups), ...), the pmap-over-vmap
    layout; an uneven batch is padded with zero items, which
    :func:`_ungroup` drops."""
    import jax.numpy as jnp

    n = x.shape[0]
    per = -(-n // groups)
    if per * groups != n:
        x = jnp.pad(x, [(0, per * groups - n)] + [(0, 0)] * (x.ndim - 1))
    return x.reshape((groups, per) + tuple(x.shape[1:]))


def _ungroup(tree, n: int):
    """Inverse of :func:`_group` over every leaf of a pmap output: the
    first ``n`` items, without the padding."""
    import jax

    return jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],)
                            + tuple(a.shape[2:]))[:n], tree)


# ---------------------------------------------------------------------------
# §IV rig-resident fused executor (DESIGN.md §8)
# ---------------------------------------------------------------------------


class VRRigExecutor:
    """Batched §IV hot path: vmapped BSSA depth over the rig's camera pairs
    + loop-free stereo panorama composition.

    Two jit regions per rig frame: ``depth_maps`` (rough -> splat ->
    refine_grid -> slice, vmapped over pairs; refinement dispatches to the
    Pallas bilateral-blur kernel on TPU) and ``panorama`` (batched
    cylindrical warp + one scatter-add feather blend).  With
    ``rig_parallel`` and more than one local device, pairs are split
    over the devices (one per device when they fit, else a zero-padded
    even split, e.g. 2 per chip for the 8-pair rig on a 4-chip host) —
    the software analogue of the
    paper's rig of 8 parallel per-pair FPGAs.  The rough disparity's 5x5
    SADs are separable box sums, exact for 8-bit views at 3840x2160; the
    depth step names its layers ``rough``, ``splat``, ``refine`` and
    ``slice`` (``camera.bssa.bssa_depth``).  With a ``telemetry``, each
    ``depth_maps`` call is one ``dispatch``/``vr_depth`` span that lasts
    until the depth maps are ready.  The per-pair Python loop over
    ``bssa_depth_ref`` is the oracle the benchmark
    (benchmarks/vr_depth_hotpath.py) and the parity tests measure against.
    """

    def __init__(self, spec, max_disp: int = 32, n_iters: int = 8,
                 ipd_px: float = 6.0, use_pallas: bool | None = None,
                 interpret: bool = False, rig_parallel: bool | None = None,
                 telemetry=None):
        import functools

        import jax

        from repro.camera.bssa import bssa_depth
        from repro.camera.stitch import stereo_panorama
        from repro.obs.telemetry import telemetry_on

        self.telemetry = telemetry
        self._tel_on = telemetry_on(telemetry)
        self.spec = spec
        self.max_disp = max_disp
        self.n_iters = n_iters
        self.ipd_px = ipd_px
        if rig_parallel is None:
            rig_parallel = jax.local_device_count() > 1
        self.rig_parallel = rig_parallel
        pair_depth = functools.partial(
            bssa_depth, spec=spec, max_disp=max_disp, n_iters=n_iters,
            use_pallas=use_pallas, interpret=interpret)
        # traceable handles for callers composing the rig pipeline into
        # their own jit regions (camera/offload's split executors)
        self.pair_depth = pair_depth
        self.pano_fn = functools.partial(stereo_panorama, ipd_px=ipd_px)
        if self._tel_on:
            # §15 in-graph counters: same dispatch, one extra int32 scalar
            # per region (TELEMETRY_AUX vr_rig.*); the disabled branch
            # below traces the exact pre-obs closures
            from repro.obs.counters import graph_counters

            def pair_depth_tel(left, right):
                return pair_depth(left, right), graph_counters(pairs=1)

            def pano_tel(lefts, rights, depths):
                pano = self.pano_fn(lefts, rights, depths)
                return pano, graph_counters(views=2 * lefts.shape[0])

            self._depth = jax.jit(jax.vmap(pair_depth_tel))
            self._depth_pmap = (jax.pmap(jax.vmap(pair_depth_tel))
                                if rig_parallel else None)
            self._pano = jax.jit(pano_tel)
        else:
            self._depth = jax.jit(jax.vmap(pair_depth))
            self._depth_pmap = (jax.pmap(jax.vmap(pair_depth))
                                if rig_parallel else None)
            self._pano = jax.jit(self.pano_fn)

    def depth_maps(self, lefts, rights):
        """(n_pairs, h, w) x2 -> (n_pairs, h, w) refined depth."""
        import jax
        import jax.numpy as jnp

        def dispatch():
            groups = _device_groups(lefts.shape[0], jax.local_device_count())
            if self._depth_pmap is not None and groups:
                return _ungroup(self._depth_pmap(_group(lefts, groups),
                                                 _group(rights, groups)),
                                lefts.shape[0])
            return self._depth(lefts, rights)

        if not self._tel_on:
            return dispatch()
        # the span lasts until the depth maps are ready, so the step's
        # device operations begin inside it
        with self.telemetry.span("dispatch", "vr_depth",
                                 t=time.perf_counter(),
                                 pairs=int(lefts.shape[0])):
            depths, aux = dispatch()
            depths.block_until_ready()
            self.telemetry.counters.add("vr.pairs",
                                        jnp.sum(aux["tel_pairs"]))
        return depths

    def panorama(self, lefts, rights, depths):
        """(left_pano, right_pano) from per-pair views + depth maps."""
        import jax.numpy as jnp

        if self._tel_on:
            pano, aux = self._pano(lefts, rights, depths)
            self.telemetry.counters.add("vr.views",
                                        jnp.sum(aux["tel_views"]))
            return pano
        return self._pano(lefts, rights, depths)

    def __call__(self, lefts, rights):
        """Full rig frame: returns (left_pano, right_pano, depths)."""
        depths = self.depth_maps(lefts, rights)
        left_pano, right_pano = self.panorama(lefts, rights, depths)
        return left_pano, right_pano, depths


# ---------------------------------------------------------------------------
# §III frame-to-auth streaming executor (DESIGN.md §9)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FAExecResult:
    """One stream's funnel output, every array in source-frame order.

    Leading axis B = frames in the batch (add a leading S axis for
    :meth:`FaceAuthExecutor.run_streams`).  ``window_id`` indexes the
    detector's ``grid.positions``; slots beyond a frame's detections carry
    ``window_id == -1`` / ``window_valid == False`` / ``scores == 0``.
    """

    motion: object            # (B,) bool — passed motion detection
    n_windows: object         # (B,) int32 exact detection count (pre-capacity)
    n_auth: object            # (B,) int32 authenticated windows
    scores: object            # (B, W) f32 NN scores
    window_id: object         # (B, W) int32 grid position id, -1 = padding
    window_valid: object      # (B, W) bool
    auth: object              # (B, W) bool score > threshold
    windows_dropped: object   # (B,) int32 detections beyond window capacity
    motion_dropped: object    # () int32 motion frames beyond frame capacity
    cascade_dropped: object   # (B,) int32 detector-internal capacity drops

    def total_dropped(self) -> int:
        """Sum of every drop counter — 0 means the funnel was lossless."""
        import numpy as np
        return int(np.asarray(self.motion_dropped).sum()
                   + np.asarray(self.windows_dropped).sum()
                   + np.asarray(self.cascade_dropped).sum())


@dataclasses.dataclass(frozen=True)
class FunnelStages:
    """Traceable stage closures of one configured §III funnel.

    Rebuilt by :meth:`FaceAuthExecutor._rebuild`; the fused funnel and the
    offload runtime's split executors (``repro.camera.offload``) compose
    these same functions, so a cut can never drift from the on-node math.
    ``split_consts`` partitions the executor's jit-argument tuple into the
    (detector, position-table, NN) constant groups the stages consume.
    """

    motion: object            # frames -> (mframes, fidx, fvalid, motion, motion_dropped)
    detect: object            # (mframes, fvalid, det_c) -> (dmask, n_win_m, casc_drop_m)
    gather: object            # (mframes, dmask, n_win_m, pos_c) -> (patches, wsel, wvalid, win_dropped_m)
    nn: object                # (patches, wvalid, nn_c) -> (s, auth, n_auth_m)
    scatter: object           # source-frame-order result dict
    split_consts: object      # consts tuple -> (det_c, pos_c, nn_c)
    window_capacity: int


class FaceAuthExecutor:
    """Fused §III hot path: the whole motion -> Viola-Jones -> 400-8-1 NN
    funnel as ONE jit region per frame batch — the software shape of the
    paper's sensor-resident ASIC chain, with no host round-trips between
    stages.

    Stages inside the single dispatch (DESIGN.md §9):

    1. **Motion gating** — frame-difference scores in-graph; motion-passing
       frames are *compacted* to a statically-bounded prefix
       (``frame_capacity``), the §2 capacity trick applied at frame
       granularity, so downstream work scales with the motion rate while
       shapes stay static.
    2. **Fused detection** — ``FusedDetector``'s traceable core (one frame
       integral image, Haar corner taps, compacting cascade).
    3. **Capacity-padded window gather** — per frame, up to
       ``window_capacity`` detected windows are gathered and
       nearest-resampled to 20x20 *on device* (integer-exact replica of
       ``viola_jones.extract_windows``); detections beyond capacity are
       dropped and counted, like MoE token dropping.
    4. **Int8 NN tail** — both layers through the quant_matmul kernel with
       static calibrated scales and the LUT sigmoid in-kernel
       (``kernels.quant_matmul.ops.nn_forward_quantized``).

    Multi-stream scaling: ``run_streams`` vmaps the funnel over N
    independent camera feeds on one device and splits them over the local
    devices when there are several — the WISPCam-fleet analogue of ``VRRigExecutor``'s rig
    parallelism.  The per-motion-frame host loop
    (``examples/camera_face_auth.py``'s cross-check, with
    ``extract_windows`` + ``forward_quantized``) is the golden oracle for
    funnel-count and score parity.
    """

    def __init__(self, cascade, nn, h: int, w: int, *, lut=None,
                 lut_meta=None, scale_factor: float = 1.25,
                 step: float = 0.025, adaptive: bool = True,
                 strictness: float = 0.0, capacities=None,
                 motion_threshold: float = 0.004, motion_factor: int = 8,
                 frame_capacity: int | None = None,
                 window_capacity: int = 64, bits: int = 8,
                 auth_threshold: float = 0.5, use_pallas: bool | None = None,
                 interpret: bool = False, stream_parallel: bool | None = None,
                 telemetry=None):
        import jax

        from repro.camera.face_nn import make_sigmoid_lut
        from repro.camera.viola_jones import FusedDetector
        from repro.kernels.quant_matmul.ops import quantize_nn
        from repro.obs.telemetry import telemetry_on

        # §15 telemetry: when enabled, the funnel emits static-shape
        # ``tel_`` int32 aux scalars from the SAME dispatch (checked at
        # _rebuild time — disabled executors trace the pre-obs jaxpr)
        self.telemetry = telemetry
        self._tel_on = telemetry_on(telemetry)

        if lut is None:
            lut, lut_meta = make_sigmoid_lut()
        elif lut_meta is None:
            raise ValueError("pass lut_meta alongside an explicit lut")
        self.lut = lut
        self.lut_meta = lut_meta
        self.det = FusedDetector(
            cascade, h, w, scale_factor=scale_factor, step=step,
            adaptive=adaptive, strictness=strictness, capacities=capacities,
            use_pallas=use_pallas, interpret=interpret)
        pos = np.asarray(self.det.grid.positions, np.int32)   # (n, 3)
        self._pos_y, self._pos_x, self._pos_win = pos[:, 0], pos[:, 1], pos[:, 2]
        self.nn = nn
        self.qnn = quantize_nn(nn, bits=bits)
        self.motion_threshold = float(motion_threshold)
        self.motion_factor = int(motion_factor)
        self.frame_capacity = frame_capacity
        self.window_capacity = int(window_capacity)
        self.auth_threshold = float(auth_threshold)
        self.use_pallas = use_pallas
        self.interpret = bool(interpret)
        if stream_parallel is None:
            stream_parallel = jax.local_device_count() > 1
        self.stream_parallel = bool(stream_parallel)
        self._rebuild()

    # -- jitted funnel -------------------------------------------------------

    def _rebuild(self):
        import jax
        import jax.numpy as jnp

        from repro.camera.motion import motion_score
        from repro.camera.viola_jones import BASE
        from repro.kernels.quant_matmul.ops import nn_forward_quantized

        det_fn = self.det.traceable_apply
        det_consts = self.det.apply_consts
        n_det = len(det_consts)
        pos_consts = tuple(jnp.asarray(a) for a in (
            self._pos_y, self._pos_x, self._pos_win))
        nn_consts = (self.qnn.w1_q, self.qnn.b1, self.qnn.w2_q, self.qnn.b2,
                     jnp.asarray(self.lut))
        consts = det_consts + pos_consts + nn_consts
        qnn, meta = self.qnn, self.lut_meta
        W = int(self.window_capacity)
        fcap = self.frame_capacity
        thr, factor = self.motion_threshold, self.motion_factor
        auth_thr = self.auth_threshold
        use_pallas, interpret = self.use_pallas, self.interpret

        # The funnel is factored into traceable stage closures so the
        # offload runtime (camera/offload) can split it at any legal cut
        # point into a node-side and a cloud-side jit region while the
        # fused funnel below composes the very same functions — one
        # implementation, two placements (DESIGN.md §10).  Each closure
        # names its layer with a jax.named_scope, so every device op of the
        # funnel carries it in its HLO op_name (no run-time cost).

        @jax.named_scope("motion")
        def stage_motion(frames):
            """-- 1. motion gating + frame compaction to capacity M ------"""
            frames = frames.astype(jnp.float32)
            B = frames.shape[0]
            M = B if fcap is None else max(1, min(int(fcap), B))
            msc = motion_score(frames[:-1], frames[1:], factor)
            motion = jnp.concatenate(
                [jnp.zeros((1,), bool), msc > thr])
            order = jnp.argsort(jnp.where(motion, 0, 1), stable=True)
            fidx = order[:M]
            fvalid = jnp.take(motion, fidx)
            motion_dropped = jnp.maximum(
                jnp.sum(motion).astype(jnp.int32) - M, 0)
            mframes = jnp.take(frames, fidx, axis=0)
            return mframes, fidx, fvalid, motion, motion_dropped

        @jax.named_scope("detect")
        def stage_detect(mframes, fvalid, det_c):
            """-- 2. fused VJ front-end (masked by the motion gate) ------

            The detector's compacting cascade has its own capacities; its
            internal drops on motion-valid frames must surface too (the §9
            contract: dropped and counted, never silent)."""
            dmask, _surv, ddrop = det_fn(mframes, *det_c)
            dmask = dmask & fvalid[:, None]
            casc_drop_m = jnp.where(fvalid,
                                    jnp.sum(ddrop, axis=1), 0).astype(jnp.int32)
            n_win_m = jnp.sum(dmask, axis=1).astype(jnp.int32)
            return dmask, n_win_m, casc_drop_m

        @jax.named_scope("gather")
        def stage_gather(mframes, dmask, n_win_m, pos_c):
            """-- 3. capacity-padded window gather + 20x20 resample ------

            O(n) stable compaction (a full argsort over 25k window slots
            per frame would dominate the funnel): rank survivors by prefix
            count, scatter their indices into W slots, dump overflow +
            dead windows into a discard slot."""
            pos_y, pos_x, pos_win = pos_c
            M = mframes.shape[0]
            col = jnp.arange(dmask.shape[1], dtype=jnp.int32)
            rank = jnp.cumsum(dmask.astype(jnp.int32), axis=1) - 1
            slot = jnp.where(dmask & (rank < W), rank, W)
            wsel = jnp.zeros((M, W + 1), jnp.int32).at[
                jnp.arange(M)[:, None], slot].set(col[None, :])[:, :W]
            wvalid = (jnp.arange(W, dtype=jnp.int32)[None, :]
                      < jnp.minimum(n_win_m, W)[:, None])
            win_dropped_m = jnp.maximum(n_win_m - W, 0)
            wy = jnp.take(pos_y, wsel)
            wx = jnp.take(pos_x, wsel)
            ww = jnp.take(pos_win, wsel)                       # (M, W)
            t = jnp.arange(BASE, dtype=jnp.int32)
            # integer-exact replica of extract_windows' nearest resample:
            # (arange(20) * win // 20).clip(0, win - 1)
            off = jnp.minimum(t[None, None, :] * ww[:, :, None] // BASE,
                              ww[:, :, None] - 1)              # (M, W, 20)
            # two-sided clamp before the PROMISE_IN_BOUNDS patch gather:
            # every (pos_y, pos_x, pos_win) row fits the frame by
            # construction, so this is a no-op for real tables — it makes
            # the in-bounds promise *static* instead of data-dependent
            h_m, w_m = mframes.shape[-2:]
            rows = jnp.clip(wy[:, :, None] + off, 0, h_m - 1)
            cols = jnp.clip(wx[:, :, None] + off, 0, w_m - 1)
            patches = jax.vmap(
                lambda fr, r, co: fr[r[:, :, None], co[:, None, :]])(
                    mframes, rows, cols)                       # (M, W, 20, 20)
            return patches, wsel, wvalid, win_dropped_m

        @jax.named_scope("nn")
        def stage_nn(patches, wvalid, nn_c):
            """-- 4. int8 NN tail (both layers on the quant kernel) ------"""
            w1_q, b1, w2_q, b2, lut = nn_c
            M, Wc = patches.shape[:2]
            x = patches.reshape(M * Wc, BASE * BASE)
            q = dataclasses.replace(qnn, w1_q=w1_q, b1=b1, w2_q=w2_q, b2=b2)
            s = nn_forward_quantized(q, x, lut, meta,
                                     use_pallas=use_pallas,
                                     interpret=interpret).reshape(M, Wc)
            s = jnp.where(wvalid, s, 0.0)
            auth = wvalid & (s > auth_thr)
            n_auth_m = jnp.sum(auth, axis=1).astype(jnp.int32)
            return s, auth, n_auth_m

        @jax.named_scope("scatter")
        def stage_scatter(B, fidx, motion, motion_dropped, n_win_m,
                          casc_drop_m, wsel, wvalid, win_dropped_m,
                          s, auth, n_auth_m):
            """-- scatter back to source-frame order ---------------------"""
            return dict(
                motion=motion,
                n_windows=jnp.zeros((B,), jnp.int32).at[fidx].set(n_win_m),
                n_auth=jnp.zeros((B,), jnp.int32).at[fidx].set(n_auth_m),
                scores=jnp.zeros((B, W), s.dtype).at[fidx].set(s),
                window_id=jnp.full((B, W), -1, jnp.int32).at[fidx].set(
                    jnp.where(wvalid, wsel.astype(jnp.int32), -1)),
                window_valid=jnp.zeros((B, W), bool).at[fidx].set(wvalid),
                auth=jnp.zeros((B, W), bool).at[fidx].set(auth),
                windows_dropped=jnp.zeros((B,), jnp.int32).at[fidx].set(
                    win_dropped_m),
                motion_dropped=motion_dropped,
                cascade_dropped=jnp.zeros((B,), jnp.int32).at[fidx].set(
                    casc_drop_m),
            )

        def split_consts(c):
            return c[:n_det], c[n_det:n_det + 3], c[n_det + 3:]

        def funnel(frames, *c):
            det_c, pos_c, nn_c = split_consts(c)
            B = frames.shape[0]
            mframes, fidx, fvalid, motion, motion_dropped = stage_motion(
                frames)
            dmask, n_win_m, casc_drop_m = stage_detect(mframes, fvalid, det_c)
            patches, wsel, wvalid, win_dropped_m = stage_gather(
                mframes, dmask, n_win_m, pos_c)
            s, auth, n_auth_m = stage_nn(patches, wvalid, nn_c)
            return stage_scatter(B, fidx, motion, motion_dropped, n_win_m,
                                 casc_drop_m, wsel, wvalid, win_dropped_m,
                                 s, auth, n_auth_m)

        if self._tel_on:
            # §15 in-graph counters: tel_ int32 scalars hoisted out of the
            # same dispatch (TELEMETRY_AUX["face_auth.funnel"]).  Gated at
            # rebuild time, so a disabled executor traces the exact jaxpr
            # above and returns bit-identical outputs.
            from repro.obs.counters import graph_counters

            fused = funnel

            def funnel(frames, *c):
                out = fused(frames, *c)
                out.update(graph_counters(
                    windows=jnp.sum(out["n_windows"]),
                    auth=jnp.sum(out["n_auth"]),
                    motion_dropped=out["motion_dropped"],
                    cascade_dropped=jnp.sum(out["cascade_dropped"])))
                return out

        self.stages = FunnelStages(
            motion=stage_motion, detect=stage_detect, gather=stage_gather,
            nn=stage_nn, scatter=stage_scatter, split_consts=split_consts,
            window_capacity=W)
        self._consts = consts
        self._funnel = funnel
        self._single = jax.jit(funnel)
        streams_axes = (0,) + (None,) * len(consts)
        per_stream = jax.vmap(funnel, in_axes=streams_axes)
        self._multi = jax.jit(per_stream)
        self._pmapped = (jax.pmap(per_stream, in_axes=streams_axes)
                         if self.stream_parallel else None)
        self._batch_steps = {}   # (n_streams, chunk, pmap) -> step closure

    # -- calibration ---------------------------------------------------------

    def calibrate(self, frames, margin: float = 2.0, quantum: int = 32,
                  frame_margin: float = 1.25):
        """Measure the workload's funnel on calibration frames and set every
        capacity knob from it (the §2 measure-then-set procedure): cascade
        compaction capacities (via ``FusedDetector.calibrate``), the
        per-batch motion-frame capacity, and the per-frame window capacity.
        Returns (frame_capacity, window_capacity, cascade_capacities).

        ``frame_margin`` is deliberately tighter than the window ``margin``:
        every spare frame slot re-pays the whole detection front-end,
        whereas a spare window slot only pays 400 int8 MACs — and
        motion-frame overflow degrades gracefully (dropped frames are
        counted in ``motion_dropped``, never silently wrong).
        """
        import math

        import jax.numpy as jnp

        from repro.camera.motion import motion_mask

        frames = np.asarray(frames, np.float32)
        mask, _ = motion_mask(jnp.asarray(frames), self.motion_threshold,
                              self.motion_factor)
        midx = np.where(np.asarray(mask))[0]
        max_w = 1
        if len(midx):
            self.det.calibrate(frames[midx[:4]])
            dets, _stats = self.det.detect(frames[midx])
            max_w = max((len(d) for d in dets), default=1)
        fcap = int(math.ceil(len(midx) * frame_margin))
        self.frame_capacity = int(min(len(frames), max(4, (fcap + 3) // 4 * 4)))
        wcap = (int(math.ceil(max_w * margin)) // quantum + 1) * quantum
        self.window_capacity = int(min(self.det.n_windows,
                                       max(quantum, wcap)))
        self._rebuild()
        return self.frame_capacity, self.window_capacity, list(self.det.capacities)

    # -- execution -----------------------------------------------------------

    def __call__(self, frames) -> FAExecResult:
        """One stream: (B, h, w) frames -> :class:`FAExecResult`."""
        import jax.numpy as jnp

        out = self._single(jnp.asarray(frames), *self._consts)
        if self._tel_on:
            # pop the tel_ aux scalars into the panel device-lazily — no
            # host sync here; totals() materializes at export time
            out = self.telemetry.counters.consume(dict(out), prefix="fa.")
        return FAExecResult(**out)

    def batch_step(self, n_streams: int, chunk: int,
                   stream_parallel: bool | None = None, devices=None):
        """Re-entrant capacity-padded micro-batch step for the serving
        runtime (DESIGN.md §13).

        Returns ``step(frames, valid) -> dict`` where ``frames`` is
        ``(n_streams, chunk, h, w)`` and ``valid`` is ``(n_streams,)`` bool;
        the result dict has the :class:`FAExecResult` fields with a leading
        ``n_streams`` axis (``motion_dropped`` becomes ``(n_streams,)``).
        Invalid slots carry the canonical quiet result — ``motion`` False,
        ``window_id`` -1, everything else zero — exactly what the funnel
        emits for a motionless chunk, so padding a micro-batch can never be
        told apart from serving a quiet stream.

        One jit dispatch per call: the same ``FunnelStages`` funnel vmapped
        across the stream axis, with one pmap shard per device when
        ``stream_parallel`` and the device count divides ``n_streams``.
        ``devices`` restricts the pmap to an explicit device subset — the
        failover path (DESIGN.md §14): a serving runtime that loses a
        device re-requests the closure over the survivors, and falls back
        to the single-device vmap jit when they stop dividing the batch.
        Closures are cached per ``(n_streams, chunk, device-set)`` and
        invalidated by :meth:`calibrate`'s rebuild, so a scheduler can call
        the step every tick without retracing.
        """
        import jax
        import jax.numpy as jnp

        if stream_parallel is None:
            stream_parallel = self.stream_parallel
        if devices is not None:
            devices = tuple(devices)
            if not devices:
                raise ValueError("batch_step: devices must be non-empty "
                                 "when given — a group with zero devices "
                                 "cannot serve")
            ndev = len(devices)
        else:
            ndev = jax.local_device_count()
        use_pmap = bool(stream_parallel) and ndev > 1 and n_streams % ndev == 0
        if not use_pmap:
            # the vmap fallback never touches `devices`; normalizing the
            # key means failing over to it (survivors stop dividing the
            # batch) reuses the already-compiled single-device closure
            devices = None
        key = (int(n_streams), int(chunk), use_pmap,
               None if devices is None else tuple(d.id for d in devices))
        cached = self._batch_steps.get(key)
        if cached is not None:
            return cached

        funnel, consts = self._funnel, self._consts

        def step_core(frames, valid, *c):
            res = jax.vmap(funnel, in_axes=(0,) + (None,) * len(c))(
                frames, *c)
            def quiet(name, a):
                fill = (jnp.full_like(a, -1) if name == "window_id"
                        else jnp.zeros_like(a))
                keep = valid.reshape(valid.shape + (1,) * (a.ndim - 1))
                return jnp.where(keep, a, fill)
            return {k: quiet(k, v) for k, v in res.items()}

        if use_pmap:
            shard = jax.pmap(step_core,
                             in_axes=(0, 0) + (None,) * len(consts),
                             devices=None if devices is None
                             else list(devices))

            def step(frames, valid):
                self._check_step_args(frames, valid, n_streams, chunk)
                fr = frames.reshape((ndev, n_streams // ndev)
                                    + tuple(frames.shape[1:]))
                va = valid.reshape(ndev, n_streams // ndev)
                out = shard(fr, va, *consts)
                return {k: v.reshape((n_streams,) + tuple(v.shape[2:]))
                        for k, v in out.items()}
        else:
            jitted = jax.jit(step_core)

            def step(frames, valid):
                self._check_step_args(frames, valid, n_streams, chunk)
                return jitted(frames, valid, *consts)

        # the raw traceable core (consts as explicit args) — what the
        # static analyzer registers, the same way it traces self._funnel
        step._core = step_core
        step._consts = consts
        self._batch_steps[key] = step
        return step

    @staticmethod
    def _check_step_args(frames, valid, n_streams, chunk):
        if tuple(frames.shape[:2]) != (n_streams, chunk):
            raise ValueError(
                f"batch_step closure is shape-bound: expected frames "
                f"({n_streams}, {chunk}, h, w), got {tuple(frames.shape)} — "
                "request a new closure via batch_step() instead of reusing "
                "one across micro-batch geometries")
        if tuple(valid.shape) != (n_streams,):
            raise ValueError(
                f"valid must be ({n_streams},) bool, got "
                f"{tuple(valid.shape)}")

    def run_streams(self, frames) -> FAExecResult:
        """N independent feeds: (S, B, h, w) -> FAExecResult with leading S.

        With ``stream_parallel``, streams are split over the local devices
        via pmap (one per device when they fit, the batch padded with quiet
        zero streams to a multiple of the device count when they do not);
        otherwise all streams are vmapped on one device.
        """
        import jax
        import jax.numpy as jnp

        frames = jnp.asarray(frames)
        groups = _device_groups(frames.shape[0], jax.local_device_count())
        if self._pmapped is not None and groups:
            out = _ungroup(self._pmapped(_group(frames, groups),
                                         *self._consts), frames.shape[0])
        else:
            out = self._multi(frames, *self._consts)
        if self._tel_on:
            # vmapped/pmapped tel_ aux carry a leading stream axis — sum
            # device-side before the lazy accumulate (still no host sync)
            out = dict(out)
            for k in [k for k in out if k.startswith("tel_")]:
                self.telemetry.counters.add("fa." + k[4:],
                                            jnp.sum(out.pop(k)))
        return FAExecResult(**out)
