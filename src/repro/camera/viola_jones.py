"""Viola-Jones face detection: Haar features, AdaBoost cascade, scanning.

Paper §III-B reproduced end to end:

* rectangular Haar features evaluated on the integral image;
* a trained cascade — 10 stages x 33 weak classifiers (Table I: "Cascade
  10x33") fitted with AdaBoost on the synthetic face set, each stage's
  threshold tuned to a target per-stage recall (the classic cascade
  construction);
* window scanning with *scale factor* and *step size* knobs, including the
  paper's adaptive step ("a percentage of the window size") — Fig. 4a/4c;
* the cost model counts classifier invocations and per-window feature
  evaluations, reproducing the "86% fewer invocations at scale 1.25 /
  adaptive 2.5% with no accuracy loss" result.

Execution model (DESIGN.md §3): the production path is the *frame-resident
fused front-end* (:class:`FusedDetector` / :func:`detect_faces_batch`) —
one frame-level integral image, every window at every scale evaluated by
corner taps into that single table (strided slices for stage 0, which
scores the whole grid; gathers for the survivor stages), and the stage loop
routed through ``core.cascade.compacting_cascade`` so later stages only
compute on survivors.  :func:`detect_faces` is the slow reference (golden
oracle): per-window integral images and a Python loop over features,
evaluating the *same* scaled-feature math.  Scaled-feature semantics
(classic VJ: scale the features, not the image) replaced the seed's
nearest-neighbor window resampling — resampled row subsets are not
contiguous rectangles, so they cannot be expressed as corner lookups in a
frame integral image, while scaled features can, exactly; at the training
scale (win == 20) the two are identical.

The *invocation count* (what the paper's energy model charges for) is the
number of stage evaluations a data-dependent implementation would run,
computed exactly from the survivor masks.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.camera.integral import frame_integral, integral_image, window_sum
from repro.core.cascade import (
    Stage as CoreStage,
    capacities_from_counts,
    compacting_cascade,
)

BASE = 20    # canonical window resolution (matches the NN input 20x20)


# ---------------------------------------------------------------------------
# Haar features on the canonical 20x20 window
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HaarFeature:
    """Two/three-rectangle feature, coordinates in the canonical window."""
    kind: int            # 0: 2-rect horiz, 1: 2-rect vert, 2: 3-rect horiz, 3: 3-rect vert
    y: int
    x: int
    h: int
    w: int


def make_feature_pool(seed: int = 0, n: int = 400) -> list:
    rng = np.random.default_rng(seed)
    pool = []
    while len(pool) < n:
        kind = int(rng.integers(0, 4))
        nsplit = 2 if kind < 2 else 3
        if kind in (0, 2):   # horizontal split: w divisible
            w = max(nsplit, (int(rng.integers(nsplit, BASE // 2 + 1)) // nsplit) * nsplit)
            h = int(rng.integers(2, BASE // 2 + 1))
        else:
            h = max(nsplit, (int(rng.integers(nsplit, BASE // 2 + 1)) // nsplit) * nsplit)
            w = int(rng.integers(2, BASE // 2 + 1))
        y = int(rng.integers(0, BASE - h + 1))
        x = int(rng.integers(0, BASE - w + 1))
        pool.append(HaarFeature(kind, y, x, h, w))
    for f in pool:
        split = f.w if f.kind in (0, 2) else f.h
        assert split % (2 if f.kind < 2 else 3) == 0, f
    return pool


def scale_feature(f: HaarFeature, win: int) -> HaarFeature:
    """Scale a canonical-20x20 feature to a ``win`` x ``win`` window.

    Rounds each dimension while preserving split divisibility (the 2-/3-way
    split stays exact) and clamps inside the window.  At ``win == BASE``
    this is the identity — the scale the cascade was trained at.  Both the
    reference detector and the gather tables go through this one function,
    so the two paths always evaluate the same rectangles.
    """
    s = win / BASE
    if f.kind == 0:
        part = max(1, int(round(f.w / 2 * s)))
        w, h = 2 * part, max(1, int(round(f.h * s)))
    elif f.kind == 1:
        part = max(1, int(round(f.h / 2 * s)))
        h, w = 2 * part, max(1, int(round(f.w * s)))
    elif f.kind == 2:
        part = max(1, int(round(f.w / 3 * s)))
        w, h = 3 * part, max(1, int(round(f.h * s)))
    else:
        part = max(1, int(round(f.h / 3 * s)))
        h, w = 3 * part, max(1, int(round(f.w * s)))
    wq = 2 if f.kind == 0 else (3 if f.kind == 2 else 1)
    hq = 2 if f.kind == 1 else (3 if f.kind == 3 else 1)
    while w > win:
        w -= wq
    while h > win:
        h -= hq
    y = min(max(int(round(f.y * s)), 0), win - h)
    x = min(max(int(round(f.x * s)), 0), win - w)
    return HaarFeature(f.kind, y, x, h, w)


CORNER_SLOTS = 8     # max corner taps per feature (3-rect decomposition)


def feature_corners(f: HaarFeature):
    """Corner-tap decomposition: [(dy, dx, weight), ...], <= 8 taps.

    Merging the shared edges of the 2-/3-rect sums collapses the naive
    8/12 integral-image lookups to 6/8 with static +-1/+-2/+-3 weights:
    response = sum_k weight_k * ii[y0 + dy_k, x0 + dx_k] for a window whose
    top-left corner maps to ii position (y0, x0).
    """
    y, x, h, w = f.y, f.x, f.h, f.w
    if f.kind == 0:      # left - right
        hw = w // 2
        return [(y, x, 1.0), (y + h, x, -1.0),
                (y, x + hw, -2.0), (y + h, x + hw, 2.0),
                (y, x + w, 1.0), (y + h, x + w, -1.0)]
    if f.kind == 1:      # top - bottom
        hh = h // 2
        return [(y, x, 1.0), (y, x + w, -1.0),
                (y + hh, x, -2.0), (y + hh, x + w, 2.0),
                (y + h, x, 1.0), (y + h, x + w, -1.0)]
    if f.kind == 2:      # sides - 2*middle, horizontal thirds
        w3 = w // 3
        return [(y, x, 1.0), (y, x + w3, -3.0),
                (y, x + 2 * w3, 3.0), (y, x + w, -1.0),
                (y + h, x, -1.0), (y + h, x + w3, 3.0),
                (y + h, x + 2 * w3, -3.0), (y + h, x + w, 1.0)]
    h3 = h // 3          # sides - 2*middle, vertical thirds
    return [(y, x, 1.0), (y + h3, x, -3.0),
            (y + 2 * h3, x, 3.0), (y + h, x, -1.0),
            (y, x + w, -1.0), (y + h3, x + w, 3.0),
            (y + 2 * h3, x + w, -3.0), (y + h, x + w, 1.0)]


def _haar_response(ii: jax.Array, f: HaarFeature) -> jax.Array:
    """Raw (unnormalized) response of one feature via rectangle sums."""
    if f.kind == 0:      # 2-rect horizontal: left - right
        wl = window_sum(ii, f.y, f.x, f.h, f.w // 2)
        wr = window_sum(ii, f.y, f.x + f.w // 2, f.h, f.w // 2)
        return wl - wr
    if f.kind == 1:      # 2-rect vertical: top - bottom
        wt = window_sum(ii, f.y, f.x, f.h // 2, f.w)
        wb = window_sum(ii, f.y + f.h // 2, f.x, f.h // 2, f.w)
        return wt - wb
    if f.kind == 2:      # 3-rect horizontal: sides - 2*middle
        w3 = f.w // 3
        a = window_sum(ii, f.y, f.x, f.h, w3)
        b = window_sum(ii, f.y, f.x + w3, f.h, w3)
        c = window_sum(ii, f.y, f.x + 2 * w3, f.h, w3)
        return a + c - 2 * b
    h3 = f.h // 3        # 3-rect vertical
    a = window_sum(ii, f.y, f.x, h3, f.w)
    b = window_sum(ii, f.y + h3, f.x, h3, f.w)
    c = window_sum(ii, f.y + 2 * h3, f.x, h3, f.w)
    return a + c - 2 * b


def eval_features(windows: jax.Array, feats: list) -> jax.Array:
    """windows: (n, 20, 20) -> (n, n_feats) Haar responses (variance-normalized).

    Evaluated via each window's integral image — the same arithmetic the
    streaming accelerator performs, vectorized over windows.
    """
    ii = integral_image(windows)                     # (n, 21, 21)
    mu = window_sum(ii, 0, 0, BASE, BASE) / (BASE * BASE)
    sq = integral_image(windows * windows)
    var = window_sum(sq, 0, 0, BASE, BASE) / (BASE * BASE) - mu * mu
    sd = jnp.sqrt(jnp.maximum(var, 1e-6))
    cols = [_haar_response(ii, f) / (sd * BASE * BASE) for f in feats]
    return jnp.stack(cols, axis=-1)


def eval_features_scaled(patches: jax.Array, win: int, feats: list) -> jax.Array:
    """Native-resolution windows (n, win, win) -> (n, n_feats) responses
    with the canonical features *scaled* to the window (classic VJ: scale
    the features, not the image).  At ``win == BASE`` this is exactly
    :func:`eval_features`."""
    area = win * win
    ii = integral_image(patches)
    sq = integral_image(patches * patches)
    mu = window_sum(ii, 0, 0, win, win) / area
    var = window_sum(sq, 0, 0, win, win) / area - mu * mu
    sd = jnp.sqrt(jnp.maximum(var, 1e-6))
    cols = [_haar_response(ii, scale_feature(f, win)) / (sd * win * win)
            for f in feats]
    return jnp.stack(cols, axis=-1)


# ---------------------------------------------------------------------------
# AdaBoost cascade (10 stages x 33 weak classifiers, Table I)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cascade:
    feats: list                     # selected HaarFeatures, flat
    thresholds: np.ndarray          # (n_weak,) decision-stump thresholds
    polarity: np.ndarray            # (n_weak,) +-1
    alphas: np.ndarray              # (n_weak,) AdaBoost weights
    stage_sizes: list               # weak-classifier count per stage
    stage_thresholds: np.ndarray    # (n_stages,) stage pass thresholds

    @property
    def n_stages(self):
        return len(self.stage_sizes)


def train_cascade(X: np.ndarray, y: np.ndarray, pool: list,
                  n_stages: int = 10, per_stage: int = 33,
                  stage_recall: float = 0.995, seed: int = 0) -> Cascade:
    """AdaBoost decision stumps per stage; stage thresholds set to hit
    ``stage_recall`` on training positives (classic VJ construction:
    Fig. 4b's nested tree with cheap-front stages)."""
    rng = np.random.default_rng(seed)
    windows = jnp.asarray(X.reshape(-1, BASE, BASE))
    F = np.asarray(eval_features(windows, pool))     # (n, n_pool)
    yb = y.astype(np.float64) * 2 - 1

    active = np.ones(len(X), bool)                   # survivors so far
    feats, thresholds, polarity, alphas = [], [], [], []
    stage_sizes, stage_thrs = [], []

    for _ in range(n_stages):
        idx = np.where(active)[0]
        if len(idx) < 10 or (y[idx] == 1).sum() < 5 or (y[idx] == 0).sum() < 2:
            break
        Xi, yi = F[idx], yb[idx]
        w = np.ones(len(idx)) / len(idx)
        stage_score = np.zeros(len(idx))
        stage_feats = []
        for _k in range(per_stage):
            # best stump over a random subsample of the pool (speed)
            cand = rng.choice(len(pool), size=min(80, len(pool)), replace=False)
            best = None
            for ci in cand:
                vals = Xi[:, ci]
                order = np.argsort(vals)
                sv, sy, sw = vals[order], yi[order], w[order]
                # threshold between consecutive values; vectorized error
                cum_pos = np.cumsum(sw * (sy > 0))
                cum_neg = np.cumsum(sw * (sy < 0))
                tot_pos, tot_neg = cum_pos[-1], cum_neg[-1]
                # polarity +1: predict + if val > thr
                err_p = cum_pos + (tot_neg - cum_neg)
                err_m = cum_neg + (tot_pos - cum_pos)
                i_p, i_m = np.argmin(err_p), np.argmin(err_m)
                if err_p[i_p] <= err_m[i_m]:
                    err, i_thr, pol = err_p[i_p], i_p, 1.0
                else:
                    err, i_thr, pol = err_m[i_m], i_m, -1.0
                thr = sv[min(i_thr, len(sv) - 1)]
                if best is None or err < best[0]:
                    best = (err, ci, thr, pol)
            err, ci, thr, pol = best
            err = min(max(err, 1e-10), 1 - 1e-10)
            alpha = 0.5 * np.log((1 - err) / err)
            pred = pol * np.sign(Xi[:, ci] - thr)
            pred[pred == 0] = 1
            w = w * np.exp(-alpha * yi * pred)
            w /= w.sum()
            stage_score += alpha * pred
            feats.append(pool[ci])
            thresholds.append(thr)
            polarity.append(pol)
            alphas.append(alpha)
            stage_feats.append(ci)
        # stage threshold for target recall on positives
        pos_scores = np.sort(stage_score[yi > 0])
        k = max(0, int((1 - stage_recall) * len(pos_scores)) - 1)
        thr_stage = pos_scores[k] - 1e-9 if len(pos_scores) else 0.0
        stage_thrs.append(thr_stage)
        stage_sizes.append(len(stage_feats))
        # survivors: windows passing this stage
        passed = stage_score >= thr_stage
        active[idx] = passed

    return Cascade(feats, np.array(thresholds), np.array(polarity),
                   np.array(alphas), stage_sizes, np.array(stage_thrs))


def _run_stages(cascade: Cascade, F: jax.Array, strictness: float = 0.0):
    """Stump votes + masked stage loop on precomputed features (n, n_weak).

    Returns (accepted (n,) bool, stage_evals (n,) int32 — how many stages a
    data-dependent implementation would evaluate per window; the energy
    model charges exactly this).
    """
    pol = jnp.asarray(cascade.polarity, jnp.float32)
    thr = jnp.asarray(cascade.thresholds, jnp.float32)
    al = jnp.asarray(cascade.alphas, jnp.float32)
    pred = pol * jnp.sign(F - thr)
    pred = jnp.where(pred == 0, 1.0, pred)
    weighted = al * pred                              # (n, n_weak)

    alive = jnp.ones(F.shape[0], bool)
    evals = jnp.zeros(F.shape[0], jnp.int32)
    off = 0
    for si, size in enumerate(cascade.stage_sizes):
        evals = evals + alive.astype(jnp.int32)
        score = jnp.sum(weighted[:, off:off + size], axis=1)
        alive = alive & (score >= cascade.stage_thresholds[si] + strictness)
        off += size
    return alive, evals


def cascade_apply(cascade: Cascade, windows: jax.Array):
    """Run the cascade on canonical (n, 20, 20) windows (training scale)."""
    F = eval_features(windows, cascade.feats)        # (n, n_weak)
    return _run_stages(cascade, F)


# ---------------------------------------------------------------------------
# Window scanning (Fig. 4a): scale pyramid + (adaptive) step
# ---------------------------------------------------------------------------


def scan_positions(h: int, w: int, scale_factor: float = 1.25,
                   step: float = 0.025, adaptive: bool = True,
                   min_window: int = BASE):
    """Yield (y, x, win) scanning positions per Fig. 4a.

    ``adaptive`` step = max(1, step * window) pixels (the paper's 2.5%
    choice); non-adaptive uses ``int(step)`` pixels at every scale.
    """
    out = []
    win = float(min_window)
    while win <= min(h, w):
        iw = int(round(win))
        # adaptive floor of 2 px: the paper's 2.5%-of-window step on its
        # (higher-resolution) imagery never reaches sub-pixel steps; at our
        # 176x144 scale the equivalent relative step floors at 2 px
        s = max(2, int(round(step * iw))) if adaptive else max(1, int(step))
        for y in range(0, h - iw + 1, s):
            for x in range(0, w - iw + 1, s):
                out.append((y, x, iw))
        win *= scale_factor
    return out


def extract_windows(frame: np.ndarray, positions) -> np.ndarray:
    """Resample each scanning window to the canonical 20x20 (nearest)."""
    out = np.empty((len(positions), BASE, BASE), np.float32)
    for i, (y, x, win) in enumerate(positions):
        patch = frame[y:y + win, x:x + win]
        yy = (np.arange(BASE) * win // BASE).clip(0, win - 1)
        xx = (np.arange(BASE) * win // BASE).clip(0, win - 1)
        out[i] = patch[np.ix_(yy, xx)]
    return out


def detect_faces(cascade: Cascade, frame: np.ndarray, scale_factor=1.25,
                 step=0.025, adaptive=True, strictness: float = 0.0,
                 chunk: int = 1024):
    """Full-frame detection — the slow *reference* path (golden oracle).

    Returns (detections, n_invocations, n_stage_evals).  Every scanning
    window is materialized at native resolution, gets its own integral
    image, and the features (scaled to the window) are evaluated in a
    Python loop — the per-window dataflow the paper's streaming ASIC
    executes, with no early-exit savings.  :func:`detect_faces_batch`
    computes the same math from one frame-level integral image and is what
    production uses; tests pin the two to identical detection sets.

    ``strictness`` adds a margin to every stage threshold — the deployment
    precision/recall knob (the paper tunes stage thresholds the same way).
    """
    pos = scan_positions(frame.shape[0], frame.shape[1], scale_factor, step, adaptive)
    if not pos:
        return [], 0, 0
    dets, total_evals = [], 0
    i = 0
    while i < len(pos):                 # scan order is scale-major
        win = pos[i][2]
        j = i
        while j < len(pos) and pos[j][2] == win:
            j += 1
        for c0 in range(i, j, chunk):
            group = pos[c0:min(c0 + chunk, j)]
            patches = np.stack([frame[y:y + win, x:x + win]
                                for (y, x, _w) in group])
            F = eval_features_scaled(jnp.asarray(patches), win, cascade.feats)
            alive, evals = _run_stages(cascade, F, strictness)
            dets.extend(group[k] for k in np.where(np.asarray(alive))[0])
            total_evals += int(np.asarray(evals).sum())
        i = j
    return dets, len(pos), total_evals


# ---------------------------------------------------------------------------
# Frame-resident fused front-end (DESIGN.md §3): one integral image,
# Haar features as corner taps, compacting cascade
# ---------------------------------------------------------------------------

_NORM_W = np.array([1.0, -1.0, -1.0, 1.0], np.float32)   # window-sum corners


@dataclasses.dataclass(frozen=True)
class ScanGrid:
    """Static scan geometry for one (frame shape, scan parameters) pair:
    every (y, x, win) position, each window's flat *base* index into the
    zero-padded (h+1, w+1) integral image, its pyramid-scale id, and each
    scale's windows as one row-major rectangle of top-left corners."""

    h: int
    w: int
    positions: tuple
    scales: tuple                # distinct window sizes, pyramid order
    bases: np.ndarray            # (n,) int32: y * (w + 1) + x
    scale_id: np.ndarray         # (n,) int32 index into ``scales``
    rects: tuple                 # per scale (y0, x0, ny, nx, sy, sx)


def _scale_rect(corners: list) -> tuple:
    """(y0, x0, ny, nx, sy, sx) of one scale's window corners, which must
    be exactly the row-major grid {(y0 + i*sy, x0 + j*sx)}."""
    ys = sorted({y for y, _x in corners})
    xs = sorted({x for _y, x in corners})
    sy = ys[1] - ys[0] if len(ys) > 1 else 1
    sx = xs[1] - xs[0] if len(xs) > 1 else 1
    rect = (ys[0], xs[0], len(ys), len(xs), sy, sx)
    if corners != [(ys[0] + i * sy, xs[0] + j * sx)
                   for i in range(len(ys)) for j in range(len(xs))]:
        raise ValueError(f"the windows of one scale are not a single "
                         f"row-major grid (nearest fit {rect}); a scale "
                         f"factor that repeats a window size gives two")
    return rect


@functools.lru_cache(maxsize=32)
def build_scan_grid(h: int, w: int, scale_factor: float = 1.25,
                    step: float = 0.025, adaptive: bool = True) -> ScanGrid:
    pos = scan_positions(h, w, scale_factor, step, adaptive)
    scales, sid = [], []
    for (_y, _x, win) in pos:
        if not scales or scales[-1] != win:
            scales.append(win)
        sid.append(len(scales) - 1)
    bases = np.array([y * (w + 1) + x for (y, x, _win) in pos], np.int32)
    rects = tuple(_scale_rect([(y, x) for (y, x, win) in pos if win == s])
                  for s in scales)
    return ScanGrid(h, w, tuple(pos), tuple(scales), bases,
                    np.array(sid, np.int32), rects)


@dataclasses.dataclass(frozen=True)
class GatherTables:
    """Per-(cascade, grid) corner-tap tensors for the fused front-end:
    each weak classifier as <= 8 integral-image taps, coordinates scaled
    per pyramid level and flattened to base-relative offsets."""

    offsets: np.ndarray          # (n_scales, n_weak, CORNER_SLOTS) int32
    weights: np.ndarray          # (n_weak, CORNER_SLOTS) f32, 0-padded
    areas: np.ndarray            # (n_scales,) f32 win^2
    thresholds: np.ndarray       # (n_weak,) stump params
    polarity: np.ndarray
    alphas: np.ndarray
    stage_sizes: tuple
    stage_thresholds: np.ndarray


def build_gather_tables(cascade: Cascade, grid: ScanGrid) -> GatherTables:
    stride = grid.w + 1
    n_weak = len(cascade.feats)
    offsets = np.zeros((len(grid.scales), n_weak, CORNER_SLOTS), np.int32)
    weights = np.zeros((n_weak, CORNER_SLOTS), np.float32)
    for k, f in enumerate(cascade.feats):
        for c, (_dy, _dx, wv) in enumerate(feature_corners(f)):
            weights[k, c] = wv     # weight pattern is scale-invariant
    for s, win in enumerate(grid.scales):
        for k, f in enumerate(cascade.feats):
            for c, (dy, dx, _wv) in enumerate(
                    feature_corners(scale_feature(f, win))):
                offsets[s, k, c] = dy * stride + dx
    areas = np.array([float(win * win) for win in grid.scales], np.float32)
    return GatherTables(
        offsets, weights, areas,
        np.asarray(cascade.thresholds, np.float32),
        np.asarray(cascade.polarity, np.float32),
        np.asarray(cascade.alphas, np.float32),
        tuple(cascade.stage_sizes),
        np.asarray(cascade.stage_thresholds, np.float32))


def _stage_vote(resp, inv_norm, thresholds, polarity, alphas):
    """Stump vote and stage reduce over raw responses (sz, *windows)."""
    def col(v):
        return v.reshape(v.shape + (1,) * (resp.ndim - 1))

    resp = resp * inv_norm[None]
    pred = col(polarity) * jnp.sign(resp - col(thresholds))
    pred = jnp.where(pred == 0, 1.0, pred)
    return jnp.sum(pred * col(alphas), axis=0)


def haar_stage_scores(ii_flat, base, sid, inv_norm, offsets, weights,
                      thresholds, polarity, alphas):
    """One cascade stage's AdaBoost score per window, (n,) f32: corner-tap
    gathers into the flattened frame integral image, stump vote, stage
    reduce.  Survivor stages run this, since their windows are an
    arbitrary compacted subset; stage 0, which sees the whole scan grid,
    reads the same taps as strided slices (:func:`grid_stage_scores`).

    ii_flat:    (L,) flattened zero-padded frame integral image.
    base:       (n,) int32 window top-left flat index, y * (W + 1) + x.
    sid:        (n,) int32 pyramid-scale id per window.
    inv_norm:   (n,) f32 per-window 1 / (sd * area).
    offsets:    (n_scales, sz, K) int32 corner taps per scale.
    weights:    (sz, K) f32 corner weights (0 in padded slots).
    thresholds, polarity, alphas: (sz,) decision-stump parameters.

    XLA on every backend: Mosaic cannot lower flat-table gathers.
    """
    # one stump at a time, windows on the minor axis: a whole-stage
    # (n, sz, K) tap tensor costs 264 int32 per window, and on the TPU its
    # small minor dims pad ~4-20x to 128-lane tiles — past 16 GB of HBM for
    # stage 0 over a 128-frame serving batch
    def stump(args):
        off_k, w_k = args                                # (K, n_scales), (K,)
        idx = base[None, :] + jnp.take(off_k, sid, axis=1)   # (K, n)
        vals = jnp.take(ii_flat, idx.reshape(-1), axis=0).reshape(idx.shape)
        return jnp.sum(vals * w_k[:, None], axis=0)

    resp = jax.lax.map(stump, (jnp.transpose(offsets, (1, 2, 0)), weights))
    return _stage_vote(resp, inv_norm, thresholds, polarity, alphas)


def _grid_phases(ii, rects):
    """Every stride phase of ``ii`` that the grid's scales read, as one
    (P, hc, wc) table, and each step's first phase: phase a * sx + b of
    step (sy, sx) holds ii[a::sy, b::sx].  A tap read every sy rows and sx
    columns is then a unit-stride window of one phase (the TPU lowers a
    strided slice to a gather).  The phases are zero-padded so that a
    window of the largest scale's grid fits at every tap."""
    h, w = ii.shape
    steps = sorted({rect[4:] for rect in rects})
    hc = max(-(-h // sy) for sy, _sx in steps) + max(r[2] for r in rects)
    wc = max(-(-w // sx) for _sy, sx in steps) + max(r[3] for r in rects)
    tabs, first = [], {}
    for sy, sx in steps:
        first[sy, sx] = sum(t.shape[0] for t in tabs)
        padded = jnp.pad(ii, ((0, hc * sy - h), (0, wc * sx - w)))
        tabs.append(padded.reshape(hc, sy, wc, sx).transpose(1, 3, 0, 2)
                    .reshape(sy * sx, hc, wc))
    return jnp.concatenate(tabs), first


def _phase_starts(rect, dy, dx, first) -> np.ndarray:
    """Each tap's (phase, row, col) start in :func:`_grid_phases`'s table
    for one scale's grid, from its offsets (dy, dx) to the window's
    top-left corner: the shape of ``dy`` plus a trailing 3."""
    y0, x0, _ny, _nx, sy, sx = rect
    r, c = y0 + np.asarray(dy), x0 + np.asarray(dx)
    phase = first[sy, sx] + (r % sy) * sx + c % sx
    return np.stack([phase, r // sy, c // sx], axis=-1).astype(np.int32)


def _grid_windows(tab, starts, ny, nx):
    """(K, ny, nx): the windows of ``tab`` at the K ``starts``."""
    return jnp.stack([jax.lax.dynamic_slice(tab, tuple(starts[k]),
                                            (1, ny, nx))[0]
                      for k in range(starts.shape[0])])


def _by_scale(rects, flat, shape):
    """(n,) in grid order -> (n_scales, *shape), each scale's (ny, nx)
    rectangle zero-padded to ``shape``; :func:`_to_grid` inverts it."""
    out, o = [], 0
    for rect in rects:
        ny, nx = rect[2:4]
        plane = flat[o:o + ny * nx].reshape(ny, nx)
        out.append(jnp.pad(plane, ((0, shape[0] - ny), (0, shape[1] - nx))))
        o += ny * nx
    return jnp.stack(out)


def _to_grid(rects, planes):
    """(n_scales, ny_max, nx_max) -> (n,) in grid order."""
    return jnp.concatenate([planes[s, :rect[2], :rect[3]].reshape(-1)
                            for s, rect in enumerate(rects)])


def grid_stage_scores(ii, inv_norm, rects, offsets, weights, thresholds,
                      polarity, alphas):
    """One cascade stage's AdaBoost score for every window of a whole scan
    grid, (n,) f32 in grid order: the same taps, sums and vote as
    :func:`haar_stage_scores`, with each tap of a scale read as a strided
    slice of the integral image instead of gathered.

    ii:         (h+1, w+1) zero-padded frame integral image.
    inv_norm:   (n,) f32 per-window 1 / (sd * area), grid order.
    rects:      per scale (y0, x0, ny, nx, sy, sx) (``ScanGrid.rects``).
    offsets:    (n_scales, sz, K) corner taps per scale, dy * (w + 1) + dx,
                as NumPy: static, since they place the slices.
    weights:    (sz, K) f32 corner weights (0 in padded slots).
    thresholds, polarity, alphas: (sz,) decision-stump parameters.

    Every scale is computed on the largest scale's (ny, nx) rectangle, so
    one stump body serves them all; the padding is dropped at the end.
    """
    dy, dx = np.divmod(np.asarray(offsets), ii.shape[1])
    n_scales, sz, k = dy.shape
    shape = (max(r[2] for r in rects), max(r[3] for r in rects))
    tab, first = _grid_phases(ii, rects)
    starts = np.stack([_phase_starts(rect, dy[s], dx[s], first)
                       for s, rect in enumerate(rects)], axis=1)

    # one (stump, scale) at a time, as haar_stage_scores goes stump by stump
    def stump(args):
        st, w_k = args                                   # (K, 3), (K,)
        vals = _grid_windows(tab, st, *shape)
        return jnp.sum(vals * w_k[:, None, None], axis=0)

    resp = jax.lax.map(stump, (jnp.asarray(starts.reshape(-1, k, 3)),
                               jnp.repeat(weights, n_scales, axis=0)))
    resp = resp.reshape((sz, n_scales) + shape)
    inv = _by_scale(rects, inv_norm, shape)
    return _to_grid(rects, _stage_vote(resp, inv, thresholds, polarity,
                                       alphas))


def grid_inv_norm(ii, ii2, rects, scales, areas):
    """Every scan window's normalizer 1 / (sd * area), (n,) f32 in grid
    order, from the window-sum corners of the frame's integral image ``ii``
    and of its square ``ii2``, each read as a strided slice (a window of
    :func:`_grid_phases`).  ``areas`` (n_scales,) holds each scale's
    win^2."""
    norm_w = jnp.asarray(_NORM_W)[:, None, None]
    shape = (max(r[2] for r in rects), max(r[3] for r in rects))
    tab, first = _grid_phases(ii, rects)
    tab2, _ = _grid_phases(ii2, rects)
    starts = np.stack([
        _phase_starts(rect, (win, 0, win, 0), (win, win, 0, 0), first)
        for rect, win in zip(rects, scales)])            # (n_scales, 4, 3)

    def window_sums(st):
        return tuple(jnp.sum(_grid_windows(t, st, *shape) * norm_w, axis=0)
                     for t in (tab, tab2))

    s1, s2 = jax.lax.map(window_sums, jnp.asarray(starts))
    area = areas[:, None, None]
    mu = s1 / area
    var = s2 / area - mu * mu
    sd = jnp.sqrt(jnp.maximum(var, 1e-6))
    return _to_grid(rects, 1.0 / (sd * area))


class FusedDetector:
    """Frame-resident fused detection front-end.

    The frame is touched once: one frame-level integral image (plus one of
    the squared frame, for variance normalization) — computed by the
    streaming Pallas kernel on TPU (kernels/integral_image) or the jnp
    oracle elsewhere.  Every scanning window at every pyramid scale is then
    evaluated from <= 8 corners per weak classifier out of that one table,
    replacing the seed's ~400x data amplification (25,853 materialized
    20x20 windows per 176x144 frame) with lookups: stage 0 reads each
    corner of the whole grid as a strided slice
    (:func:`grid_stage_scores`), the survivor stages gather theirs
    (:func:`haar_stage_scores`).  The stage loop runs through
    ``core.cascade.compacting_cascade``: after :meth:`calibrate`, stage i
    computes only on a capacity-bounded survivor prefix, so the paper's
    "86% fewer invocations" saves real FLOPs under static shapes.

    :func:`detect_faces` is the golden oracle; with ample capacities the
    two produce identical detection sets (tests/test_detect.py).
    """

    def __init__(self, cascade: Cascade, h: int, w: int, *,
                 scale_factor: float = 1.25, step: float = 0.025,
                 adaptive: bool = True, strictness: float = 0.0,
                 capacities=None, use_pallas=None, interpret: bool = False):
        self.cascade = cascade
        # window bases ride through the compacted item triple as float32,
        # which is exact only below 2^24
        if (h + 1) * (w + 1) >= 2 ** 24:
            raise ValueError(f"frame {h}x{w} too large for f32-exact "
                             "window indices (needs (h+1)*(w+1) < 2^24)")
        self.grid = build_scan_grid(h, w, scale_factor, step, adaptive)
        self.tables = build_gather_tables(cascade, self.grid)
        self.n_windows = len(self.grid.positions)
        self.n_stages = len(self.tables.stage_sizes)
        self.strictness = float(strictness)
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = bool(use_pallas)
        self.interpret = bool(interpret)
        self.capacities = (list(capacities) if capacities is not None
                           else [self.n_windows] * self.n_stages)
        self._apply = self._build(tuple(self.capacities))

    # -- jitted core --------------------------------------------------------

    def _build(self, capacities: tuple):
        t = self.tables
        # NOTE: the tables ride in as jit *arguments*, not closure constants
        # — embedded as constants, XLA constant-folds the (n_windows, sz, 8)
        # index tensors at compile time (minutes of folding for zero runtime
        # gain, since the gathers themselves depend on the frame).
        consts = tuple(jnp.asarray(a) for a in (
            self.grid.bases, self.grid.scale_id, t.offsets, t.weights,
            t.thresholds, t.polarity, t.alphas, t.areas))
        bounds, o = [], 0
        for sz in t.stage_sizes:
            bounds.append((o, o + sz))
            o += sz
        stage_thr = [float(v) + self.strictness for v in t.stage_thresholds]
        use_pallas, interpret = self.use_pallas, self.interpret
        # stage 0 always sees the whole grid in grid order (its capacity is
        # the window count), so it reads its taps as slices of the integral
        # image, placed by its offsets as static NumPy
        rects, scales, n_windows = (self.grid.rects, self.grid.scales,
                                    self.n_windows)
        lo0, hi0 = bounds[0]
        offsets0 = t.offsets[:, lo0:hi0]

        def apply(frames, bases, sids, offsets, weights, thr, pol, al,
                  areas):
            # the scale-id table rides in as a jit *argument* (NOTE above),
            # so its in-bounds promise is data-dependent; clamp once — a
            # no-op for real grids — to make the per-scale lookups below
            # statically guarded
            sids = jnp.clip(sids, 0, areas.shape[0] - 1)

            def one_frame(ii, ii2):
                iif = ii.reshape(-1)
                inv = grid_inv_norm(ii, ii2, rects, scales, areas)
                # a window is fully described by (base, scale id,
                # normalizer) — this triple is what the compacting cascade
                # carries and compacts.
                items = jnp.stack([bases.astype(jnp.float32),
                                   sids.astype(jnp.float32), inv], axis=1)

                def grid_fn(it):
                    assert it.shape[0] == n_windows, it.shape
                    return grid_stage_scores(ii, it[:, 2], rects, offsets0,
                                             weights[lo0:hi0], thr[lo0:hi0],
                                             pol[lo0:hi0], al[lo0:hi0])

                def stage_fn(lo, hi):
                    def fn(it):
                        # the item triple rides through compaction as f32
                        # (exact below 2^24); clamp in float before the int
                        # casts so dead/padded slots index in-bounds instead
                        # of hitting a backend-defined NaN cast
                        return haar_stage_scores(
                            iif,
                            jnp.clip(it[:, 0], 0,
                                     iif.shape[0] - 1).astype(jnp.int32),
                            jnp.clip(it[:, 1], 0,
                                     areas.shape[0] - 1).astype(jnp.int32),
                            it[:, 2],
                            offsets[:, lo:hi], weights[lo:hi], thr[lo:hi],
                            pol[lo:hi], al[lo:hi])
                    return fn

                stages = [CoreStage(grid_fn if si == 0 else stage_fn(lo, hi),
                                    stage_thr[si], f"vj{si}")
                          for si, (lo, hi) in enumerate(bounds)]
                res = compacting_cascade(stages, items, list(capacities))
                return res.mask, res.n_survivors, res.dropped

            frames = frames.astype(jnp.float32)
            with jax.named_scope("integral"):
                ii = frame_integral(frames, use_pallas=use_pallas,
                                    interpret=interpret)
                ii2 = frame_integral(frames * frames, use_pallas=use_pallas,
                                     interpret=interpret)
            return jax.vmap(one_frame)(ii, ii2)

        jitted = jax.jit(apply)
        # Traceable handle for callers that fuse the detector into a LARGER
        # jit region (camera/pipelines.FaceAuthExecutor): call
        # ``traceable_apply(frames, *apply_consts)`` inside your own jit and
        # pass ``apply_consts`` through as jit *arguments* (same
        # constant-folding hazard as the NOTE above).
        self.traceable_apply = apply
        self.apply_consts = consts
        return lambda frames: jitted(frames, *consts)

    # -- capacity calibration ----------------------------------------------

    def calibrate(self, frames, margin: float = 2.0, quantum: int = 128):
        """Measure per-stage survivor counts on calibration frames (full-
        capacity pass = masked oracle) and set compacting capacities from
        them — choosing the knob from workload statistics, exactly how the
        paper picked window scale/step."""
        frames = np.asarray(frames, np.float32)
        if frames.ndim == 2:
            frames = frames[None]
        if frames.shape[0] == 0:
            return self.capacities            # nothing to measure; keep as-is
        full = (self._apply
                if self.capacities == [self.n_windows] * self.n_stages
                else self._build((self.n_windows,) * self.n_stages))
        _, surv, _ = full(jnp.asarray(frames))
        counts = np.asarray(surv).max(axis=0)
        self.capacities = capacities_from_counts(
            self.n_windows, counts, margin=margin, quantum=quantum)
        self._apply = self._build(tuple(self.capacities))
        return self.capacities

    # -- detection ----------------------------------------------------------

    def __call__(self, frames):
        """(B, h, w) -> (mask (B, n_windows), n_survivors (B, n_stages),
        dropped (B, n_stages)) as device arrays."""
        return self._apply(jnp.asarray(frames))

    def detect(self, frames):
        """Batched detection with detect_faces-compatible accounting.

        Returns (detections per frame — list of (y, x, win) lists, stats).
        stats["stage_evals"] counts data-dependent stage evaluations (the
        energy model's charge); stats["static_stage_evals"] counts what the
        static-shape compacted execution actually computed.
        """
        frames = np.asarray(frames, np.float32)
        if frames.ndim == 2:
            frames = frames[None]
        mask, surv, dropped = (np.asarray(a) for a in self(frames))
        pos = self.grid.positions
        dets = [[pos[i] for i in np.where(m)[0]] for m in mask]
        entering = np.concatenate(
            [np.full((len(frames), 1), self.n_windows, np.int64),
             surv[:, :-1].astype(np.int64)], axis=1)
        stats = {
            "n_windows": self.n_windows,
            "n_invocations": self.n_windows * len(frames),
            "stage_evals": int(entering.sum()),
            "static_stage_evals": len(frames) * int(np.sum(self.capacities)),
            "n_survivors": surv,
            "dropped": int(dropped.sum()),
            "capacities": list(self.capacities),
        }
        return dets, stats


_FUSED_CACHE: dict = {}


def detect_faces_batch(cascade: Cascade, frames, scale_factor=1.25,
                       step=0.025, adaptive=True, strictness: float = 0.0,
                       capacities="auto", use_pallas=None,
                       interpret: bool = False):
    """Fused, jitted, batched detection over (B, h, w) frames.

    ``capacities="auto"`` calibrates compacting capacities on the first
    (up to 4) frames; ``None`` disables compaction (full capacities, the
    masked oracle); an explicit list is used as-is.  Detectors are cached
    per (cascade, shape, scan parameters), so steady-state calls pay only
    the jitted computation.  Returns (dets_per_frame, stats) as
    :meth:`FusedDetector.detect`.
    """
    frames = np.asarray(frames, np.float32)
    if frames.ndim == 2:
        frames = frames[None]
    if frames.shape[0] == 0:
        return [], {"n_windows": 0, "n_invocations": 0, "stage_evals": 0,
                    "static_stage_evals": 0,
                    "n_survivors": np.zeros((0, 0), np.int32),
                    "dropped": 0, "capacities": []}
    h, w = frames.shape[-2:]
    auto = isinstance(capacities, str) and capacities == "auto"
    cap_key = (capacities if auto or capacities is None
               else tuple(capacities))
    key = (id(cascade), h, w, scale_factor, step, adaptive, strictness,
           use_pallas, interpret, cap_key)
    hit = _FUSED_CACHE.get(key)
    if hit is not None and hit[0] is cascade:
        det = hit[1]
    else:
        det = FusedDetector(cascade, h, w, scale_factor=scale_factor,
                            step=step, adaptive=adaptive,
                            strictness=strictness,
                            capacities=None if auto else capacities,
                            use_pallas=use_pallas, interpret=interpret)
        if auto:
            det.calibrate(frames[: min(4, len(frames))])
        if len(_FUSED_CACHE) >= 16:      # bound the jitted-program cache
            _FUSED_CACHE.pop(next(iter(_FUSED_CACHE)))
        _FUSED_CACHE[key] = (cascade, det)
    return det.detect(frames)


def harvest_hard_negatives(frames, truth, n: int = 1500, seed: int = 0):
    """Bootstrap negatives from scene windows away from true faces — the
    classic cascade-training trick (the paper's detector is trained the
    same way on real imagery)."""
    rng = np.random.default_rng(seed)
    neg = []
    idxs = rng.choice(len(frames), min(10, len(frames)), replace=False)
    per = max(1, n // len(idxs))
    for i in idxs:
        pos = scan_positions(frames[i].shape[0], frames[i].shape[1], 1.6, 0.08, True)
        take = rng.choice(len(pos), min(per, len(pos)), replace=False)
        wins = extract_windows(frames[i], [pos[j] for j in take])
        for w, (yy, xx, sz) in zip(wins, [pos[j] for j in take]):
            near = any(abs(yy - fy) < 15 and abs(xx - fx) < 15
                       for (fy, fx, _s) in truth[i]["faces"])
            if not near:
                neg.append(w.reshape(-1))
    return np.stack(neg).astype(np.float32)
