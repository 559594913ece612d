"""Bilateral-space stereo (BSSA) — paper §IV-A/B, after Barron et al. [4].

Pipeline per camera pair (Fig. 10/12):

1. **Rough disparity** — block matching over a disparity range (the "rough
   disparity" of global stereo pipelines).
2. **Bilateral grid construction (splat)** — pixels map to grid vertices
   (y/s_y, x/s_x, intensity/s_r): the paper's B3 output, the biggest
   intermediate (Fig. 13).
3. **Bilateral-space refinement** — the FPGA-accelerated block: iterated
   [1,2,1] blurs of the disparity-weighted grid ("applying millions of
   blurs ... most of these filters can run in parallel"), which in
   bilateral space equals a global edge-aware smoothing in pixel space.
   f32 throughout — the paper found >=32-bit float necessary for quality.
4. **Slice** — sample the refined grid back at pixel coordinates.

The rough disparity's 5x5 SADs are separable box sums of shifted adds,
exact in float32 for 8-bit views at any frame size.  The blur kernel is
the perf-critical unit: kernels/bilateral_blur holds the Pallas TPU
version, and :func:`bssa_depth` refines through it (via
``ops.refine_grid`` backend dispatch).  This module keeps the jnp oracles
(:func:`rough_disparity_ref`, :func:`refine`, :func:`slice_grid_ref`,
:func:`bssa_depth_ref`) and the quality harness (MS-SSIM vs grid size,
Fig. 11b); the rig-scale batched executor is
``camera.pipelines.VRRigExecutor``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Rough disparity (block matching)
# ---------------------------------------------------------------------------
#
# Disparity convention (both implementations): hypothesis d aligns
# ``left[y, x]`` with ``right[y, x - d]`` after shifting the right view d
# pixels toward higher x — i.e. a pair generated as right[x] = left[x + d]
# is recovered exactly (pinned by the shifted-pair property test).


def _box_sad(left: jax.Array, rs: jax.Array, patch: int) -> jax.Array:
    """5x5 (``patch`` x ``patch``) SAD of one hypothesis, exact association:
    |left - rs| edge-padded by ``patch // 2``, then ``patch`` row sums, then
    ``patch`` column sums, each added left to right in float32."""
    h, w = left.shape
    dp = jnp.pad(jnp.abs(left - rs), patch // 2, mode="edge")
    rows = dp[0:h]
    for i in range(1, patch):
        rows = rows + dp[i:i + h]
    sad = rows[:, 0:w]
    for j in range(1, patch):
        sad = sad + rows[:, j:j + w]
    return sad


def rough_disparity(left: jax.Array, right: jax.Array, max_disp: int = 16,
                    patch: int = 5, *, hypothesis_chunk: int = 8) -> jax.Array:
    """Winner-take-all SAD block matching.  (h, w) f32 -> (h, w) f32.

    Each hypothesis' patch SAD is a separable box sum of shifted adds
    (:func:`_box_sad`): a few additions per pixel, every partial sum no
    larger than one patch's, so float32 keeps the SADs of 8-bit views
    exact at any frame size (a whole-frame summed-area table reaches the
    millions at 3840x2160 and rounds a 1/256 step away).  The hypothesis
    axis is scanned in ``hypothesis_chunk``-sized chunks: one dynamic slice
    of the left-edge-padded right view per chunk, a static slice of it per
    hypothesis, and a strict running min, so the first least hypothesis
    wins.  Equal, bit for bit, to :func:`rough_disparity_ref` and to the
    plain reference's ``rough``.
    """
    h, w = left.shape
    n_hyp = max_disp + 1
    chunk = min(hypothesis_chunk, n_hyp)
    n_chunks = -(-n_hyp // chunk)
    span = n_chunks * chunk
    # rpad[:, j] = right[:, max(j - (span - 1), 0)]: shifting by d is the
    # window starting at span - 1 - d (edge columns replicate)
    rpad = jnp.pad(right, ((0, 0), (span - 1, 0)), mode="edge")

    def body(carry, c):
        best, bestd = carry
        d0 = c * chunk
        block = jax.lax.dynamic_slice_in_dim(rpad, span - chunk - d0,
                                             w + chunk - 1, axis=1)
        for k in range(chunk):
            rs = block[:, chunk - 1 - k:chunk - 1 - k + w]
            sad = _box_sad(left, rs, patch)
            # the ragged tail's hypotheses past max_disp never win
            better = (sad < best) & (d0 + k <= max_disp)
            best = jnp.where(better, sad, best)
            bestd = jnp.where(better, d0 + k, bestd)
        return (best, bestd), None

    init = (jnp.full((h, w), jnp.inf), jnp.zeros((h, w), jnp.int32))
    (_, bestd), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return bestd.astype(jnp.float32)


def rough_disparity_ref(left: jax.Array, right: jax.Array, max_disp: int = 16,
                        patch: int = 5) -> jax.Array:
    """Per-hypothesis Python loop, the golden oracle: materializes D+1
    full-frame SAD maps, each five row sums then five column sums of the
    edge-padded |left - right shifted d|, and takes the first argmin."""
    h, w = left.shape
    pad = patch // 2
    costs = []
    for d in range(max_disp + 1):
        rs = jnp.roll(right, d, axis=1)
        rs = rs.at[:, :d].set(right[:, :1] if d else rs[:, :d])
        dp = jnp.pad(jnp.abs(left - rs), pad, mode="edge")
        rows = dp[0:h]
        for i in range(1, patch):
            rows = rows + dp[i:i + h]
        sad = rows[:, 0:w]
        for j in range(1, patch):
            sad = sad + rows[:, j:j + w]
        costs.append(sad)
    cost = jnp.stack(costs)                      # (D+1, h, w)
    return jnp.argmin(cost, axis=0).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Bilateral grid
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GridSpec:
    sigma_spatial: int          # pixels per grid vertex (paper sweeps 4..64)
    sigma_range: float = 16.0   # intensity bins (on [0,255] scale)

    def dims(self, h: int, w: int):
        gy = int(np.ceil(h / self.sigma_spatial)) + 1
        gx = int(np.ceil(w / self.sigma_spatial)) + 1
        gr = int(np.ceil(256.0 / self.sigma_range)) + 1
        return gy, gx, gr


def _grid_coords(img: jax.Array, spec: GridSpec):
    h, w = img.shape
    yy, xx = jnp.mgrid[0:h, 0:w]
    gy = yy / spec.sigma_spatial
    gx = xx / spec.sigma_spatial
    gr = img * 255.0 / spec.sigma_range
    return gy.reshape(-1), gx.reshape(-1), gr.reshape(-1)


def splat(img: jax.Array, values: jax.Array, spec: GridSpec):
    """Accumulate (value, weight) into the bilateral grid (nearest vertex).

    Returns (grid_val, grid_wt) of shape (gy, gx, gr).  Nearest-vertex
    splatting matches the hardware design (the FPGA streams vertices, not
    8-corner trilinear updates); slicing interpolates instead.
    """
    h, w = img.shape
    gy, gx, gr = spec.dims(h, w)
    cy, cx, cr = _grid_coords(img, spec)
    # clip in float, then cast: same vertices for finite inputs, but a NaN
    # intensity no longer hits a backend-defined float->int cast
    iy = jnp.clip(jnp.round(cy), 0, gy - 1).astype(jnp.int32)
    ix = jnp.clip(jnp.round(cx), 0, gx - 1).astype(jnp.int32)
    ir = jnp.clip(jnp.round(cr), 0, gr - 1).astype(jnp.int32)
    flat = (iy * gx + ix) * gr + ir
    v = jnp.zeros((gy * gx * gr,), jnp.float32).at[flat].add(values.reshape(-1))
    wt = jnp.zeros((gy * gx * gr,), jnp.float32).at[flat].add(1.0)
    return v.reshape(gy, gx, gr), wt.reshape(gy, gx, gr)


def blur_121(grid: jax.Array) -> jax.Array:
    """Separable [1,2,1]/4 blur over the three grid dimensions.

    This is the compute unit the paper maps to FPGA DSPs; the Pallas TPU
    version lives in kernels/bilateral_blur (same semantics, tested
    allclose against this oracle).
    """
    def blur_axis(g, axis):
        lo = jnp.roll(g, 1, axis)
        hi = jnp.roll(g, -1, axis)
        # replicate edges (roll wraps; overwrite the wrapped slices)
        idx_lo = [slice(None)] * g.ndim
        idx_lo[axis] = slice(0, 1)
        idx_hi = [slice(None)] * g.ndim
        idx_hi[axis] = slice(-1, None)
        lo = lo.at[tuple(idx_lo)].set(g[tuple(idx_lo)])
        hi = hi.at[tuple(idx_hi)].set(g[tuple(idx_hi)])
        return 0.25 * lo + 0.5 * g + 0.25 * hi

    for ax in range(3):
        grid = blur_axis(grid, ax)
    return grid


def refine(grid_val: jax.Array, grid_wt: jax.Array, n_iters: int = 8):
    """Iterated bilateral-space smoothing of the disparity field.

    Normalized blur: both value and weight grids are blurred each
    iteration; the ratio is the edge-aware smoothed disparity ("simple
    local filters are equivalent to costly global edge-aware filters").
    """
    def body(carry, _):
        v, w = carry
        return (blur_121(v), blur_121(w)), None

    (v, w), _ = jax.lax.scan(body, (grid_val, grid_wt), None, length=n_iters)
    return v, w


def _lattice(n: int, s: int, g: int):
    """Lower corner and weight of the upper one for pixel positions
    0..n-1 on an axis of ``g`` vertices ``s`` pixels apart, clipped as
    :func:`slice_grid_ref` clips them.  Static: float32 numpy."""
    c = np.arange(n, dtype=np.float32) / np.float32(s)
    i0 = np.clip(np.floor(c), 0, g - 2)
    return i0, np.clip(c - i0, 0, 1)


def slice_grid(grid_val: jax.Array, grid_wt: jax.Array, img: jax.Array,
               spec: GridSpec) -> jax.Array:
    """Trilinear sampling of the refined grid at each pixel's coordinates,
    with no gather.

    Only the range corner depends on the data: a pixel's spatial corners
    and weights are fixed by its position.  So the x direction is a
    float32 (``HIGHEST``) matmul of each grid's (row, bin) planes against a
    static two-tap (gx, w) matrix; the y direction splits the image into
    blocks of s rows (edge-padded to whole blocks, cropped after), and
    block j blends grid rows j and j + 1; the range picks bins r0 and
    r0 + 1, computed and clipped as :func:`slice_grid_ref` does, by a
    select chain over the gr - 1 values of r0.  Within float32 rounding of
    :func:`slice_grid_ref` (tests/test_camera_pipeline.py).
    """
    h, w = img.shape
    gy, gx, gr = grid_val.shape
    s = spec.sigma_spatial
    nb = -(-h // s)                      # row blocks; rows y0 = j, j + 1
    x0, fx = _lattice(w, s, gx)
    taps = jnp.arange(gx)[:, None]
    wx = jnp.where(taps == x0, 1 - fx, 0) + jnp.where(taps == x0 + 1, fx, 0)
    _, fy = _lattice(nb * s, s, gy)
    fy = fy.reshape(nb, s, 1)

    # the barrier keeps the blocks (nb, s, w) the shape of the fusion
    # below: fused with the reshape back to (h, w), XLA:TPU materializes
    # every grid row's broadcast over its s pixel rows instead
    blocks = jax.lax.optimization_barrier(
        jnp.pad(img, ((0, nb * s - h), (0, 0)), mode="edge")
        .reshape(nb, s, w))
    cr = blocks * 255.0 / spec.sigma_range
    r0 = jnp.clip(jnp.floor(cr), 0, gr - 2)
    fr = jnp.clip(cr - r0, 0, 1)
    hits = [(k, r0 == k) for k in range(1, gr - 1)]

    def sample(grid):
        # (gy, gx, gr) -> (gy, gr, w): each row and bin interpolated in x
        xs = jnp.einsum("yxr,xw->yrw", grid, wx,
                        precision=jax.lax.Precision.HIGHEST)

        def at(dy, k):                           # (nb, 1, w): rows j + dy
            return xs[dy:dy + nb, None, k]

        out = 0.0
        for dy in (0, 1):
            lo, hi = at(dy, 0), at(dy, 1)
            for k, hit in hits:
                lo = jnp.where(hit, at(dy, k), lo)
                hi = jnp.where(hit, at(dy, k + 1), hi)
            out = out + (fy if dy else 1 - fy) * ((1 - fr) * lo + fr * hi)
        return out

    out = sample(grid_val) / jnp.maximum(sample(grid_wt), 1e-6)
    return out.reshape(nb * s, w)[:h]


def slice_grid_ref(grid_val: jax.Array, grid_wt: jax.Array, img: jax.Array,
                   spec: GridSpec) -> jax.Array:
    """Trilinear sampling by 16 flat-table gathers (8 corners x 2 grids),
    the golden oracle of :func:`slice_grid`."""
    h, w = img.shape
    gy, gx, gr = grid_val.shape
    cy, cx, cr = _grid_coords(img, spec)

    # clip in float, then cast (see splat): keeps the trilinear corner
    # indices in-bounds even for non-finite pixel values
    y0 = jnp.clip(jnp.floor(cy), 0, gy - 2).astype(jnp.int32)
    x0 = jnp.clip(jnp.floor(cx), 0, gx - 2).astype(jnp.int32)
    r0 = jnp.clip(jnp.floor(cr), 0, gr - 2).astype(jnp.int32)
    fy, fx, fr = cy - y0, cx - x0, cr - r0
    fy = jnp.clip(fy, 0, 1)
    fx = jnp.clip(fx, 0, 1)
    fr = jnp.clip(fr, 0, 1)

    def at(dy, dx, dr):
        flat = ((y0 + dy) * gx + (x0 + dx)) * gr + (r0 + dr)
        return grid_val.reshape(-1)[flat], grid_wt.reshape(-1)[flat]

    num = jnp.zeros_like(cy)
    den = jnp.zeros_like(cy)
    for dy in (0, 1):
        for dx in (0, 1):
            for dr in (0, 1):
                wv = (jnp.where(dy, fy, 1 - fy)
                      * jnp.where(dx, fx, 1 - fx)
                      * jnp.where(dr, fr, 1 - fr))
                v, wt = at(dy, dx, dr)
                num += wv * v
                den += wv * wt
    out = num / jnp.maximum(den, 1e-6)
    return out.reshape(h, w)


def bssa_depth(left: jax.Array, right: jax.Array, spec: GridSpec,
               max_disp: int = 16, n_iters: int = 8, *,
               use_pallas: bool | None = None, interpret: bool = False):
    """Full BSSA: rough disparity -> splat -> refine_grid -> slice.

    Refinement runs through kernels/bilateral_blur's ``refine_grid``
    (backend dispatch: the Pallas stencil on TPU, the blur_121 oracle math
    elsewhere — identical semantics either way, pinned in
    tests/test_kernels.py); ``use_pallas``/``interpret`` steer that
    dispatch.  Each of the four layers is a ``jax.named_scope`` (``rough``,
    ``splat``, ``refine``, ``slice``) around the whole layer, its loops
    included, so a device trace gives every operation to its layer.  The
    end-to-end seed path survives as :func:`bssa_depth_ref`, the golden
    oracle.
    """
    from repro.kernels.bilateral_blur.ops import refine_grid

    with jax.named_scope("rough"):
        rough = rough_disparity(left, right, max_disp)
    with jax.named_scope("splat"):
        gv, gw = splat(left, rough, spec)
    with jax.named_scope("refine"):
        gv, gw = refine_grid(gv, gw, n_iters=n_iters, use_pallas=use_pallas,
                             interpret=interpret)
    with jax.named_scope("slice"):
        return slice_grid(gv, gw, left, spec)


def bssa_depth_ref(left: jax.Array, right: jax.Array, spec: GridSpec,
                   max_disp: int = 16, n_iters: int = 8):
    """Seed jnp oracle: Python-loop rough disparity -> splat -> scan refine
    -> slice.  The benchmark baseline and parity anchor for the fused path."""
    rough = rough_disparity_ref(left, right, max_disp)
    gv, gw = splat(left, rough, spec)
    gv, gw = refine(gv, gw, n_iters)
    return slice_grid_ref(gv, gw, left, spec)


# ---------------------------------------------------------------------------
# MS-SSIM (paper's quality metric, Fig. 11b) — [42]
# ---------------------------------------------------------------------------


def _ssim(a: jax.Array, b: jax.Array, win: int = 8):
    """Mean SSIM with box windows (adequate for relative comparisons)."""
    def box(x):
        ii = jnp.cumsum(jnp.cumsum(x, 0), 1)
        ii = jnp.pad(ii, ((1, 0), (1, 0)))
        s = (ii[win:, win:] - ii[:-win, win:] - ii[win:, :-win]
             + ii[:-win, :-win])
        return s / (win * win)

    c1, c2 = 0.01 ** 2, 0.03 ** 2
    mu_a, mu_b = box(a), box(b)
    va = box(a * a) - mu_a ** 2
    vb = box(b * b) - mu_b ** 2
    cov = box(a * b) - mu_a * mu_b
    ssim = ((2 * mu_a * mu_b + c1) * (2 * cov + c2)) / (
        (mu_a ** 2 + mu_b ** 2 + c1) * (va + vb + c2))
    return jnp.mean(ssim)


def ms_ssim(a: jax.Array, b: jax.Array, levels: int = 3) -> float:
    """Multi-scale SSIM: geometric mean of SSIM over dyadic downsamples."""
    total = 1.0
    for _ in range(levels):
        total = total * jnp.clip(_ssim(a, b), 1e-4, 1.0) ** (1.0 / levels)
        h, w = a.shape
        a = a[: h // 2 * 2, : w // 2 * 2].reshape(h // 2, 2, w // 2, 2).mean((1, 3))
        b = b[: h // 2 * 2, : w // 2 * 2].reshape(h // 2, 2, w // 2, 2).mean((1, 3))
    return float(total)
