"""Plain reference of one stereo pair's BSSA depth map.

Straight ``jax.numpy`` from the deployment's stated semantics (the
configuration file's ``bssa`` group); it imports nothing of the program.

1. Rough disparity: for each hypothesis d in 0..max_disp the right view
   shifted d pixels (its first column repeated), the absolute difference
   to the left view, edge-padded by two, summed over every 5x5 patch as
   five row sums then five column sums; the first hypothesis with the
   least sum wins.
2. Splat: every pixel's disparity and a unit weight added at its nearest
   bilateral-grid vertex (y / sigma, x / sigma, 255 * intensity /
   sigma_range).
3. Refinement: ``n_iters`` separable [1, 2, 1] / 4 blurs of both grids,
   edges repeated.
4. Slice: trilinear interpolation of value over weight at every pixel.

``dtype`` is float32 for the reference and bfloat16 for the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def rough(left, right, max_disp: int, patch: int = 5):
    h, w = left.shape
    pad = patch // 2
    inf = jnp.asarray(jnp.inf, left.dtype)

    def body(d, carry):
        best, bestd = carry
        xs = jnp.clip(jnp.arange(w) - d, 0, w - 1)
        diff = jnp.abs(left - right[:, xs])
        dp = jnp.pad(diff, pad, mode="edge")
        rows = sum(dp[i:i + h, :] for i in range(patch))
        sad = sum(rows[:, j:j + w] for j in range(patch))
        better = sad < best
        return jnp.where(better, sad, best), jnp.where(better, d, bestd)

    init = (jnp.full((h, w), inf), jnp.zeros((h, w), jnp.int32))
    _, bestd = jax.lax.fori_loop(0, max_disp + 1, body, init)
    return bestd


def _coords(img, sigma_spatial, sigma_range):
    h, w = img.shape
    dt = img.dtype
    yy, xx = jnp.mgrid[0:h, 0:w]
    return ((yy.astype(dt) / sigma_spatial).reshape(-1),
            (xx.astype(dt) / sigma_spatial).reshape(-1),
            (img * 255.0 / sigma_range).reshape(-1))


def dims(h, w, sigma_spatial, sigma_range):
    return (-(-h // sigma_spatial) + 1, -(-w // sigma_spatial) + 1,
            int(-(-256.0 // sigma_range)) + 1)


def splat(img, values, sigma_spatial, sigma_range):
    h, w = img.shape
    gy, gx, gr = dims(h, w, sigma_spatial, sigma_range)
    cy, cx, cr = _coords(img, sigma_spatial, sigma_range)
    iy = jnp.clip(jnp.round(cy), 0, gy - 1).astype(jnp.int32)
    ix = jnp.clip(jnp.round(cx), 0, gx - 1).astype(jnp.int32)
    ir = jnp.clip(jnp.round(cr), 0, gr - 1).astype(jnp.int32)
    flat = (iy * gx + ix) * gr + ir
    n = gy * gx * gr
    v = jax.ops.segment_sum(values.reshape(-1).astype(img.dtype), flat, n)
    wt = jax.ops.segment_sum(jnp.ones_like(cy), flat, n)
    return v.reshape(gy, gx, gr), wt.reshape(gy, gx, gr)


def blur(g):
    for ax in range(3):
        n = g.shape[ax]
        lo = jnp.take(g, jnp.maximum(jnp.arange(n) - 1, 0), axis=ax)
        hi = jnp.take(g, jnp.minimum(jnp.arange(n) + 1, n - 1), axis=ax)
        g = 0.25 * lo + 0.5 * g + 0.25 * hi
    return g


def slice_grid(v, wt, img, sigma_spatial, sigma_range):
    h, w = img.shape
    gy, gx, gr = v.shape
    cy, cx, cr = _coords(img, sigma_spatial, sigma_range)
    y0 = jnp.clip(jnp.floor(cy), 0, gy - 2).astype(jnp.int32)
    x0 = jnp.clip(jnp.floor(cx), 0, gx - 2).astype(jnp.int32)
    r0 = jnp.clip(jnp.floor(cr), 0, gr - 2).astype(jnp.int32)
    fy = jnp.clip(cy - y0, 0, 1)
    fx = jnp.clip(cx - x0, 0, 1)
    fr = jnp.clip(cr - r0, 0, 1)
    vf, wf = v.reshape(-1), wt.reshape(-1)
    num = jnp.zeros_like(cy)
    den = jnp.zeros_like(cy)
    for dy in (0, 1):
        for dx in (0, 1):
            for dr in (0, 1):
                k = (((fy if dy else 1 - fy) * (fx if dx else 1 - fx))
                     * (fr if dr else 1 - fr))
                flat = ((y0 + dy) * gx + (x0 + dx)) * gr + (r0 + dr)
                num = num + k * vf[flat]
                den = den + k * wf[flat]
    return (num / jnp.maximum(den, 1e-6)).reshape(h, w)


@functools.partial(jax.jit, static_argnames=("max_disp", "n_iters",
                                             "sigma_spatial", "sigma_range",
                                             "dtype"))
def depth(left, right, *, max_disp: int, n_iters: int, sigma_spatial: int,
          sigma_range: float, dtype=jnp.float32):
    """(h, w) x2 -> (h, w) float32 depth of one pair."""
    left, right = left.astype(dtype), right.astype(dtype)
    d = rough(left, right, max_disp).astype(dtype)
    v, wt = splat(left, d, sigma_spatial, sigma_range)
    for _ in range(n_iters):
        v, wt = blur(v), blur(wt)
    return slice_grid(v, wt, left, sigma_spatial,
                      sigma_range).astype(jnp.float32)


def compare(program: np.ndarray, reference: np.ndarray) -> dict:
    """The numbers compared between two depth maps of one pair."""
    err = np.abs(np.asarray(program, np.float64)
                 - np.asarray(reference, np.float64))
    return {"depth_mean_err_px": float(err.mean()),
            "depth_far_share": float((err > 1.0).mean()),
            "depth_max_err_px": float(err.max())}
