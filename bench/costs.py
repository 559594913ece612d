"""Peaks of the chips and the work each measured piece needs.

The work is what the algorithm needs for the shapes it is given, counted
the same way whatever implements it: an integral image adds twice per
pixel and reads and writes the frame once, whether a kernel computes it
with prefix sums or with triangular matrix products.
"""

from __future__ import annotations

# Published peaks per chip, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; a chip not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def roofline(ops: float, nbytes: float, seconds: float, peak_ops: float,
             peak_bw: float):
    """(share of the roofline in %, bound) for work done in ``seconds``.

    The least time is the larger of ops over the compute peak and bytes
    over the memory peak; ``bound`` says which ("compute" or "memory").
    None when nothing ran.
    """
    if seconds <= 0 or (ops <= 0 and nbytes <= 0):
        return None
    t_ops, t_mem = ops / peak_ops, nbytes / peak_bw
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound


# -- kernels ----------------------------------------------------------------


def integral_image(n: int, h: int, w: int) -> tuple:
    """(ops, bytes) of n summed-area tables of h x w float32 frames:
    two adds per pixel, one read and one write of the frame."""
    px = n * h * w
    return 2.0 * px, 8.0 * px


def quant_matmul(m: int, k: int, n: int) -> tuple:
    """(ops, bytes) of an int8 (m, k) x (k, n) product into float32 with a
    bias and a table sigmoid: 2mkn ops, int8 operands read once, float32
    result written once."""
    return 2.0 * m * k * n, float(m * k + k * n + 4 * m * n + 4 * n)


def bilateral_blur(gy: int, gx: int, gr: int) -> tuple:
    """(ops, bytes) of one [1,2,1] blur pass over a value and a weight grid:
    per vertex and axis two adds and two multiplies, both float32 grids
    read and written once."""
    v = gy * gx * gr
    return 2.0 * 3 * 4 * v, 2.0 * 2 * 4 * v


# -- whole steps --------------------------------------------------------------


def grid_dims(h: int, w: int, sigma_spatial: int, sigma_range: float) -> tuple:
    """Bilateral grid vertices (gy, gx, gr) of an h x w image."""
    gy = -(-h // sigma_spatial) + 1
    gx = -(-w // sigma_spatial) + 1
    gr = int(-(-256.0 // sigma_range)) + 1
    return gy, gx, gr


def bssa_pair(h: int, w: int, max_disp: int, n_iters: int,
              sigma_spatial: int, sigma_range: float) -> float:
    """Operations of one stereo pair's depth map (rough disparity, splat,
    refinement, slice).

    Rough disparity, per pixel and hypothesis: difference and absolute
    value, two adds of the summed-area table, three for the 5x5 box, one
    compare (8).  Splat, per pixel: three coordinate scalings and roundings
    and two accumulations (8).  Refinement: ``n_iters`` blur passes over
    both grids.  Slice, per pixel: eight corners of two weight products and
    two multiply-adds into value and weight (6 each), three coordinates and
    a divide (4).
    """
    px = h * w
    gy, gx, gr = grid_dims(h, w, sigma_spatial, sigma_range)
    rough = px * (max_disp + 1) * 8.0
    splat = px * 8.0
    refine = n_iters * bilateral_blur(gy, gx, gr)[0]
    slice_ = px * (8 * 6 + 4.0)
    return rough + splat + refine + slice_


def funnel_frame(h: int, w: int, motion: bool, n_scan: int, stage0: int,
                 n_found: int, factor: int = 8, patch: int = 20,
                 hidden: int = 8) -> float:
    """Operations one frame of the face-authentication funnel needs.

    Every frame: the motion gate's block means (one add per pixel) and the
    coarse difference (three per block).  A motion frame also: the
    summed-area tables of the frame and its square (five per pixel), the
    variance of every scan window (eight taps and six more), and the first
    cascade stage on every window (per stump: at most eight taps, a
    compare and a weighted vote, 18).  Later stages see only survivors,
    whose number the result does not give, so they are left out and the
    count is a lower bound.  Every window found is cropped to 20x20 and
    scored by the 400-8-1 network (two ops per multiply-add).
    """
    ops = h * w + 3.0 * (h // factor) * (w // factor)
    if motion:
        ops += 5.0 * h * w + n_scan * 14.0 + n_scan * stage0 * 18.0
        ops += n_found * 2.0 * (patch * patch * hidden + hidden)
    return ops
