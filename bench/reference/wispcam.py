"""Plain reference of the WISPCam face-authentication funnel.

Straight NumPy from the deployment's stated semantics (the configuration
file's ``motion``, ``scan``, ``nn`` and ``lut`` groups); it imports nothing
of the program.  Per frame of a served chunk:

1. motion gate: the mean absolute difference of 8x8 block means against
   the chunk's previous frame, above the threshold (a chunk's first frame
   has no reference and never passes);
2. Viola-Jones on motion frames: every scan window at every scale, its
   variance from the sums of the window and of its square, each trained
   Haar feature scaled to the window and evaluated as rectangle sums,
   normalized by the window's deviation and area, stump votes summed per
   stage against the stage threshold; the windows that pass every stage;
3. each window found, cropped to 20x20 by nearest rows and columns, scored
   by the 400-8-1 network in ``bits``-bit integers: symmetric per-tensor
   weight scales, the input and hidden activations on the known [0, 1]
   range, exact integer products, then rescale, bias and the table
   sigmoid.

``dtype`` is the float type of steps 1 and 2: float64 for the reference,
bfloat16 for the lower-precision control.  ``bits`` is 8 for the reference
and 4 for the control.
"""

from __future__ import annotations

import numpy as np

BASE = 20


def scan_groups(h: int, w: int, scale_factor: float, step: float,
                adaptive: bool, min_step: int):
    """Per scale, in scan order: (win, step, ys, xs, first index).  A
    window's index is its place in scale-major, row-major scan order."""
    out, first, win = [], 0, float(BASE)
    while win <= min(h, w):
        iw = int(round(win))
        s = max(min_step, int(round(step * iw))) if adaptive else max(1, int(step))
        ys = np.arange(0, h - iw + 1, s)
        xs = np.arange(0, w - iw + 1, s)
        out.append((iw, s, ys, xs, first))
        first += len(ys) * len(xs)
        win *= scale_factor
    return out


def scaled_rects(kind: int, y: int, x: int, h: int, w: int, win: int):
    """A 20x20 Haar feature scaled to a ``win`` window, as signed rectangles
    (sign, y, x, h, w).  Sizes round to the nearest pixel while keeping the
    two- or three-way split exact, and the feature stays inside the
    window."""
    s = win / BASE
    nsplit = 2 if kind < 2 else 3
    if kind in (0, 2):
        part = max(1, int(round(w / nsplit * s)))
        fw, fh = nsplit * part, max(1, int(round(h * s)))
        while fw > win:
            fw -= nsplit
        while fh > win:
            fh -= 1
    else:
        part = max(1, int(round(h / nsplit * s)))
        fh, fw = nsplit * part, max(1, int(round(w * s)))
        while fh > win:
            fh -= nsplit
        while fw > win:
            fw -= 1
    fy = min(max(int(round(y * s)), 0), win - fh)
    fx = min(max(int(round(x * s)), 0), win - fw)
    if kind == 0:
        p = fw // 2
        return [(1, fy, fx, fh, p), (-1, fy, fx + p, fh, p)]
    if kind == 1:
        p = fh // 2
        return [(1, fy, fx, p, fw), (-1, fy + p, fx, p, fw)]
    if kind == 2:
        p = fw // 3
        return [(1, fy, fx, fh, p), (-2, fy, fx + p, fh, p),
                (1, fy, fx + 2 * p, fh, p)]
    p = fh // 3
    return [(1, fy, fx, p, fw), (-2, fy + p, fx, p, fw),
            (1, fy + 2 * p, fx, p, fw)]


def _integral(img, dtype):
    ii = np.zeros((img.shape[0] + 1, img.shape[1] + 1), dtype)
    ii[1:, 1:] = np.cumsum(np.cumsum(img.astype(dtype), axis=0, dtype=dtype),
                           axis=1, dtype=dtype)
    return ii


def _rect(ii, ys, xs, ry, rx, rh, rw):
    """Sums of one rectangle at every window of a scale, (len(ys), len(xs)).
    ``ys`` and ``xs`` are evenly spaced, so each corner is a strided view."""
    sy = int(ys[1] - ys[0]) if len(ys) > 1 else 1
    sx = int(xs[1] - xs[0]) if len(xs) > 1 else 1

    def at(dy, dx):
        y0, x0 = int(ys[0]) + dy, int(xs[0]) + dx
        return ii[y0:y0 + sy * (len(ys) - 1) + 1:sy,
                  x0:x0 + sx * (len(xs) - 1) + 1:sx]
    return (at(ry + rh, rx + rw) - at(ry, rx + rw) - at(ry + rh, rx)
            + at(ry, rx))


def motion(frames, threshold: float, factor: int, dtype=np.float64):
    """(n,) bool: which frames of one chunk pass the motion gate."""
    f = np.asarray(frames).astype(dtype)
    n, h, w = f.shape
    h2, w2 = h // factor * factor, w // factor * factor
    blocks = f[:, :h2, :w2].reshape(n, h2 // factor, factor, w2 // factor,
                                    factor).mean(axis=(2, 4), dtype=dtype)
    score = np.abs(blocks[1:] - blocks[:-1]).mean(axis=(1, 2), dtype=dtype)
    return np.concatenate([[False], score > threshold])


def detect(frame, cascade: dict, scan: dict, dtype=np.float64) -> np.ndarray:
    """Sorted scan-window indices that pass every cascade stage."""
    h, w = frame.shape
    f = np.asarray(frame).astype(dtype)
    ii = _integral(f, dtype)
    ii2 = _integral(f * f, dtype)
    feats = cascade["feats"]
    thr = np.asarray(cascade["thresholds"], dtype)
    pol = np.asarray(cascade["polarity"], dtype)
    alpha = np.asarray(cascade["alphas"], dtype)
    found = []
    for win, _s, ys, xs, first in scan_groups(
            h, w, scan["scale_factor"], scan["step"], scan["adaptive"],
            scan["min_step_px"]):
        area = dtype(win * win)
        mu = _rect(ii, ys, xs, 0, 0, win, win) / area
        var = _rect(ii2, ys, xs, 0, 0, win, win) / area - mu * mu
        norm = np.sqrt(np.maximum(var, dtype(1e-6))) * area
        alive = np.ones((len(ys), len(xs)), bool)
        k = 0
        for size, stage_thr in zip(cascade["stage_sizes"],
                                   cascade["stage_thresholds"]):
            score = np.zeros(alive.shape, dtype)
            for _ in range(size):
                resp = np.zeros(alive.shape, dtype)
                for sign, ry, rx, rh, rw in scaled_rects(*feats[k], win):
                    resp = resp + dtype(sign) * _rect(ii, ys, xs, ry, rx,
                                                      rh, rw)
                pred = pol[k] * np.sign(resp / norm - thr[k])
                score = score + alpha[k] * np.where(pred == 0, dtype(1),
                                                    pred)
                k += 1
            alive &= score >= dtype(stage_thr)
        iy, ix = np.nonzero(alive)
        found.append(first + iy * len(xs) + ix)
    return np.sort(np.concatenate(found)) if found else np.zeros(0, int)


def positions(h: int, w: int, scan: dict) -> dict:
    """Window index -> (y, x, win) for every scan window."""
    out = {}
    for win, _s, ys, xs, first in scan_groups(
            h, w, scan["scale_factor"], scan["step"], scan["adaptive"],
            scan["min_step_px"]):
        for iy, y in enumerate(ys):
            for ix, x in enumerate(xs):
                out[first + iy * len(xs) + ix] = (int(y), int(x), win)
    return out


def crops(frame, windows, pos: dict) -> np.ndarray:
    """(n, 400) nearest-neighbour 20x20 crops of the given windows."""
    out = np.zeros((len(windows), BASE * BASE), np.float32)
    t = np.arange(BASE)
    for i, widx in enumerate(windows):
        y, x, win = pos[int(widx)]
        off = np.minimum(t * win // BASE, win - 1)
        out[i] = np.asarray(frame)[np.ix_(y + off, x + off)].reshape(-1)
    return out


def _quantize(w, bits: int):
    qmax = 2 ** (bits - 1) - 1
    w = np.asarray(w, np.float32)
    scale = float(max(np.abs(w).max(), 1e-12)) / qmax
    return np.clip(np.round(w / scale), -qmax, qmax).astype(np.int64), scale


def nn_scores(x, nn: dict, bits: int, lut: dict) -> np.ndarray:
    """(n,) scores of the 400-8-1 network on (n, 400) crops in [0, 1]."""
    qmax = 2 ** (bits - 1) - 1
    lo, hi, entries = float(lut["lo"]), float(lut["hi"]), int(lut["entries"])
    xs = np.linspace(lo, hi, entries, dtype=np.float32)
    table = (np.float32(1) / (np.float32(1) + np.exp(-xs))).astype(np.float32)

    def sigmoid(y):
        idx = np.clip((y - np.float32(lo)) / np.float32(hi - lo)
                      * np.float32(entries - 1), 0, entries - 1)
        return table[idx.astype(np.int64)]

    def layer(a_q, w, b, scale_in):
        w_q, scale_w = _quantize(w, bits)
        acc = (a_q @ w_q).astype(np.float32)
        y = acc * np.float32(scale_in * scale_w) + np.asarray(b, np.float32)
        return sigmoid(y.astype(np.float32))

    step = 1.0 / qmax
    x_q = np.clip(np.round(np.asarray(x, np.float32) / np.float32(step)),
                  -qmax, qmax).astype(np.int64)
    hid = layer(x_q, nn["w1"], nn["b1"], step)
    h_q = np.clip(np.round(hid / np.float32(step)), -qmax,
                  qmax).astype(np.int64)
    return layer(h_q, nn["w2"], nn["b2"], step)[:, 0]


def chunk(frames, n_real: int, cfg: dict, cascade: dict, nn: dict, pos: dict,
          dtype=np.float64, bits: int = 8) -> list:
    """Per real frame of one served chunk: (motion, windows found, scores)."""
    mot = motion(frames, cfg["motion"]["threshold"], cfg["motion"]["factor"],
                 dtype)
    out = []
    for j in range(n_real):
        if not mot[j]:
            out.append((False, np.zeros(0, int), np.zeros(0, np.float32)))
            continue
        wins = detect(frames[j], cascade, cfg["scan"], dtype)
        s = (nn_scores(crops(frames[j], wins, pos), nn, bits,
                       cfg["nn"]["lut"]) if len(wins)
             else np.zeros(0, np.float32))
        out.append((True, wins, s))
    return out


def compare(program: list, reference: list, auth_threshold: float,
            margin: float) -> dict:
    """The numbers compared, over frames given as (motion, windows, scores,
    auth decisions) from the program and (motion, windows, scores) from the
    reference.  An auth decision counts as flipped where the reference's
    score lies more than ``margin`` from the threshold and the decision
    disagrees with it."""
    motion_flips = auth_flips = sym = n_ref = 0
    gap = 0.0
    for (pm, pw, ps, pa), (rm, rw, rs) in zip(program, reference):
        motion_flips += int(bool(pm) != bool(rm))
        pset, rset = set(map(int, pw)), set(map(int, rw))
        sym += len(pset ^ rset)
        n_ref += len(rset)
        rmap = dict(zip(map(int, rw), rs))
        for widx, s, a in zip(map(int, pw), ps, pa):
            if widx in rmap:
                r = float(rmap[widx])
                gap = max(gap, abs(float(s) - r))
                if abs(r - auth_threshold) > margin:
                    auth_flips += int(bool(a) != (r > auth_threshold))
    return {"motion_flips": motion_flips,
            "window_miss_share": sym / max(n_ref, 1),
            "score_gap_max": gap, "auth_flips": auth_flips}
