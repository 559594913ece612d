"""Seeded inputs for the benchmark's traffic mixes.

Copies of the repository's synthetic camera generators (WISPCam security
video, textured stereo pairs), kept here so that a change to the program
cannot move the yardstick.  A seed is anything ``numpy.random.default_rng``
takes; the traffic mixes pass ``(run seed, item index)`` pairs so every run
seed draws a fresh pool of the same sizes.
"""

from __future__ import annotations

import numpy as np


def face_patch(rng, identity_vec, size: int = 20, jitter: float = 1.0,
               light: float = 0.0) -> np.ndarray:
    """One parametric 20x20 face in [0, 1] (eyes, nose, mouth blobs)."""
    y, x = np.mgrid[0:size, 0:size] / (size - 1)
    iv = identity_vec

    def blob(cy, cx, sy, sx, amp):
        return amp * np.exp(-(((y - cy) / sy) ** 2 + ((x - cx) / sx) ** 2))

    jy, jx = rng.normal(0, jitter / size, 2)
    face = np.zeros((size, size))
    face += blob(0.5 + jy, 0.5 + jx, 0.42 + 0.05 * iv[0],
                 0.34 + 0.05 * iv[1], 0.8)
    eye_dy = 0.36 + 0.04 * iv[2]
    eye_dx = 0.20 + 0.03 * iv[3]
    face -= blob(eye_dy + jy, 0.5 - eye_dx + jx, 0.06, 0.07 + 0.02 * iv[4],
                 0.55)
    face -= blob(eye_dy + jy, 0.5 + eye_dx + jx, 0.06, 0.07 + 0.02 * iv[4],
                 0.55)
    face += blob(0.55 + jy, 0.5 + jx, 0.16 + 0.03 * iv[5], 0.05, 0.25)
    face -= blob(0.76 + 0.03 * iv[6] + jy, 0.5 + jx, 0.05,
                 0.16 + 0.04 * iv[7], 0.45)
    face = face + light + rng.normal(0, 0.04, face.shape)
    return np.clip(face + 0.1, 0, 1)


def security_video(n_frames: int = 62, h: int = 144, w: int = 176,
                   motion_frames: int = 12, faces_in_motion: float = 0.66,
                   seed=1) -> np.ndarray:
    """A static scene with a poster face and ``motion_frames`` frames in
    which a walker (with a face in ``faces_in_motion`` of them) appears.
    Frame 0 is the static reference.  Returns (n_frames, h, w) float32."""
    rng = np.random.default_rng(seed)
    motion_frames = max(0, min(motion_frames, n_frames - 1))
    yb, xb = np.mgrid[0:h, 0:w]
    background = (0.45 + 0.1 * np.sin(xb / 17.0) + 0.08 * np.cos(yb / 23.0)
                  + 0.05 * rng.standard_normal((h, w)))
    poster = face_patch(rng, rng.uniform(-1, 1, 8), size=20)
    background[20:40, 140:160] = (0.7 * poster
                                  + 0.3 * background[20:40, 140:160])
    background = np.clip(background, 0, 1)
    ids = rng.uniform(-1, 1, (4, 8))
    moving = set(rng.choice(np.arange(1, n_frames), motion_frames,
                            replace=False))
    frames = []
    for t in range(n_frames):
        f = background.copy()
        if t in moving:
            px = int(rng.uniform(10, w - 30))
            py = int(rng.uniform(30, h - 60))
            f[py:py + 46, px:px + 14] *= 0.55
            if rng.uniform() < faces_in_motion:
                fp = face_patch(rng, ids[rng.integers(0, len(ids))], size=20,
                                jitter=rng.uniform(0.5, 1.2))
                f[py - 20:py, px - 3:px + 17] = fp
        f = np.clip(f + rng.normal(0, 0.01, f.shape), 0, 1)
        frames.append(f.astype(np.float32))
    return np.stack(frames)


def stereo_pair(h: int = 256, w: int = 320, max_disp: int = 12, seed=2):
    """A multi-scale noise texture and a piecewise-smooth disparity field
    (a plane and two boxes); the right view is the left shifted by it.
    Returns (left, right, disparity), float32."""
    rng = np.random.default_rng(seed)
    y, x = np.mgrid[0:h, 0:w]
    tex = np.zeros((h, w))
    for s_ in (4, 8, 16, 32):
        n = rng.standard_normal((h // s_ + 2, w // s_ + 2))
        tex += np.kron(n, np.ones((s_, s_)))[:h, :w] / np.sqrt(s_)
    tex = (tex - tex.min()) / (np.ptp(tex) + 1e-9)
    disp = 2.0 + 2.0 * (y / h)
    for _ in range(2):
        cy = rng.integers(h // 4, 3 * h // 4)
        cx = rng.integers(w // 4, 3 * w // 4)
        hh, ww = rng.integers(h // 8, h // 4), rng.integers(w // 8, w // 4)
        d = rng.uniform(max_disp * 0.6, max_disp)
        disp[max(cy - hh, 0):cy + hh, max(cx - ww, 0):cx + ww] = d
    xs = np.clip(x - disp.astype(int), 0, w - 1)
    right = tex[y, xs]
    return (tex.astype(np.float32), right.astype(np.float32),
            disp.astype(np.float32))


def quantize(img: np.ndarray, bits: int) -> np.ndarray:
    """A camera's ``bits``-bit pixel values, k / 2**bits, as float32."""
    levels = 2 ** bits
    return (np.minimum(np.floor(img * levels), levels - 1)
            / levels).astype(np.float32)
