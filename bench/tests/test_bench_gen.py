"""The benchmark's copies of the generators: seeded, and frozen at what
they gave when copied from the program's own generators."""

import numpy as np

from bench import gen


def test_security_video_is_fixed_by_its_seed():
    a = gen.security_video(n_frames=6, motion_frames=3, seed=(7, 1))
    b = gen.security_video(n_frames=6, motion_frames=3, seed=(7, 1))
    c = gen.security_video(n_frames=6, motion_frames=3, seed=(8, 1))
    assert a.shape == (6, 144, 176) and a.dtype == np.float32
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_stereo_pair_is_fixed_by_its_seed():
    a = gen.stereo_pair(40, 64, max_disp=6, seed=(2 ** 31 + 5, 0, 0))
    b = gen.stereo_pair(40, 64, max_disp=6, seed=(2 ** 31 + 5, 0, 0))
    c = gen.stereo_pair(40, 64, max_disp=6, seed=(2 ** 31 + 6, 0, 0))
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[0], c[0])


def test_copies_are_frozen():
    """The copies give the frames they gave when copied from
    ``repro.camera.synthetic``: a change to the program cannot move them."""
    v = gen.security_video(n_frames=8, motion_frames=4, seed=3)
    assert np.isclose(v.astype(np.float64).sum(), 93565.6762833288,
                      rtol=1e-9)
    assert np.allclose(v[[1, 4, 7], [10, 70, 130], [20, 90, 160]],
                       [0.65804123878479, 0.27203449606895447,
                        0.4821680784225464], rtol=1e-6)
    left, right, disp = gen.stereo_pair(48, 64, seed=4)
    assert np.allclose([left.astype(np.float64).sum(),
                        right.astype(np.float64).sum(),
                        disp.astype(np.float64).sum()],
                       [1328.9150111675262, 1339.2396321594715,
                        12426.192474842072], rtol=1e-9)
    assert np.allclose(left[[3, 20, 40], [5, 33, 60]],
                       [0.5169981122016907, 0.5608194470405579,
                        0.3133893311023712], rtol=1e-6)


def test_quantize_gives_camera_levels():
    q = gen.quantize(np.array([0.0, 0.5, 0.99999, 1.0], np.float32), 8)
    assert np.array_equal(q * 256, [0, 128, 255, 255])
