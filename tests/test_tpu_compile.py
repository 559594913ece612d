"""Compile rehearsal for a described TPU v5e: the main-path kernels at their
``configs.shapes.KERNEL_SHAPES`` sizes go through the chip's compiler, with
no chip attached and nothing run.

A Pallas kernel that interpret mode accepts can still be refused by Mosaic
(unaligned slices, unsupported gathers and reshapes); these cases catch
that here.  Kernels meant to stay Pallas on the TPU must show up as a
``tpu_custom_call`` in the compiled HLO; the Haar front-end is XLA on every
backend (camera/viola_jones.haar_stage_scores) and must not.  Its stage 0,
which scores the whole scan grid, reads its taps as slices and must compile
with no gather under its ``vj0`` scope; so must the rig's slice under
``slice``.

The dispatch helpers ask ``jax.default_backend()``, which is the CPU here,
so each case calls the TPU branch explicitly.  The topology is described
only inside the module fixture: libtpu may be loaded by one process at a
time, so nothing here may touch it while test files are imported.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.shapes import KERNEL_SHAPES


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on the first described chip."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _case(kernel, name):
    return next(c for c in KERNEL_SHAPES[kernel] if c["case"] == name)


def _compiled_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_integral_image_fa_native(spec):
    from repro.kernels.integral_image.ops import integral_image

    c = _case("integral_image", "fa_native")
    hlo = _compiled_hlo(integral_image, spec((c["n"], c["h"], c["w"])))
    assert "tpu_custom_call" in hlo


def test_integral_image_vr_4k_eye(spec):
    """Wider than one tile: the column-tiled path (lane-aligned top-row
    carry, left carry across column blocks)."""
    from repro.kernels.integral_image.ops import integral_image

    c = _case("integral_image", "vr_4k_eye")
    hlo = _compiled_hlo(integral_image, spec((c["n"], c["h"], c["w"])))
    assert "tpu_custom_call" in hlo


def test_haar_stage_fa_scan_compiles_on_xla(spec):
    from repro.camera.viola_jones import CORNER_SLOTS, haar_stage_scores

    # one Table-I stage (33 stumps) over a 144x176 frame's scan grid
    n, sz, k, n_scales, size = 5868, 33, CORNER_SLOTS, 4, 145 * 177
    hlo = _compiled_hlo(
        haar_stage_scores, spec((size,)), spec((n,), jnp.int32),
        spec((n,), jnp.int32), spec((n,)),
        spec((n_scales, sz, k), jnp.int32), spec((sz, k)),
        spec((sz,)), spec((sz,)), spec((sz,)))
    assert "tpu_custom_call" not in hlo


def test_fused_detector_stage0_reads_no_gather(spec):
    """A fleet dispatch's detector (16 frames of 144x176, the scan the
    fleet runs): stage 0 reads slices of the integral image; the survivor
    stages still gather."""
    import numpy as np

    from repro.camera.viola_jones import Cascade, FusedDetector, HaarFeature

    z = np.load(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "tests", "data",
        "toy_detector.npz"))
    casc = Cascade(feats=[HaarFeature(*map(int, r)) for r in z["feats"]],
                   thresholds=z["thresholds"], polarity=z["polarity"],
                   alphas=z["alphas"],
                   stage_sizes=[int(s) for s in z["stage_sizes"]],
                   stage_thresholds=z["stage_thresholds"])
    det = FusedDetector(casc, 144, 176, use_pallas=True)
    hlo = _compiled_hlo(det.traceable_apply, spec((16, 144, 176)),
                        *(spec(a.shape, a.dtype) for a in det.apply_consts))
    gathers = [ln for ln in hlo.splitlines() if " gather(" in ln]
    assert any("vj1" in ln for ln in gathers)
    assert not [ln for ln in gathers if "vj0" in ln]


def test_rig_slice_reads_no_gather(spec):
    """The rig pair's slice at 3840x2160 (grids of 136 x 241 x 17, vmapped
    over one pair, as ``VRRigExecutor.depth_maps`` runs it): the corners
    are static slices of the grid and a select over its bins, so no
    gather carries the ``slice`` scope.  Nor is a grid row broadcast over
    its 16 pixel rows once per bin in memory: that took 2.2 GB of
    temporaries, and with the image's row blocks the slice takes under
    0.1 GB."""
    from repro.camera.bssa import GridSpec, slice_grid

    grid = GridSpec(16, 16.0)

    def scoped(v, w, img):
        with jax.named_scope("slice"):
            return slice_grid(v, w, img, grid)

    g = spec((1, *grid.dims(2160, 3840)))
    compiled = jax.jit(jax.vmap(scoped)).lower(
        g, g, spec((1, 2160, 3840))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    hlo = compiled.as_text()
    assert "vmap(slice)" in hlo
    assert not [ln for ln in hlo.splitlines()
                if " gather(" in ln and "slice" in ln]


@pytest.mark.parametrize("name", ["fa_nn_l1", "fa_nn_l2"])
def test_quant_matmul_nn_layers(spec, name):
    from repro.kernels.quant_matmul.ops import quant_matmul_static

    c = _case("quant_matmul", name)
    m, k, n = c["m"], c["k"], c["n"]

    def layer(x, w, lut, bias):
        return quant_matmul_static(x, w, lut, scale_x=1 / 127,
                                   scale_w=1 / 127, bias=bias)

    hlo = _compiled_hlo(layer, spec((m, k), jnp.int8), spec((k, n), jnp.int8),
                        spec((256,)), spec((n,)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name", ["fa_motion_cut", "vr_depth_cut"])
def test_wire_codec_roundtrip(spec, name):
    from repro.kernels.wire_codec.ops import wire_roundtrip

    c = _case("wire_codec", name)

    def roundtrip(x):
        return wire_roundtrip(x, bits=c["bits"], use_pallas=True)

    hlo = _compiled_hlo(roundtrip, spec((c["n_values"],)))
    assert hlo.count("tpu_custom_call") >= 2          # encode + decode


def test_bilateral_blur_vr_stereo(spec):
    from repro.camera.bssa import GridSpec
    from repro.kernels.bilateral_blur.ops import refine_grid

    c = _case("bilateral_blur", "vr_stereo")
    grid = GridSpec(c["sigma_spatial"], c["sigma_range"]).dims(c["h"], c["w"])

    def refine(v, w):
        return refine_grid(v, w, block_gy=c["block_gy"], use_pallas=True)

    hlo = _compiled_hlo(refine, spec(grid), spec(grid))
    assert "tpu_custom_call" in hlo
