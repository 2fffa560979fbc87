"""Compile-only checks of the training path's kernels for a TPU v5e.

Each test compiles one kernel, with ``interpret=False``, for a v5e chip
that is described but not attached, at the shapes VGG16 resolves at
224x224 and batch 8 (block 128x128x128).  What the chip's compiler
refuses here — blocks off the (8, 128) tiling, SMEM past its 1 MiB, ops
Mosaic cannot lower — fails in CI instead of on the chip.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.masked_matmul import (
    grouped_compact_masked_matmul_kernel,
    grouped_masked_matmul_kernel,
)
from repro.kernels.relu_encode import relu_encode_kernel
from repro.kernels.shapes import slab_rows

BLOCK = 128
# dX GEMMs of VGG16 at 224x224, batch 8, padded to the 128 block, with the
# emit granularity the conv engine asks for (per-pixel rows, channel cells
# of gcd(C, 128)).
GEMMS = {
    # conv2: (8·224·224, 9·64 -> 640) @ (640, 64 -> 128); the largest M
    "conv2_dx": ((401408, 640, 128), (1, 64)),
    # conv9: (8·28·28, 9·512) @ (4608, 512)
    "conv9_dx": ((6272, 4608, 512), (1, 128)),
}
EPILOGUES = {"none": (False, None), "sigma": (True, None),
             "sigma_emit": (True, "gemm")}


@pytest.fixture(scope="module")
def chip():
    """One described (not attached) v5e chip, as a sharding."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("epilogue", list(EPILOGUES))
@pytest.mark.parametrize("gemm", list(GEMMS))
@pytest.mark.parametrize("schedule", ["predicated", "compact"])
def test_grouped_gemm_compiles(chip, schedule, gemm, epilogue):
    (m, k, n), gran = GEMMS[gemm]
    has_mult, emit = EPILOGUES[epilogue]
    emit_gran = gran if emit else None
    ni, nk, nj = m // BLOCK, k // BLOCK, n // BLOCK
    f32, i32 = jnp.float32, jnp.int32
    shapes = [((1, m, k), f32), ((1, k, n), f32)]
    blocks = dict(bm=BLOCK, bk=BLOCK, bn=BLOCK, emit_gran=emit_gran,
                  interpret=False)
    if schedule == "predicated":
        shapes += [((1, ni, nj), i32), ((1, ni, nk), i32), ((1, nk, nj), i32)]

        def fn(a, b, om, am, bmk, mult=None):
            return grouped_masked_matmul_kernel(
                a, b, om, am, bmk, epilogue_mult=mult, **blocks)
    else:
        s = ni * nj
        shapes += [((s,), i32), ((s,), i32), ((s,), i32), ((1,), i32),
                   ((1, ni, nk), i32), ((1, nk, nj), i32)]

        def fn(a, b, gg, ii, jj, na, am, bmk, mult=None):
            return grouped_compact_masked_matmul_kernel(
                a, b, gg, ii, jj, na, am, bmk, epilogue_mult=mult, **blocks)
    if has_mult:
        shapes.append(((1, m, n), f32))
    compiled = _compile(chip, fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def test_relu_encode_compiles_at_conv1_activation(chip):
    m, c, gc = 401408, 64, 64            # conv1's output, (8·224·224, 64)
    lr = slab_rows(m, 1, c)
    compiled = _compile(
        chip, lambda z: relu_encode_kernel(z, bm=1, bn=gc, lr=lr, lc=c,
                                           interpret=False),
        ((m, c), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_queue_builder_compiles_at_conv_tile_bitmap(chip):
    mb, nb = 3136, 1                     # conv2 dX output tiles
    _compile(chip, lambda b: ops.build_queue(b, capacity=mb * nb),
             ((mb, nb), jnp.int32))
