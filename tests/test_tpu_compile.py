"""Ahead-of-time compiles of the main path's conv kernels for a described
TPU v5e chip, at ResNet-50 widths (batch 8, the analytic blockings).

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached, and refuses what Mosaic would refuse on
the device (block shapes off the (8, 128) tile, element offsets it cannot
prove aligned, scoped VMEM overflow).  Interpret mode sees none of that.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest-xdist workers all import
this file.  Where it cannot be described, every test here skips.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import duality
from repro.core.blocking import conv_blocking_analytic
from repro.kernels.conv2d_direct import conv2d_direct
from repro.kernels.conv2d_q8 import conv2d_q8
from repro.kernels.conv2d_wu import conv2d_wu

BATCH = 8

# h, c, k, r, stride: the forward shapes of ResNet-50 that exercise every
# band geometry — 3x3 at each stage width, the 1x1 expansions and the
# stride-2 3x3 and 1x1 downsampling convs
FWD_SHAPES = [
    (56, 64, 64, 3, 1),
    (56, 64, 256, 1, 1),
    (56, 128, 128, 3, 2),
    (28, 128, 128, 3, 1),
    (14, 256, 256, 3, 1),
    (7, 512, 512, 3, 1),
    (56, 256, 512, 1, 2),
]
WU_SHAPES = [(56, 64, 64, 3, 1), (14, 256, 256, 3, 1)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _blocking(x_shape, w_shape, *, stride, padding, kind, dtype_bytes=4):
    _, h, w, c = x_shape
    r, s, _, k = w_shape
    return conv_blocking_analytic(h=h, w=w, c=c, k=k, r=r, s=s, stride=stride,
                                  padding=padding, dtype_bytes=dtype_bytes,
                                  kind=kind)


def _direct(x, w, stride, padding, kind="fwd"):
    blk = _blocking(x.shape, w.shape, stride=stride, padding=padding,
                    kind=kind)
    return conv2d_direct(x, w, stride=stride, padding=padding,
                         rb_p=blk.rb_p, k_blk=blk.k_blk, c_blk=blk.c_blk,
                         order=blk.order, whole_plane=False)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=sharding)
            for sh, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("h,c,k,r,stride", FWD_SHAPES)
def test_conv2d_direct_compiles(one_chip, h, c, k, r, stride):
    _compile(lambda x, w: _direct(x, w, stride, r // 2), one_chip,
             ((BATCH, h, h, c), jnp.float32), ((r, r, c, k), jnp.float32))


def test_phase_bwd_data_dual_compiles(one_chip):
    """dI of the stride-2 3x3 conv (56² -> 28², 128 -> 128): the stride²
    phase sub-convs, each a forward launch of the tiled kernel."""
    h, c, k = 56, 128, 128

    def bwd_data(do, w):
        return duality.phase_bwd_data(
            do, w, stride=2, padding=1, input_hw=(h, h),
            conv_fn=lambda a, b, st, pd: _direct(a, b, st, pd, kind="bwd"))

    _compile(bwd_data, one_chip, ((BATCH, h // 2, h // 2, k), jnp.float32),
             ((3, 3, c, k), jnp.float32))


@pytest.mark.parametrize("h,c,k,r,stride", WU_SHAPES)
def test_conv2d_wu_compiles(one_chip, h, c, k, r, stride):
    pad = r // 2
    p = (h + 2 * pad - r) // stride + 1
    blk = _blocking((BATCH, h, h, c), (r, r, c, k), stride=stride,
                    padding=pad, kind="wu")

    def wu(x, do):
        return conv2d_wu(x, do, stride=stride, padding=pad, filter_rs=(r, r),
                         b_p=blk.rb_p, k_blk=blk.k_blk, c_blk=blk.c_blk,
                         whole_plane=False)

    _compile(wu, one_chip, ((BATCH, h, h, c), jnp.float32),
             ((BATCH, p, p, k), jnp.float32))


def test_conv2d_q8_compiles(one_chip):
    h, c, k, r = 28, 128, 128, 3
    blk = _blocking((BATCH, h, h, c), (r, r, c, k), stride=1, padding=1,
                    kind="q8", dtype_bytes=1)

    def q8(x_q, w_q, w_scale):
        return conv2d_q8(x_q, w_q, x_scale=jnp.float32(0.05),
                         w_scale=w_scale, stride=1, padding=1,
                         rb_p=blk.rb_p, k_blk=blk.k_blk, c_blk=blk.c_blk,
                         order=blk.order, whole_plane=False)

    _compile(q8, one_chip, ((BATCH, h, h, c), jnp.int8),
             ((r, r, c, k), jnp.int8), ((k,), jnp.float32))
