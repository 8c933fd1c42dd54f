"""Blocking heuristics (§II-B/C/D on TPU constraints): VMEM budget
respected, MXU-aligned blocks, divisor mode, loop-order rule, and a VMEM
model that prices what the kernels ask for."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.blocking import (VMEM_BUDGET, conv_blocking,
                                 conv_blocking_analytic, conv_working_set,
                                 divisors, matmul_blocking,
                                 tiled_conv_buffers)
from repro.kernels.conv2d_direct import (VMEM_HEADROOM, conv2d_direct,
                                         pipelined_vmem_bytes)
from repro.kernels.conv2d_q8 import conv2d_q8
from repro.kernels.conv2d_wu import conv2d_wu
from repro.core.wu_strategy import choose_wu_strategy, hybrid_copies
from repro.graph.topology import RESNET50_LAYERS


def test_resnet_layers_fit_vmem():
    for lid, l in RESNET50_LAYERS.items():
        if l["c"] < 8:
            continue  # conv1 takes the im2col path
        blk = conv_blocking(h=l["h"], w=l["w"], c=l["c"], k=l["k"],
                            r=l["r"], s=l["s"], stride=l["stride"],
                            padding=l["r"] // 2)
        assert blk.vmem_bytes <= VMEM_BUDGET, (lid, blk)
        assert l["k"] % blk.k_blk == 0


def test_loop_order_rule():
    b1 = conv_blocking(h=56, w=56, c=256, k=64, r=1, s=1, stride=1,
                       padding=0)
    b3 = conv_blocking(h=56, w=56, c=64, k=64, r=3, s=3, stride=1,
                       padding=1)
    assert b1.order == "npkc"   # paper §II-C: pull C_b in for 1x1
    assert b3.order == "nkpc"


@settings(max_examples=30, deadline=None)
@given(h=st.integers(7, 224), c=st.sampled_from([8, 64, 256, 1024]),
       k=st.sampled_from([8, 64, 256]), r=st.sampled_from([1, 3, 5, 7]),
       stride=st.integers(1, 2))
def test_conv_blocking_properties(h, c, k, r, stride):
    blk = conv_blocking(h=h, w=h, c=c, k=k, r=r, s=r, stride=stride,
                        padding=r // 2)
    p = (h + 2 * (r // 2) - r) // stride + 1
    assert 1 <= blk.rb_p <= max(p, 1)
    assert k % blk.k_blk == 0
    assert blk.k_blk <= 128


@settings(max_examples=20, deadline=None)
@given(h=st.integers(7, 56), r=st.sampled_from([1, 3]))
def test_divisor_mode(h, r):
    blk = conv_blocking(h=h, w=h, c=64, k=64, r=r, s=r, stride=1,
                        padding=r // 2, require_divisor=True)
    p = h + 2 * (r // 2) - r + 1
    assert p % blk.rb_p == 0


def test_analytic_vmem_model_matches_kernel_residency():
    """The VMEM model must charge what each kernel actually keeps resident:
    a row band for the tiled fwd, a C_blk plane slice for streams, the
    full-C plane for wu — not the (much smaller) band for all three."""
    big = dict(h=512, w=512, c=64, k=64, r=3, s=3, stride=1, padding=1)
    hp, wp = 512 + 2 + 3, 512 + 2
    plane = hp * wp * 64 * 4
    tiled = conv_blocking_analytic(**big)
    streams = conv_blocking_analytic(**big, whole_plane=True)
    wu = conv_blocking_analytic(**big, require_divisor=True)
    assert tiled.vmem_bytes < plane                   # band, not plane
    assert streams.vmem_bytes >= hp * wp * streams.c_blk * 4
    assert wu.vmem_bytes >= plane                     # full-C plane resident


VMEM_CASES = [
    # kind, h, w, c, k, r, stride, pad, rb_p, c_blk
    ("fwd", 14, 14, 64, 128, 3, 1, 1, 4, None),      # Q off the tile
    ("fwd", 56, 56, 256, 256, 1, 2, 0, 8, 128),      # 1x1 s2, C blocked
    ("fwd", 224, 224, 8, 64, 7, 2, 3, 2, None),      # stem: 4 phase planes
    ("wu", 28, 28, 128, 128, 3, 2, 1, 7, None),
    ("wu", 7, 7, 512, 256, 3, 1, 1, 7, 128),
    ("q8", 28, 28, 128, 128, 3, 1, 1, 16, None),     # int8 sublane tile
    ("q8", 14, 14, 256, 256, 1, 2, 0, 7, 128),
]


def _vmem_request(fn, *args) -> int:
    """The scoped-VMEM limit the traced kernel asks Mosaic for."""
    eqns = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1
    return eqns[0].params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes


@pytest.mark.parametrize("case", VMEM_CASES)
def test_working_set_is_the_kernels_vmem_request(case):
    """The VMEM model prices the buffers each tiled kernel declares: the
    kernel's scoped-VMEM request is exactly those buffers double-buffered
    and tile-padded plus the fixed headroom, and ``conv_working_set`` is
    the same buffers in logical pixels (rows of Q), one copy each."""
    kind, h, w, c, k, r, stride, pad, rb_p, c_blk = case
    p = (h + 2 * pad - r) // stride + 1
    k_blk = min(k, 128)
    x = jnp.zeros((1, h, w, c), jnp.float32)
    wt = jnp.zeros((r, r, c, k), jnp.float32)
    if kind == "fwd":
        fn = lambda x, wt: conv2d_direct(
            x, wt, stride=stride, padding=pad, rb_p=rb_p, k_blk=k_blk,
            c_blk=c_blk, whole_plane=False, interpret=True)
    elif kind == "wu":
        wt = jnp.zeros((1, p, p, k), jnp.float32)          # dO
        fn = lambda x, do: conv2d_wu(
            x, do, stride=stride, padding=pad, filter_rs=(r, r), b_p=rb_p,
            k_blk=k_blk, c_blk=c_blk, whole_plane=False, interpret=True)
    else:
        x, wt = x.astype(jnp.int8), wt.astype(jnp.int8)
        fn = lambda x, wt: conv2d_q8(
            x, wt, x_scale=jnp.float32(1), w_scale=jnp.ones((k,)),
            stride=stride, padding=pad, rb_p=rb_p, k_blk=k_blk, c_blk=c_blk,
            whole_plane=False, interpret=True)
    db = 1 if kind == "q8" else 4
    blocks, scratch = tiled_conv_buffers(
        q=p, r=r, s=r, stride=stride, rb_p=rb_p, c_blk=c_blk or c,
        k_blk=k_blk, dtype_bytes=db, kind=kind)
    assert (_vmem_request(fn, x, wt) - VMEM_HEADROOM
            == pipelined_vmem_bytes(blocks, scratch))
    blocks, scratch = tiled_conv_buffers(
        q=p, r=r, s=r, stride=stride, rb_p=rb_p, c_blk=c_blk or c,
        k_blk=k_blk, dtype_bytes=db, kind=kind, cols=p)
    assert conv_working_set(
        h=h, w=w, c=c, k_blk=k_blk, r=r, s=r, q=p, rb_p=rb_p, padding=pad,
        dtype_bytes=db, stride=stride, c_blk=c_blk, kind=kind) \
        == sum(np.prod(sh) * b for sh, b in blocks + scratch)


def test_matmul_blocking_budget():
    blk = matmul_blocking(4096, 4096, 24576, dtype_bytes=2)
    assert blk.vmem_bytes <= VMEM_BUDGET
    assert 24576 % blk.bk == 0


def test_wu_strategy_tradeoff():
    """Small spatial layer (dW dominates) -> 'shared'; big spatial layer
    (activations dominate) -> 'copies' (paper §II-J)."""
    small = choose_wu_strategy(n=28, c=2048, k=512, h=7, w=7, p=7, q=7,
                               r=1, s=1, n_workers=64)
    big = choose_wu_strategy(n=28, c=64, k=64, h=56, w=56, p=56, q=56,
                             r=3, s=3, n_workers=64)
    assert small.strategy == "shared"
    assert big.strategy == "copies"


def test_hybrid_copies_bounds():
    m = hybrid_copies(n=64, dw_bytes=10_000, act_bytes=100_000_000,
                      n_workers=64)
    assert 1 <= m <= 64


def test_divisors():
    assert divisors(12) == [1, 2, 3, 4, 6, 12]
