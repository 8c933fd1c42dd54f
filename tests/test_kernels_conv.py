"""Per-kernel allclose vs the pure-jnp oracle (interpret mode), swept over
shapes / strides / dtypes, plus hypothesis property sweeps.

The forward kernel runs *tiled* by default (full-row band streaming over
stride-phase planes, C_b accumulation — DESIGN.md §9); the legacy whole-plane
variant is pinned explicitly so both input strategies stay bit-exact."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.shapes import STEM_CONV, STEM_CONV_HALF
from repro.core.blocking import conv_blocking_analytic, conv_working_set
from repro.tune.space import out_dim
from repro.kernels import ref
from repro.kernels.conv2d_direct import conv2d_direct, pad_input
from repro.kernels.conv2d_streams import conv2d_streams_auto
from repro.kernels.conv2d_wu import conv2d_wu

CASES = [
    # n, h, w, c, k, r, stride, pad, rb_p
    (2, 8, 8, 8, 16, 3, 1, 1, 4),
    (1, 14, 14, 16, 32, 1, 1, 0, 7),
    (2, 16, 16, 8, 8, 3, 2, 1, 4),
    (1, 7, 7, 8, 16, 3, 1, 1, 7),
    (1, 9, 9, 8, 8, 3, 1, 1, 4),      # ceil-div row grid
    (1, 8, 8, 8, 8, 1, 2, 0, 2),
    (1, 12, 12, 8, 8, 5, 1, 2, 3),    # 5x5 filter
]


def _data(rng, n, h, w, c, k, r, dtype=np.float32):
    x = rng.standard_normal((n, h, w, c)).astype(dtype)
    wt = (rng.standard_normal((r, r, c, k)) * 0.1).astype(dtype)
    return jnp.asarray(x), jnp.asarray(wt)


@pytest.mark.parametrize("case", CASES)
def test_conv2d_direct_matches_ref(rng, case):
    n, h, w, c, k, r, stride, pad, rb_p = case
    x, wt = _data(rng, n, h, w, c, k, r)
    out = conv2d_direct(x, wt, stride=stride, padding=pad, rb_p=rb_p,
                        interpret=True)
    exp = ref.conv2d(x, wt, stride=stride, padding=pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_direct_bf16(rng):
    x, wt = _data(rng, 1, 8, 8, 8, 16, 3, dtype=np.float32)
    x, wt = x.astype(jnp.bfloat16), wt.astype(jnp.bfloat16)
    out = conv2d_direct(x, wt, stride=1, padding=1, rb_p=4, interpret=True)
    exp = ref.conv2d(x, wt, stride=1, padding=1)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=0.05, atol=0.05)


def test_conv2d_fused_epilogue(rng):
    x, wt = _data(rng, 1, 8, 8, 8, 16, 3)
    b = jnp.asarray(rng.standard_normal(16), jnp.float32)
    sc = jnp.asarray(rng.standard_normal(16), jnp.float32)
    sh = jnp.asarray(rng.standard_normal(16), jnp.float32)
    res = jnp.asarray(rng.standard_normal((1, 8, 8, 16)), jnp.float32)
    out = conv2d_direct(x, wt, stride=1, padding=1, bias=b, scale=sc,
                        shift=sh, residual=res, relu=True, rb_p=4,
                        interpret=True)
    exp = ref.conv2d_fused(x, wt, stride=1, padding=1, bias=b, scale=sc,
                           shift=sh, residual=res, relu=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("case", [c for c in CASES if c[1] != 9])
def test_conv2d_wu_matches_vjp(rng, case):
    n, h, w, c, k, r, stride, pad, bp = case
    p = (h + 2 * pad - r) // stride + 1
    if p % bp:
        bp = 1
    x, _ = _data(rng, n, h, w, c, k, r)
    do = jnp.asarray(rng.standard_normal((n, p, p, k)), jnp.float32)
    out = conv2d_wu(x, do, stride=stride, padding=pad, filter_rs=(r, r),
                    b_p=bp, interpret=True)
    exp = ref.conv2d_bwd_weights(x, do, stride=stride, padding=pad,
                                 filter_rs=(r, r))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("order", ["nkpc", "npkc", "knpc"])
def test_conv2d_streams_matches_ref(rng, order):
    x, wt = _data(rng, 2, 8, 8, 16, 16, 3)
    b = jnp.asarray(rng.standard_normal(16), jnp.float32)
    out = conv2d_streams_auto(x, wt, stride=1, padding=1, bias=b, relu=True,
                              rb_p=4, k_blk=8, c_blk=8, order=order,
                              interpret=True)
    exp = ref.conv2d_fused(x, wt, stride=1, padding=1, bias=b, relu=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(1, 2), hw=st.integers(6, 12),
    c=st.sampled_from([8, 16]), k=st.sampled_from([8, 16]),
    r=st.sampled_from([1, 3]), stride=st.integers(1, 2),
    rb_p=st.integers(1, 4), seed=st.integers(0, 2**31 - 1),
)
def test_conv2d_direct_property(n, hw, c, k, r, stride, rb_p, seed):
    rng = np.random.default_rng(seed)
    pad = r // 2
    x = jnp.asarray(rng.standard_normal((n, hw, hw, c)), jnp.float32)
    wt = jnp.asarray(rng.standard_normal((r, r, c, k)) * 0.1, jnp.float32)
    out = conv2d_direct(x, wt, stride=stride, padding=pad, rb_p=rb_p,
                        interpret=True)
    exp = ref.conv2d(x, wt, stride=stride, padding=pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-3, atol=1e-3)


# -- tiled-input path (row-band streaming, C_b accumulation) ----------------

TILED_CASES = [
    # n, h, w, c, k, r, stride, pad, rb_p, c_blk, order
    (2, 8, 8, 16, 16, 3, 1, 1, 4, 8, "nkpc"),     # C_b accumulation
    (1, 9, 9, 8, 16, 3, 1, 1, 4, 8, "npkc"),      # P tail, Q off the tile
    (2, 16, 16, 8, 8, 3, 2, 1, 4, 8, "knpc"),     # stride 2 phase planes
    (1, 14, 14, 16, 32, 1, 1, 0, 7, 8, "pknc"),   # 1x1, every axis free
    (1, 12, 12, 8, 8, 5, 1, 2, 3, 8, "nkpc"),     # 5x5 halo
    (1, 24, 24, 8, 16, 7, 2, 3, 4, 8, "npkc"),    # 7x7 stride-2 halo
]


@pytest.mark.parametrize("case", TILED_CASES)
def test_conv2d_tiled_blocking_sweep(rng, case):
    """Every freed axis — c_blk, loop order — stays bit-exact vs the
    oracle, including the ceil-div row tail and rows off the sublane tile."""
    n, h, w, c, k, r, stride, pad, rb_p, c_blk, order = case
    x, wt = _data(rng, n, h, w, c, k, r)
    out = conv2d_direct(x, wt, stride=stride, padding=pad, rb_p=rb_p,
                        c_blk=c_blk, order=order, interpret=True)
    exp = ref.conv2d(x, wt, stride=stride, padding=pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_tail_with_fused_residual(rng):
    """Ceil-div P tail + full fused epilogue: the residual BlockSpec reads a
    (1, rb_p, Q', k_blk) block at the tail, so p % rb_p != 0 with
    relu+residual must stay bit-exact (pallas masks the out-of-range rows)."""
    n, h, c, k, r, pad = 1, 9, 8, 16, 3, 1
    rb_p = 4                                    # p = 9 -> tail block of 1
    x, wt = _data(rng, n, h, h, c, k, r)
    res = jnp.asarray(rng.standard_normal((n, 9, 9, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(k), jnp.float32)
    exp = ref.conv2d_fused(x, wt, stride=1, padding=pad, bias=b,
                           residual=res, relu=True)
    for kwargs in (dict(),                          # C unblocked, full row
                   dict(c_blk=8),                   # C_b passes
                   dict(c_blk=8, order="npkc")):
        out = conv2d_direct(x, wt, stride=1, padding=pad, bias=b,
                            residual=res, relu=True, rb_p=rb_p,
                            interpret=True, **kwargs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-4, atol=1e-4)


def test_conv2d_whole_plane_legacy_path(rng):
    """The A/B knob: the legacy whole-plane kernel must agree bit-for-bit
    with the tiled default."""
    x, wt = _data(rng, 2, 9, 9, 8, 16, 3)
    tiled = conv2d_direct(x, wt, stride=1, padding=1, rb_p=4, interpret=True)
    whole = conv2d_direct(x, wt, stride=1, padding=1, rb_p=4,
                          whole_plane=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(whole))


def test_resnet_stem_tiled_regression(rng):
    """ResNet conv1 (224x224 input, 7x7 stride-2 -> 112x112): the padded
    input plane exceeds a small VMEM budget on the whole-plane path — the
    shape only runs blocked.  Pin bit-exactness of the tiled kernel and
    H-independence of its working set."""
    sh = STEM_CONV
    small_budget = 1 << 20            # the CI kernel-tiling smoke budget
    blk = conv_blocking_analytic(
        h=sh["h"], w=sh["w"], c=sh["c"], k=sh["k"], r=sh["r"], s=sh["s"],
        stride=sh["stride"], padding=sh["padding"], vmem_budget=small_budget)

    def ws(shape, whole):
        q = out_dim(shape["w"], shape["s"], shape["stride"],
                    shape["padding"])
        # with a fixed (rb_p, c_blk) band of full rows the tiled working
        # set must not see the image height at all
        return conv_working_set(
            h=shape["h"], w=shape["w"], c=shape["c"], k_blk=blk.k_blk,
            r=shape["r"], s=shape["s"], q=q, rb_p=blk.rb_p,
            padding=shape["padding"], stride=shape["stride"],
            c_blk=None if whole else blk.c_blk, whole_plane=whole)

    assert ws(STEM_CONV, whole=True) > small_budget        # legacy: too big
    assert ws(STEM_CONV, whole=False) <= small_budget      # tiled: fits
    # tiled working set is independent of the image height (same band)
    short = dict(STEM_CONV, h=STEM_CONV_HALF["h"])
    assert ws(STEM_CONV, whole=False) == ws(short, whole=False)
    assert ws(STEM_CONV_HALF, whole=True) < ws(STEM_CONV, whole=True)

    x, wt = _data(rng, sh["n"], sh["h"], sh["w"], sh["c"], sh["k"], sh["r"])
    out = conv2d_direct(x, wt, stride=sh["stride"], padding=sh["padding"],
                        rb_p=blk.rb_p, c_blk=sh["c"], interpret=True)
    exp = ref.conv2d(x, wt, stride=sh["stride"], padding=sh["padding"])
    assert out.shape == (1, 112, 112, sh["k"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-3, atol=1e-3)


# -- tiled update pass (band streaming, C blocking, ceil-div tails) ----------

TILED_WU_CASES = [
    # n, h, w, c, k, r, stride, pad, b_p, c_blk
    (2, 8, 8, 16, 16, 3, 1, 1, 4, 8),      # C_b accumulation
    (2, 9, 9, 8, 16, 3, 1, 1, 4, 8),       # P tail, Q off the tile
    (1, 16, 16, 16, 8, 3, 2, 1, 3, 8),     # stride 2 + non-divisor tail
    (1, 12, 12, 8, 8, 5, 1, 2, 5, None),   # 5x5 halo + tail
    (1, 24, 24, 8, 16, 7, 2, 3, 4, 8),     # 7x7 stride-2 halo
    (1, 14, 14, 16, 32, 1, 1, 0, 7, 8),    # 1x1, every axis free
]


@pytest.mark.parametrize("case", TILED_WU_CASES)
def test_conv2d_wu_tiled_blocking_sweep(rng, case):
    """The band-streamed update pass: every freed axis — c_blk and the
    ceil-div P tail over a zero-padded dO — stays correct vs the VJP
    oracle.  No divisibility of P is required any more."""
    n, h, w, c, k, r, stride, pad, bp, cb = case
    x, _ = _data(rng, n, h, w, c, k, r)
    p = (h + 2 * pad - r) // stride + 1
    do = jnp.asarray(rng.standard_normal((n, p, p, k)), jnp.float32)
    out = conv2d_wu(x, do, stride=stride, padding=pad, filter_rs=(r, r),
                    b_p=bp, c_blk=cb, whole_plane=False,
                    interpret=True)
    exp = ref.conv2d_bwd_weights(x, do, stride=stride, padding=pad,
                                 filter_rs=(r, r))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-3, atol=1e-3)


def test_conv2d_wu_whole_plane_legacy_path(rng):
    """The A/B knob: on a divisor-friendly layer the legacy resident-plane
    update kernel must agree bit-for-bit with the tiled default."""
    x, _ = _data(rng, 2, 8, 8, 8, 16, 3)
    do = jnp.asarray(rng.standard_normal((2, 8, 8, 16)), jnp.float32)
    kw = dict(stride=1, padding=1, filter_rs=(3, 3), b_p=4, interpret=True)
    tiled = conv2d_wu(x, do, whole_plane=False, **kw)
    whole = conv2d_wu(x, do, whole_plane=True, **kw)
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(whole))


def test_wu_stem_tiled_regression(rng):
    """The training-pass acceptance bar: the update pass of the 224x224 7x7
    stride-2 stem — un-schedulable for the legacy resident-plane kernel
    under a 1 MiB budget, and P=112 has awkward divisors — runs band-
    streamed with a working set independent of H."""
    sh = STEM_CONV
    p = out_dim(sh["h"], sh["r"], sh["stride"], sh["padding"])
    small_budget = 1 << 20            # the CI training-pass smoke budget
    blk = conv_blocking_analytic(
        h=sh["h"], w=sh["w"], c=sh["c"], k=sh["k"], r=sh["r"], s=sh["s"],
        stride=sh["stride"], padding=sh["padding"], kind="wu",
        vmem_budget=small_budget)

    def ws(shape, whole):
        q = out_dim(shape["w"], shape["s"], shape["stride"],
                    shape["padding"])
        return conv_working_set(
            h=shape["h"], w=shape["w"], c=shape["c"], k_blk=blk.k_blk,
            r=shape["r"], s=shape["s"], q=q, rb_p=blk.rb_p,
            padding=shape["padding"], stride=shape["stride"],
            c_blk=None if whole else blk.c_blk, whole_plane=whole,
            kind="wu")

    assert ws(STEM_CONV, whole=True) > small_budget        # legacy: too big
    assert ws(STEM_CONV, whole=False) <= small_budget      # tiled: fits
    # tiled working set is independent of the image height (same band)
    short = dict(STEM_CONV, h=STEM_CONV_HALF["h"])
    assert ws(STEM_CONV, whole=False) == ws(short, whole=False)
    assert ws(STEM_CONV_HALF, whole=True) < ws(STEM_CONV, whole=True)

    x, _ = _data(rng, sh["n"], sh["h"], sh["w"], sh["c"], sh["k"], sh["r"])
    do = jnp.asarray(rng.standard_normal((sh["n"], p, p, sh["k"])),
                     jnp.float32)
    out = conv2d_wu(x, do, stride=sh["stride"], padding=sh["padding"],
                    filter_rs=(sh["r"], sh["s"]), b_p=blk.rb_p,
                    c_blk=sh["c"], whole_plane=False, interpret=True)
    exp = ref.conv2d_bwd_weights(x, do, stride=sh["stride"],
                                 padding=sh["padding"],
                                 filter_rs=(sh["r"], sh["s"]))
    assert out.shape == (7, 7, sh["c"], sh["k"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-2, atol=1e-2)


def test_pad_input_no_overpad_stride2():
    """pad_input must stop at the last row the grid can touch: for stride > 1
    the symmetric bottom pad used to inflate the plane past it."""
    h = w = p = 12
    r, stride, padding = 3, 2, 1
    p_out = (h + 2 * padding - r) // stride + 1           # 6
    for rb_p in (2, 3, 6):                                 # rb_p | p cases
        x = jnp.zeros((1, h, w, 8), jnp.float32)
        xp = pad_input(x, padding=padding, stride=stride, rb_p=rb_p, r=r,
                       p=p_out)
        rows_needed = (int(np.ceil(p_out / rb_p)) * rb_p - 1) * stride + r
        assert xp.shape[1] == max(rows_needed, h + padding)
        assert xp.shape[1] < h + 2 * padding              # strictly tighter
    # ceil-div tail still covered: rb_p = 4 -> 2 blocks of 4 rows over p=6
    xp = pad_input(jnp.zeros((1, h, w, 8), jnp.float32), padding=padding,
                   stride=stride, rb_p=4, r=r, p=p_out)
    assert xp.shape[1] >= (2 * 4 - 1) * stride + r
