"""Quantized conv kernel (§II-K as a kernel) + pooling kernel vs oracles.

The tiled-q8 sections pin the PR-7 retile: tiled ≡ whole-plane bit-exact
(int32 accumulation is associative and both paths share one premultiplied
f32 dequant epilogue), q8 vs f32 within the analytic quantization bound
R·S·C·sx·sw·127.25 per element, and the 224x224 7x7 stem schedulable under
a 1 MiB budget with an H·W-independent working set (the int8 blocking
dividend).  "Both backends" = interpret-mode eager AND under ``jax.jit``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.shapes import STEM_CONV, STEM_CONV_HALF
from repro.core.blocking import conv_blocking_analytic, conv_working_set
from repro.kernels import ref
from repro.kernels.conv2d_q8 import conv2d_q8, quantize_conv_inputs
from repro.kernels.pool2d import maxpool2d
from repro.tune.space import out_dim


@pytest.mark.parametrize("case", [
    (2, 8, 8, 8, 16, 3, 1, 1),
    (1, 9, 9, 8, 8, 3, 2, 1),
    (1, 8, 8, 16, 8, 1, 1, 0),
])
def test_conv2d_q8_close_to_f32(rng, case):
    n, h, w, c, k, r, stride, pad = case
    x = jnp.asarray(rng.standard_normal((n, h, w, c)), jnp.float32)
    wt = jnp.asarray(rng.standard_normal((r, r, c, k)) * 0.1, jnp.float32)
    xq, wq, sx, sw = quantize_conv_inputs(x, wt)
    out = conv2d_q8(xq, wq, x_scale=sx, w_scale=sw, stride=stride,
                    padding=pad, rb_p=4, interpret=True)
    exp = ref.conv2d(x, wt, stride=stride, padding=pad)
    # int8 quantization error bound: relative to output scale
    denom = float(jnp.abs(exp).max()) + 1e-6
    rel = float(jnp.abs(out - exp).max()) / denom
    assert rel < 0.05, rel


def test_conv2d_q8_int32_accumulation_exact(rng):
    """With integer-valued inputs the int8 path must be EXACT (the paper's
    claim that the quantized kernel computes the same chained GEMMs)."""
    n, h, c, k = 1, 6, 8, 8
    x = jnp.asarray(rng.integers(-3, 4, (n, h, h, c)), jnp.float32)
    wt = jnp.asarray(rng.integers(-3, 4, (3, 3, c, k)), jnp.float32)
    xq = x.astype(jnp.int8)
    wq = wt.astype(jnp.int8)
    out = conv2d_q8(xq, wq, x_scale=jnp.float32(1.0),
                    w_scale=jnp.ones((k,), jnp.float32), stride=1,
                    padding=1, rb_p=3, interpret=True)
    exp = ref.conv2d(x, wt, stride=1, padding=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_conv2d_q8_relu_epilogue(rng):
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 8)), jnp.float32)
    wt = jnp.asarray(rng.standard_normal((3, 3, 8, 8)) * 0.1, jnp.float32)
    xq, wq, sx, sw = quantize_conv_inputs(x, wt)
    out = conv2d_q8(xq, wq, x_scale=sx, w_scale=sw, stride=1, padding=1,
                    relu=True, rb_p=4, interpret=True)
    assert float(out.min()) >= 0.0


# -- tiled q8: band streaming, C/K blocking, ceil-div tails ------------------

TILED_Q8_CASES = [
    # n, h, w, c, k, r, stride, pad, blocking kwargs
    (2, 12, 12, 16, 16, 3, 1, 1, dict(rb_p=5, c_blk=8)),
    (1, 13, 13, 8, 16, 3, 2, 1, dict(rb_p=3, k_blk=8)),
    (1, 11, 11, 8, 24, 1, 1, 0, dict(rb_p=4, k_blk=8)),
    (1, 24, 24, 8, 16, 7, 2, 3, dict(rb_p=4, c_blk=8)),
    (1, 10, 10, 16, 8, 3, 1, 1, dict(rb_p=4, c_blk=8, order="npkc")),
]


def _q8_case_data(rng, case):
    n, h, w, c, k, r, stride, pad, kw = case
    x = jnp.asarray(rng.standard_normal((n, h, w, c)), jnp.float32)
    wt = jnp.asarray(rng.standard_normal((r, r, c, k)) * 0.1, jnp.float32)
    return (x, wt, quantize_conv_inputs(x, wt),
            dict(stride=stride, padding=pad), kw)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", TILED_Q8_CASES)
def test_conv2d_q8_tiled_equals_whole_plane_bitexact(rng, case, jit):
    """The retile must not change a single output bit: int32 accumulation
    is associative, and both kernels apply the identical premultiplied-deq
    f32 epilogue — on the eager interpret path AND under jax.jit."""
    x, wt, (xq, wq, sx, sw), conv_kw, blk_kw = _q8_case_data(rng, case)

    def run(whole):
        fn = lambda a, b: conv2d_q8(a, b, x_scale=sx, w_scale=sw, **conv_kw,
                                    **blk_kw, whole_plane=whole,
                                    interpret=True)
        return (jax.jit(fn) if jit else fn)(xq, wq)

    np.testing.assert_array_equal(np.asarray(run(False)),
                                  np.asarray(run(True)))


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", TILED_Q8_CASES)
def test_conv2d_q8_within_analytic_bound(rng, case, jit):
    """|q8 - f32| <= R*S*C*sx*sw_k*127.25 per element: each product term
    errs by at most |x̂||ŵ-w| + |w||x̂-x| <= 127*sx*sw (plus f32 slop),
    summed over the R*S*C accumulation chain."""
    x, wt, (xq, wq, sx, sw), conv_kw, blk_kw = _q8_case_data(rng, case)
    r, _, c, _ = wt.shape
    fn = lambda a, b: conv2d_q8(a, b, x_scale=sx, w_scale=sw, **conv_kw,
                                **blk_kw, whole_plane=False, interpret=True)
    out = np.asarray((jax.jit(fn) if jit else fn)(xq, wq))
    exp = np.asarray(ref.conv2d(x, wt, **conv_kw))
    bound = r * r * c * float(sx) * np.asarray(sw, np.float32) * 127.25
    assert np.all(np.abs(out - exp) <= bound), \
        float(np.max(np.abs(out - exp) / bound))


def test_q8_stem_tiled_under_pressure_budget(rng):
    """The serving acceptance bar: the 224x224 7x7 stride-2 stem is
    un-schedulable whole-plane under the 1 MiB CI budget, but the int8 band
    fits with room to grow — and the tiled working set is independent of H
    (same band for the 224- and 112-row image)."""
    sh = STEM_CONV
    small_budget = 1 << 20            # the CI q8-smoke budget
    blk = conv_blocking_analytic(
        h=sh["h"], w=sh["w"], c=sh["c"], k=sh["k"], r=sh["r"], s=sh["s"],
        stride=sh["stride"], padding=sh["padding"], dtype_bytes=1,
        kind="q8", vmem_budget=small_budget)

    def ws(shape, whole):
        q = out_dim(shape["w"], shape["s"], shape["stride"],
                    shape["padding"])
        return conv_working_set(
            h=shape["h"], w=shape["w"], c=shape["c"], k_blk=blk.k_blk,
            r=shape["r"], s=shape["s"], q=q, rb_p=blk.rb_p,
            padding=shape["padding"], stride=shape["stride"],
            c_blk=None if whole else blk.c_blk, whole_plane=whole,
            dtype_bytes=1, kind="q8")

    assert ws(STEM_CONV, whole=True) > small_budget        # legacy: too big
    assert ws(STEM_CONV, whole=False) <= small_budget      # tiled: fits
    short = dict(STEM_CONV, h=STEM_CONV_HALF["h"])           # same width
    assert ws(STEM_CONV, whole=False) == ws(short, whole=False)
    # the int8 band is 4x smaller than the f32 one, so the same budget
    # admits a taller row block than the f32 blocking gets
    f32_blk = conv_blocking_analytic(
        h=sh["h"], w=sh["w"], c=sh["c"], k=sh["k"], r=sh["r"], s=sh["s"],
        stride=sh["stride"], padding=sh["padding"], dtype_bytes=4,
        vmem_budget=small_budget)
    assert blk.rb_p >= f32_blk.rb_p

    x = jnp.asarray(rng.standard_normal(
        (sh["n"], sh["h"], sh["w"], sh["c"])), jnp.float32)
    wt = jnp.asarray(rng.standard_normal(
        (sh["r"], sh["s"], sh["c"], sh["k"])) * 0.1, jnp.float32)
    xq, wq, sx, sw = quantize_conv_inputs(x, wt)
    out = conv2d_q8(xq, wq, x_scale=sx, w_scale=sw, stride=sh["stride"],
                    padding=sh["padding"], rb_p=blk.rb_p,
                    c_blk=sh["c"], whole_plane=False, interpret=True)
    exp = np.asarray(ref.conv2d(x, wt, stride=sh["stride"],
                                padding=sh["padding"]))
    assert out.shape == (1, 112, 112, sh["k"])
    bound = sh["r"] * sh["s"] * sh["c"] * float(sx) \
        * np.asarray(sw, np.float32) * 127.25
    assert np.all(np.abs(np.asarray(out) - exp) <= bound)


@pytest.mark.parametrize("window,stride,pad,h", [
    (3, 2, 1, 12), (2, 2, 0, 8), (3, 1, 1, 7),
])
def test_maxpool2d_matches_lax(rng, window, stride, pad, h):
    x = jnp.asarray(rng.standard_normal((2, h, h, 8)), jnp.float32)
    out = maxpool2d(x, window=window, stride=stride, padding=pad, rb_p=3,
                    interpret=True)
    exp = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1),
        [(0, 0), (pad, pad), (pad, pad), (0, 0)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp))
