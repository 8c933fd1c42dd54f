"""Quantized direct-convolution kernel — paper §II-K as a *kernel*, not
just weight storage.

The paper's 4VNNIW path takes int16 inputs, multiplies into int32
accumulators, and manages accumulation-chain length to avoid overflow; the
output stays 32-bit (so output-side bandwidth does not improve — their
measured 1.6x, not 2x).  TPU analog: int8 activations and weights feed the
MXU's 8-bit path, accumulate in int32, and the per-channel scales are
applied once in the epilogue.  Overflow management maps to the int32
accumulator width: the worst-case chain here is R*S*C * 127*127 which for
R=S=3, C=2048 is ~3e8 << 2^31 — checked statically below (the paper had to
*restrict* chain length for int16 accumulation into 32 bits; int8->int32
gives us the headroom for free, which is exactly why serving stacks picked
int8).

The kernel is tiled exactly like ``conv2d_direct``: a (N, K_b, P_b, C_b)
grid streaming only the halo'd row band of the stride-phase planes per step
(``conv2d_direct.band_spec``), with an *int32* VMEM scratch accumulated
across C-block visits (init on the first visit, dequant + fused §II-G
epilogue + store on the last).  int8 bands are 4x smaller than f32 ones, so
``core.blocking.conv_working_set(kind="q8")`` lets RB_P grow ~4x under the
same VMEM budget.  The two per-channel scales are premultiplied into one
(1, K) f32 ``deq`` input before launch, so the epilogue arithmetic — and
therefore the output bits — are identical between the tiled and
whole-plane kernels: int32 accumulation is associative, and both paths
compute ``acc.astype(f32) * deq`` with the same single rounding.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.conv2d_direct import (FuseSpec, _epilogue, _grid_layout,
                                         _unpack_fuse_refs, band_spec,
                                         band_tap, compiler_params, pad_input,
                                         phase_planes, tile_cols)


def _check_overflow(r: int, s: int, c: int) -> None:
    # static overflow check (the §II-K chain-length discipline)
    assert r * s * c * 127 * 127 < 2 ** 31, "int32 accumulator overflow"


def _kernel_q8_tiled(x_ref, w_ref, deq_ref, *refs, fuse: FuseSpec, rb_p: int,
                     cols: int, stride: int, r: int, s: int, c_axis: int,
                     out_dtype):
    """One microkernel invocation on a streamed int8 row band: accumulate one
    C-block into the int32 scratch; init on the first visit, dequantize +
    fused epilogue + store on the last (FLAG_INIT/FLAG_EPILOGUE, static)."""
    refs, acc_ref = refs[:-1], refs[-1]
    bias_ref, scale_ref, shift_ref, res_ref, o_ref = \
        _unpack_fuse_refs(refs, fuse)

    ci = pl.program_id(c_axis)
    c_b = pl.num_programs(c_axis)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_blk = w_ref.shape[-1]
    acc = jnp.zeros((rb_p * cols, k_blk), dtype=jnp.int32)
    for rr in range(r):
        for ss in range(s):
            a = band_tap(x_ref, rr, ss, rows=rb_p, cols=cols, stride=stride)
            # int8 x int8 -> int32 accumulate (the 4VNNIW analog)
            acc += jax.lax.dot(a, w_ref[rr, ss, :, :],
                               preferred_element_type=jnp.int32)
    acc_ref[...] += acc

    @pl.when(ci == c_b - 1)
    def _finish():
        # dequantize once, while the tile is hot in VMEM, then the f32
        # §II-G chain — bit-identical to the whole-plane kernel's epilogue
        out = acc_ref[...].astype(jnp.float32) * deq_ref[0, :]
        out = _epilogue(out, fuse, bias_ref, scale_ref, shift_ref, res_ref,
                        rb_p * cols, k_blk, jnp.float32)
        o_ref[0] = out.reshape(rb_p, cols, k_blk).astype(out_dtype)


def _kernel_q8_whole(x_ref, w_ref, deq_ref, *refs, fuse: FuseSpec, rb_p: int,
                     q: int, stride: int, r: int, s: int, p_axis: int,
                     out_dtype):
    """Legacy microkernel: whole padded int8 plane resident, row selection via
    the P-block program id (kept for A/B benchmarking vs the tiled path)."""
    bias_ref, scale_ref, shift_ref, res_ref, o_ref = \
        _unpack_fuse_refs(refs, fuse)

    pb = pl.program_id(p_axis)
    c = x_ref.shape[-1]
    k_blk = w_ref.shape[-1]
    acc = jnp.zeros((rb_p * q, k_blk), dtype=jnp.int32)
    row0 = pb * rb_p * stride
    for rr in range(r):
        for ss in range(s):
            xs = x_ref[0, pl.dslice(row0 + rr, rb_p, stride),
                       pl.dslice(ss, q, stride), :]
            a = xs.reshape(rb_p * q, c)
            acc += jax.lax.dot(a.astype(jnp.int32),
                               w_ref[rr, ss, :, :].astype(jnp.int32),
                               preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * deq_ref[0, :]
    out = _epilogue(out, fuse, bias_ref, scale_ref, shift_ref, res_ref,
                    rb_p * q, k_blk, jnp.float32)
    o_ref[0] = out.reshape(rb_p, q, k_blk).astype(out_dtype)


def conv2d_q8(x_q, w_q, *, x_scale, w_scale, stride: int = 1,
              padding: int = 0, bias=None, scale=None, shift=None,
              residual=None, relu: bool = False, rb_p: int = 8,
              k_blk: int | None = None, c_blk: int | None = None,
              order: str = "nkpc",
              whole_plane: bool | None = None, out_dtype=jnp.float32,
              interpret: bool = False):
    """Quantized direct conv fwd.  x_q: (N,H,W,C) int8; w_q: (R,S,C,K) int8;
    x_scale: scalar f32 per-tensor activation scale; w_scale: (K,) f32
    per-output-channel.  -> (N,P,Q,K) out_dtype (f32 by default — output
    bandwidth stays 32-bit, the paper's reason 1.6x != 4x).

    Blocking kwargs mirror ``conv2d_direct`` (`rb_p` full-row register block,
    `k_blk` MXU N-tile, `c_blk` C-block accumulated in int32 VMEM scratch,
    `order` the §II-C grid order); `whole_plane` selects the legacy untiled
    kernel (default: the ``repro.backend`` conv-tiling knob).  The optional
    bias / folded-BN scale+shift / residual / relu epilogue is applied in
    f32 *after* dequantization.
    """
    assert x_q.dtype == jnp.int8 and w_q.dtype == jnp.int8
    n, h, wdt, c = x_q.shape
    r, s, _, k = w_q.shape
    _check_overflow(r, s, c)
    p = (h + 2 * padding - r) // stride + 1
    q = (wdt + 2 * padding - s) // stride + 1
    rb_p = min(rb_p, p)
    k_blk = k_blk or min(k, 128)
    c_blk = c if c_blk in (None, 0) else c_blk
    assert k % k_blk == 0, (k, k_blk)
    assert c % c_blk == 0, (c, c_blk)
    if whole_plane is None:
        from repro import backend as be
        whole_plane = be.get_conv_tiling() == "whole"

    fuse = FuseSpec(bias=bias is not None, bn=scale is not None,
                    residual=residual is not None, relu=relu)
    if fuse.bn:
        assert shift is not None

    # premultiplied dequant scales: one (1, K) f32 row, identical math on
    # both kernel paths (tiled ≡ whole-plane bit-exactness depends on this)
    deq = (jnp.reshape(x_scale, ()).astype(jnp.float32)
           * w_scale.reshape(1, k).astype(jnp.float32))

    if whole_plane:
        return _conv2d_q8_whole_plane(
            x_q, w_q, deq, fuse=fuse, stride=stride, padding=padding,
            bias=bias, scale=scale, shift=shift, residual=residual,
            rb_p=rb_p, k_blk=k_blk, p=p, q=q, r=r, s=s, n=n, k=k, c=c,
            out_dtype=out_dtype, interpret=interpret)

    p_b = math.ceil(p / rb_p)
    k_b = k // k_blk
    c_b = c // c_blk

    cols = tile_cols(q, x_q.dtype.itemsize)
    xp = phase_planes(x_q, padding=padding, stride=stride, r=r, s=s, p=p,
                      rb_p=rb_p, cols=cols)
    grid, axis, semantics = _grid_layout(order, n=n, k_b=k_b, p_b=p_b,
                                         c_b=c_b)
    an, ak, ap, ac = (axis[d] for d in "nkpc")

    x_spec, band = band_spec(xp.shape, rb_p=rb_p, r=r, stride=stride,
                             c_blk=c_blk, n_axis=an, p_axis=ap, c_axis=ac)
    tile = (1, rb_p, cols, k_blk)
    in_specs = [
        x_spec,
        pl.BlockSpec((r, s, c_blk, k_blk), lambda *i: (0, 0, i[ac], i[ak])),
        pl.BlockSpec((1, k_blk), lambda *i: (0, i[ak])),     # deq scales
    ]
    args = [xp, w_q, deq]
    blocks = [(band, jnp.int8), ((r, s, c_blk, k_blk), jnp.int8),
              ((1, k_blk), jnp.float32), (tile, out_dtype)]
    for vec in (bias, scale, shift):
        if vec is not None:
            in_specs.append(pl.BlockSpec((1, k_blk), lambda *i: (0, i[ak])))
            args.append(vec.reshape(1, k))
            blocks.append(((1, k_blk), vec.dtype))
    if fuse.residual:
        in_specs.append(pl.BlockSpec(tile,
                                     lambda *i: (i[an], i[ap], 0, i[ak])))
        args.append(jnp.pad(residual, ((0, 0), (0, 0), (0, cols - q),
                                       (0, 0))))
        blocks.append((tile, residual.dtype))

    kern = functools.partial(_kernel_q8_tiled, fuse=fuse, rb_p=rb_p,
                             cols=cols, stride=stride, r=r, s=s, c_axis=ac,
                             out_dtype=out_dtype)
    acc = (rb_p * cols, k_blk)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(tile, lambda *i: (i[an], i[ap], 0, i[ak])),
        out_shape=jax.ShapeDtypeStruct((n, p, cols, k), out_dtype),
        scratch_shapes=[pltpu.VMEM(acc, jnp.int32)],
        compiler_params=compiler_params(semantics, blocks=blocks,
                                        scratch=[(acc, jnp.int32)]),
        interpret=interpret,
        name="conv_q8",
    )(*args)
    return out[:, :, :q] if cols != q else out


def _conv2d_q8_whole_plane(x_q, w_q, deq, *, fuse, stride, padding, bias,
                           scale, shift, residual, rb_p, k_blk, p, q, r, s,
                           n, k, c, out_dtype, interpret):
    """The pre-refactor kernel: whole padded int8 plane per image in VMEM,
    C and Q unblocked, grid (N, K_b, P_b)."""
    xp = pad_input(x_q, padding=padding, stride=stride, rb_p=rb_p, r=r, p=p)
    hp, wp = xp.shape[1], xp.shape[2]
    grid = (n, k // k_blk, math.ceil(p / rb_p))

    in_specs = [
        pl.BlockSpec((1, hp, wp, c), lambda ni, ki, pi: (ni, 0, 0, 0)),
        pl.BlockSpec((r, s, c, k_blk), lambda ni, ki, pi: (0, 0, 0, ki)),
        pl.BlockSpec((1, k_blk), lambda ni, ki, pi: (0, ki)),
    ]
    args = [xp, w_q, deq]
    if fuse.bias:
        in_specs.append(pl.BlockSpec((1, k_blk), lambda ni, ki, pi: (0, ki)))
        args.append(bias.reshape(1, k))
    if fuse.bn:
        in_specs.append(pl.BlockSpec((1, k_blk), lambda ni, ki, pi: (0, ki)))
        in_specs.append(pl.BlockSpec((1, k_blk), lambda ni, ki, pi: (0, ki)))
        args.extend([scale.reshape(1, k), shift.reshape(1, k)])
    if fuse.residual:
        in_specs.append(pl.BlockSpec((1, rb_p, q, k_blk),
                                     lambda ni, ki, pi: (ni, pi, 0, ki)))
        args.append(residual)

    kern = functools.partial(_kernel_q8_whole, fuse=fuse, rb_p=rb_p, q=q,
                             stride=stride, r=r, s=s, p_axis=2,
                             out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rb_p, q, k_blk),
                               lambda ni, ki, pi: (ni, pi, 0, ki)),
        out_shape=jax.ShapeDtypeStruct((n, p, q, k), out_dtype),
        interpret=interpret,
        name="conv_q8_whole",
    )(*args)


def quantize_conv_inputs(x, w):
    """Symmetric per-tensor activation scale + per-K-channel weight scales
    (the standard inference calibration)."""
    x_scale = jnp.max(jnp.abs(x)).astype(jnp.float32) / 127.0 + 1e-12
    x_q = jnp.clip(jnp.round(x / x_scale), -127, 127).astype(jnp.int8)
    w_scale = jnp.max(jnp.abs(w), axis=(0, 1, 2)).astype(jnp.float32) \
        / 127.0 + 1e-12
    w_q = jnp.clip(jnp.round(w / w_scale), -127, 127).astype(jnp.int8)
    return x_q, w_q, x_scale, w_scale
