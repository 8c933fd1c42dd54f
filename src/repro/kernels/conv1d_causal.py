"""Depthwise causal conv1d Pallas kernel (Mamba mixer — the one convolution
on an assigned-architecture hot path; see DESIGN.md §5).

The paper's direct-conv recipe degenerates nicely here: feature maps are the
lane dimension (D innermost), the filter loop (KW taps, typically 4) is the
statically-unrolled small-kernel chain, and the "register block" is a
(L, D_blk) tile.  Left-padding happens once outside the kernel so in-kernel
reads are static slices (the boundary-variant problem of §II-H vanishes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, b_ref, o_ref, *, kw: int, l: int, act: str):
    d_blk = x_ref.shape[-1]
    acc = jnp.zeros((l, d_blk), dtype=jnp.float32)
    for i in range(kw):
        acc += x_ref[0, pl.dslice(i, l), :].astype(jnp.float32) * \
            w_ref[i, :].astype(jnp.float32)
    acc += b_ref[0, :].astype(jnp.float32)
    if act == "silu":
        acc = jax.nn.silu(acc)
    o_ref[0] = acc.astype(o_ref.dtype)


def conv1d_causal(x, w, *, bias=None, act: str = "silu", d_blk: int = 128,
                  interpret: bool = False):
    """x: (B,L,D), w: (KW,D) depthwise causal -> (B,L,D)."""
    b, l, d = x.shape
    kw, _ = w.shape
    d_blk = min(d_blk, d)
    assert d % d_blk == 0
    if bias is None:
        bias = jnp.zeros((d,), x.dtype)
    xp = jnp.pad(x, ((0, 0), (kw - 1, 0), (0, 0)))

    kern = functools.partial(_kernel, kw=kw, l=l, act=act)
    return pl.pallas_call(
        kern,
        grid=(b, d // d_blk),
        in_specs=[
            pl.BlockSpec((1, l + kw - 1, d_blk), lambda bi, di: (bi, 0, di)),
            pl.BlockSpec((kw, d_blk), lambda bi, di: (0, di)),
            pl.BlockSpec((1, d_blk), lambda bi, di: (0, di)),
        ],
        out_specs=pl.BlockSpec((1, l, d_blk), lambda bi, di: (bi, 0, di)),
        out_shape=jax.ShapeDtypeStruct((b, l, d), x.dtype),
        interpret=interpret,
        name="conv1d_causal",
    )(xp, w, bias.reshape(1, d))
