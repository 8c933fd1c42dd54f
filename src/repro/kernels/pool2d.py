"""Max-pooling Pallas kernel with the §II-G fusion story: pooling is one of
the bandwidth-bound L() operators the paper fuses after convolutions.  The
kernel reads the conv output tile (still organized in the blocked layout)
and reduces the window in VREGs — one pass over the data.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, o_ref, *, window: int, stride: int, rb_p: int, q_out: int):
    pb = pl.program_id(2)
    c = x_ref.shape[-1]
    row0 = pb * rb_p * stride
    out = jnp.full((rb_p * q_out, c), -jnp.inf, dtype=jnp.float32)
    for wr in range(window):
        for wc in range(window):
            xs = x_ref[0, pl.dslice(row0 + wr, rb_p, stride),
                       pl.dslice(wc, q_out, stride), :]
            out = jnp.maximum(out, xs.reshape(rb_p * q_out, c)
                              .astype(jnp.float32))
    o_ref[0] = out.reshape(rb_p, q_out, c).astype(o_ref.dtype)


def maxpool2d(x, *, window: int = 3, stride: int = 2, padding: int = 1,
              rb_p: int = 8, interpret: bool = False):
    """x: (N,H,W,C) -> (N,P,Q,C) max pooling (paper's ResNet stem pool)."""
    n, h, w, c = x.shape
    p = (h + 2 * padding - window) // stride + 1
    q = (w + 2 * padding - window) // stride + 1
    rb_p = min(rb_p, p)
    pad_rows = max(((math.ceil(p / rb_p) * rb_p - 1) * stride + window)
                   - (h + 2 * padding), 0) + padding
    xp = jnp.pad(x, ((0, 0), (padding, pad_rows), (padding, padding),
                     (0, 0)), constant_values=-jnp.inf)
    hp, wp = xp.shape[1], xp.shape[2]
    grid = (n, 1, math.ceil(p / rb_p))

    kern = functools.partial(_kernel, window=window, stride=stride,
                             rb_p=rb_p, q_out=q)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((1, hp, wp, c),
                               lambda ni, ki, pi: (ni, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, rb_p, q, c),
                               lambda ni, ki, pi: (ni, pi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, p, q, c), x.dtype),
        interpret=interpret,
        name="maxpool",
    )(xp)
