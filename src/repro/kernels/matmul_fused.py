"""Fused blocked matmul Pallas kernel — the paper's small-GEMM + fused-L()
recipe applied to the LM hot path (QKV/MLP projections).

Grid (M_b, N_b, K_b) with a VMEM f32 accumulator tile; the epilogue
(bias / activation / residual) fires on the last K step, while the tile is
hot in VMEM — the §II-G fusion argument, verbatim.  Block shapes are chosen
by ``core.blocking`` to be MXU-aligned (multiples of (8,128)) and to fit the
VMEM working set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ACTS = {
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


def _kernel(a_ref, b_ref, *refs, act: str, has_bias: bool, has_res: bool,
            n_k: int, out_dtype):
    idx = 0
    bias_ref = res_ref = None
    if has_bias:
        bias_ref = refs[idx]; idx += 1
    if has_res:
        res_ref = refs[idx]; idx += 1
    o_ref = refs[idx]
    acc_ref = refs[idx + 1]

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(a_ref[...].astype(jnp.float32),
                                b_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _epilogue():
        out = acc_ref[...]
        if has_bias:
            out = out + bias_ref[0].astype(jnp.float32)
        if has_res:
            out = out + res_ref[...].astype(jnp.float32)
        out = _ACTS[act](out)
        o_ref[...] = out.astype(out_dtype)


def matmul_fused(a, b, *, bias=None, act: str = "none", residual=None,
                 bm: int = 128, bn: int = 128, bk: int = 512,
                 interpret: bool = False):
    """act(a @ b + bias [+ residual]).  a: (M,K), b: (K,N) -> (M,N)."""
    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, bm, bn, bk)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
        pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
    ]
    args = [a, b]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)))
        args.append(bias.reshape(1, n))
    if residual is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)))
        args.append(residual)

    kern = functools.partial(_kernel, act=act, has_bias=bias is not None,
                             has_res=residual is not None, n_k=n_k,
                             out_dtype=a.dtype)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="matmul_fused",
    )(*args)
