"""Blocked (flash-style) causal attention Pallas kernel.

Not a paper contribution per se, but the paper's blocking discipline applied
to the LM hot path: the KV sweep is the in-grid accumulation loop, the
(BQ, Dh) output tile + running (m, l) softmax statistics live in VMEM
scratch, and fully-masked KV blocks are skipped with ``pl.when`` (the
schedule-level analog of the §II-H boundary variants).  GQA is handled by
mapping each query-head grid step onto its KV head via index_map arithmetic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, bq: int, bk: int, n_kb: int,
            out_dtype):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                    # (bk, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_prev = m_ref[...]                                 # (bq, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_cur
        v = v_ref[0].astype(jnp.float32)                    # (bk, dh)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)

    if causal:
        # Skip fully-masked blocks (strictly above the diagonal) — the
        # schedule-level analog of the §II-H boundary variants.
        pl.when(ki * bk <= qi * bq + bq - 1)(_body)
    else:
        _body()

    @pl.when(ki == n_kb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(out_dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    bq: int = 128, bk: int = 128, interpret: bool = False):
    """q: (B,Hq,L,Dh), k/v: (B,Hkv,L,Dh) -> (B,Hq,L,Dh).  GQA via head map."""
    b, hq, l, dh = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0
    rep = hq // hkv
    if scale is None:
        scale = dh ** -0.5
    bq = min(bq, l)
    bk = min(bk, l)
    assert l % bq == 0 and l % bk == 0
    n_kb = l // bk
    grid = (b * hq, l // bq, n_kb)

    qr = q.reshape(b * hq, l, dh)
    kr = k.reshape(b * hkv, l, dh)
    vr = v.reshape(b * hkv, l, dh)

    kern = functools.partial(_kernel, scale=scale, causal=causal, bq=bq,
                             bk=bk, n_kb=n_kb, out_dtype=q.dtype)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, qi, ki: (bh // rep, ki, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, qi, ki: (bh // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, l, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="attention",
    )(qr, kr, vr)
    return out.reshape(b, hq, l, dh)
