"""Grouped matmul for MoE expert dispatch — kernel streams (paper §II-H)
applied to a second domain.

The routing step is the *dryrun*: it sorts tokens by expert into
capacity-padded groups whose starts are tile-aligned, and records a
``tile_eid`` stream (which expert's weight block each M-tile must use).  The
*replay* is one Pallas grid walking the tiles, with the expert-id stream
scalar-prefetched and consumed by the weight BlockSpec index_map — the exact
i_off/w_off/o_off structure of Fig. 1, with w_off = f(expert).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(eid_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int, out_dtype):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(x_ref[...].astype(jnp.float32),
                                w_ref[0].astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def moe_gmm(tokens, weights, tile_eid, *, bm: int = 128, bn: int = 128,
            bk: int = 512, interpret: bool = False):
    """tokens: (T, D) grouped by expert with tile-aligned group starts;
    weights: (E, D, F); tile_eid: (T//bm,) int32 expert id per M-tile.
    Returns (T, F)."""
    t, d = tokens.shape
    e, _, f = weights.shape
    bm, bn, bk = min(bm, t), min(bn, f), min(bk, d)
    assert t % bm == 0 and f % bn == 0 and d % bk == 0
    assert tile_eid.shape == (t // bm,)
    n_k = d // bk
    grid = (t // bm, f // bn, n_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki, eid: (mi, ki)),
            pl.BlockSpec((1, bk, bn), lambda mi, ni, ki, eid: (eid[mi], ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki, eid: (mi, ni)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kern = functools.partial(_kernel, n_k=n_k, out_dtype=tokens.dtype)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, f), tokens.dtype),
        interpret=interpret,
        name="moe_gmm",
    )(tile_eid, tokens, weights)


def route_dryrun(expert_of_token, num_experts: int, capacity: int, bm: int):
    """Dryrun/routing: build the gather indices + tile_eid stream.

    expert_of_token: (T,) int32.  Returns (gather_idx (E*cap,), tile_eid
    (E*cap//bm,), keep_mask (E*cap,)) — gather_idx[i] = source token for
    grouped row i (capacity-padded groups, group g occupies rows
    [g*cap, (g+1)*cap)).  Pure jnp: runs on device inside jit, the "dryrun
    once per routing step" of §II-H.
    """
    t = expert_of_token.shape[0]
    assert capacity % bm == 0
    # position of each token within its expert group
    onehot = jax.nn.one_hot(expert_of_token, num_experts, dtype=jnp.int32)
    pos_in_group = (jnp.cumsum(onehot, axis=0) - 1) * onehot  # (T, E)
    pos = pos_in_group.sum(axis=1)
    ok = pos < capacity
    dest = expert_of_token * capacity + pos                   # (T,)
    dest = jnp.where(ok, dest, t * 0 + num_experts * capacity)  # drop overflow
    gather_idx = jnp.zeros((num_experts * capacity + 1,), jnp.int32)
    gather_idx = gather_idx.at[dest].set(jnp.arange(t, dtype=jnp.int32) + 1)
    gather_idx = gather_idx[:-1]
    keep = gather_idx > 0
    gather_idx = jnp.maximum(gather_idx - 1, 0)
    tile_eid = jnp.repeat(jnp.arange(num_experts, dtype=jnp.int32),
                          capacity // bm)
    return gather_idx, tile_eid, keep
