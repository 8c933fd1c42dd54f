"""Replay engine for kernel streams (paper §II-H, Algorithm 5) as a single
scalar-prefetch-driven Pallas kernel.

The grid is the flat schedule; BlockSpec index_maps read the scalar-prefetched
offset streams (i_off / w_off / o_off of Fig. 1), and the per-step flag word
selects zero-init / epilogue / fused-L() — so boundary variants and fusion
cost zero branches in the steady state, exactly the paper's claim.  Unlike
``conv2d_direct`` this variant blocks the input-feature dimension C_b too, so
one output tile is *revisited* across C-block steps and the fused epilogue
really must fire only on the last visit (the Algorithm-4 ``c_b == C_b-1``
condition, moved into the schedule at dryrun time).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import backend as be
from repro.core.blocking import conv_blocking
from repro.core.streams import (FLAG_EPILOGUE, FLAG_INIT, FLAG_RELU,
                                ConvSchedule, build_conv_schedule)
from repro.kernels.conv2d_direct import pad_input


def _kernel(flags_ref, n_s, kb_s, pb_s, cb_s,   # scalar-prefetched streams
            x_ref, w_ref, bias_ref, o_ref, *, rb_p: int, q: int,
            stride: int, r: int, s: int, accum_dtype):
    i = pl.program_id(0)
    flag = flags_ref[i]

    @pl.when((flag & FLAG_INIT) != 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    c_blk = x_ref.shape[-1]
    k_blk = w_ref.shape[-1]
    acc = jnp.zeros((rb_p * q, k_blk), dtype=accum_dtype)
    for rr in range(r):
        for ss in range(s):
            pb = pb_s[i]
            xs = x_ref[0, pl.dslice(pb * rb_p * stride + rr, rb_p, stride),
                       pl.dslice(ss, q, stride), :]
            a = xs.reshape(rb_p * q, c_blk)
            acc += jax.lax.dot(a.astype(accum_dtype),
                               w_ref[rr, ss].astype(accum_dtype),
                               preferred_element_type=accum_dtype)
    o_ref[0] += acc.reshape(rb_p, q, k_blk)

    @pl.when((flag & FLAG_EPILOGUE) != 0)
    def _epilogue():
        out = o_ref[0] + bias_ref[0].astype(accum_dtype)
        out = jnp.where((flag & FLAG_RELU) != 0, jnp.maximum(out, 0), out)
        o_ref[0] = out


def conv2d_streams(x, w, *, schedule: ConvSchedule, stride: int = 1,
                   padding: int = 0, bias=None, rb_p: int = 8,
                   k_blk: int | None = None, c_blk: int | None = None,
                   accum_dtype=jnp.float32, interpret: bool = False):
    """Replay `schedule` over x (N,H,W,C), w (R,S,C,K) -> (N,P,Q,K) f32.

    Output stays f32 (the accumulator tile lives in the output block across
    C-block revisits — same as the paper's int16 kernels keeping 32-bit
    outputs); callers cast.
    """
    n, h, wdt, c = x.shape
    r, s, _, k = w.shape
    p = (h + 2 * padding - r) // stride + 1
    q = (wdt + 2 * padding - s) // stride + 1
    rb_p = min(rb_p, p)
    k_blk = k_blk or min(k, 128)
    c_blk = c_blk or min(c, 128)
    assert k % k_blk == 0 and c % c_blk == 0
    n_g, k_b, p_b, c_b = schedule.grid
    assert (n_g, k_b, p_b, c_b) == (n, k // k_blk, math.ceil(p / rb_p),
                                    c // c_blk), "schedule/layer mismatch"
    if bias is None:
        bias = jnp.zeros((k,), x.dtype)

    xp = pad_input(x, padding=padding, stride=stride, rb_p=rb_p, r=r, p=p)
    hp, wp = xp.shape[1], xp.shape[2]

    kern = functools.partial(_kernel, rb_p=rb_p, q=q, stride=stride, r=r,
                             s=s, accum_dtype=accum_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(len(schedule),),
        in_specs=[
            pl.BlockSpec((1, hp, wp, c_blk),
                         lambda i, fl, ns, ks, ps, cs: (ns[i], 0, 0, cs[i])),
            pl.BlockSpec((r, s, c_blk, k_blk),
                         lambda i, fl, ns, ks, ps, cs: (0, 0, cs[i], ks[i])),
            pl.BlockSpec((1, k_blk),
                         lambda i, fl, ns, ks, ps, cs: (0, ks[i])),
        ],
        out_specs=pl.BlockSpec((1, rb_p, q, k_blk),
                               lambda i, fl, ns, ks, ps, cs: (ns[i], ps[i], 0, ks[i])),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, p, q, k), accum_dtype),
        interpret=interpret,
        name="conv_streams",
    )(jnp.asarray(schedule.flags), jnp.asarray(schedule.n_ids),
      jnp.asarray(schedule.kb_ids), jnp.asarray(schedule.pb_ids),
      jnp.asarray(schedule.cb_ids), xp, w, bias.reshape(1, k))


def conv2d_streams_auto(x, w, *, stride=1, padding=0, bias=None, relu=False,
                        rb_p=None, k_blk=None, c_blk=None, order=None,
                        blocking=None, autotune=None, interpret=False):
    """Dryrun + replay in one call (the common path).

    Knob precedence: explicitly passed rb_p/k_blk/c_blk/order always win;
    `blocking` (a ``core.blocking.ConvBlocking``) fills whatever the caller
    left unset; the seed defaults (rb_p=8, 128-lane feature blocks, "nkpc")
    fill the rest.  When the caller pins *nothing* and autotuning is on
    (`autotune` kwarg or the ``repro.backend`` knob), the tuned "streams"
    blocking supplies the knobs *and* the dryrun loop order — the schedule
    itself is shape-specialized, not just the tile sizes.
    """
    n, h, wdt, c = x.shape
    r, s, _, k = w.shape
    p = (h + 2 * padding - r) // stride + 1
    untouched = rb_p is None and k_blk is None and c_blk is None and order is None
    if blocking is None and untouched and be.resolve_autotune(autotune) != "off":
        blocking = conv_blocking(
            h=h, w=wdt, c=c, k=k, r=r, s=s, stride=stride, padding=padding,
            dtype_bytes=x.dtype.itemsize, autotune=autotune, kind="streams",
            backend="interpret" if interpret else "pallas", minibatch=n)
    if blocking is not None:    # fills only the knobs the caller left unset
        rb_p = blocking.rb_p if rb_p is None else rb_p
        k_blk = blocking.k_blk if k_blk is None else k_blk
        c_blk = blocking.c_blk if c_blk is None else c_blk
        order = blocking.order if order is None else order
    rb_p = 8 if rb_p is None else rb_p
    order = order or "nkpc"
    rb_p_eff = min(rb_p, p)
    k_blk = k_blk or min(k, 128)
    c_blk = c_blk or min(c, 128)
    sched = build_conv_schedule(
        n=n, k_b=k // k_blk, p_b=math.ceil(p / rb_p_eff), c_b=c // c_blk,
        order=order, relu=relu)
    out = conv2d_streams(x, w, schedule=sched, stride=stride, padding=padding,
                         bias=bias, rb_p=rb_p, k_blk=k_blk, c_blk=c_blk,
                         interpret=interpret)
    return out.astype(x.dtype)
