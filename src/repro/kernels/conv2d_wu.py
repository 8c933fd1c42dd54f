"""Weight-gradient ("update pass") Pallas kernel — paper §II-J / Algorithm 9.

Each grid step computes the contribution of one (image, row-block, col-block)
to a (R, S, C_blk, K_blk) weight-gradient tile: for every static (r, s) it
performs the small GEMM  dW[r,s] += X_rs^T @ dO_tile  with M=C_blk, N=K_blk,
K=B_P*B_Q — the transpose-free analog of the paper's VLENxVLEN microkernel
(on the MXU the contraction runs over the pixel block, so the "register
blocking up to VLEN" becomes a (C_blk, K_blk) accumulator tile resident in
VMEM).

Tiled (default, the forward kernel's discipline brought to the update pass):

  * the grid is ``(K_b, C_b, N, P_b)`` — the dW tile index depends only on
    the two outer axes, so the Pallas revisiting-output pattern keeps one
    (r, s, C_blk, K_blk) f32 tile in VMEM across the whole (n, p) sweep,
    zero-initialized on the first visit of each (k, c) block pair;
  * the input streams only the halo'd row band each step reads, as stride-
    phase planes with element offsets on the untiled leading axis
    (``conv2d_direct.phase_planes`` / ``band_spec``) — the VMEM working set
    is independent of H (``core.blocking.conv_working_set``);
  * P uses a ceil-div grid: dO is zero-padded to whole row blocks and to the
    sublane-rounded row width, so padding pixels contribute nothing and
    every layer schedules — no ``P % b_p == 0`` restriction, the 224x224
    7x7 stem included.

The pre-refactor variant that shipped the **entire padded input plane per
image** into VMEM at every grid step (and could not block C or Q, and
required ``b_p | P``) is kept as ``whole_plane=True`` (knob:
``REPRO_CONV_TILING=whole`` / ``repro.backend.set_conv_tiling``) for A/B
benchmarking — ``benchmarks/bwd_wu_layers.py`` writes the comparison to
``BENCH_bwd_wu.json``.

The cross-chip part of the paper's §II-J reduction trade-off (shared dW vs.
per-thread copies) lives in ``core/wu_strategy.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.conv2d_direct import (band_spec, band_tap,
                                         compiler_params, pad_input,
                                         phase_planes, tile_cols)


def _kernel_tiled(x_ref, do_ref, o_ref, *, b_p: int, cols: int, stride: int,
                  r: int, s: int, accum_dtype):
    """One band-streamed update-pass step: accumulate this (n, p) block's
    contribution into the resident (r, s, C_blk, K_blk) dW tile."""
    first = jnp.logical_and(pl.program_id(2) == 0, pl.program_id(3) == 0)

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    k_blk = do_ref.shape[-1]
    g = do_ref[0].reshape(b_p * cols, k_blk).astype(accum_dtype)
    for rr in range(r):
        for ss in range(s):
            a = band_tap(x_ref, rr, ss, rows=b_p, cols=cols, stride=stride)
            # dW[r,s] += A^T @ G : contract over the pixel block.
            o_ref[rr, ss, :, :] += jax.lax.dot_general(
                a.astype(accum_dtype), g, (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=accum_dtype)


def _kernel_whole(x_ref, do_ref, o_ref, *, b_p: int, q: int, stride: int,
                  r: int, s: int, accum_dtype):
    """Legacy update-pass step: whole padded plane resident, row selection
    via the P-block program id (kept for A/B benchmarking)."""
    n_i = pl.program_id(1)
    pb = pl.program_id(2)

    @pl.when(jnp.logical_and(n_i == 0, pb == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    c = x_ref.shape[-1]
    k_blk = do_ref.shape[-1]
    g = do_ref[0].reshape(b_p * q, k_blk).astype(accum_dtype)
    row0 = pb * b_p * stride
    for rr in range(r):
        for ss in range(s):
            xs = x_ref[0, pl.dslice(row0 + rr, b_p, stride),
                       pl.dslice(ss, q, stride), :]           # (b_p, q, c)
            a = xs.reshape(b_p * q, c).astype(accum_dtype)
            upd = jax.lax.dot_general(
                a, g, (((0,), (0,)), ((), ())),
                preferred_element_type=accum_dtype)           # (c, k_blk)
            o_ref[rr, ss, :, :] += upd


def conv2d_wu(x, do, *, stride: int = 1, padding: int = 0,
              filter_rs: tuple[int, int], b_p: int = 7,
              k_blk: int | None = None, c_blk: int | None = None,
              accum_dtype=jnp.float32, whole_plane: bool | None = None,
              interpret: bool = False):
    """dW (R,S,C,K) from x (N,H,W,C) and dO (N,P,Q,K).

    ``b_p`` is the paper's B_P spatial blocking of the update pass (output
    rows per step, each the full row); ``k_blk``/``c_blk`` block the
    output/input features (``c_blk=None`` = unblocked).  The P grid is
    ceil-div over a zero-padded dO, so no divisibility of the spatial dims
    is required.  ``whole_plane`` selects the legacy resident-plane kernel
    (default: the ``repro.backend`` conv-tiling knob); that path keeps the
    seed's ``P % b_p == 0`` restriction.
    """
    n, h, wdt, c = x.shape
    _, p, q, k = do.shape
    r, s = filter_rs
    b_p = min(b_p, p)
    if k_blk is None:
        k_blk = min(k, 128)
    assert k % k_blk == 0, (k, k_blk)
    if whole_plane is None:
        from repro import backend as be
        whole_plane = be.get_conv_tiling() == "whole"

    if whole_plane:
        return _conv2d_wu_whole(x, do, stride=stride, padding=padding,
                                r=r, s=s, b_p=b_p, k_blk=k_blk,
                                accum_dtype=accum_dtype, interpret=interpret)

    c_blk = c if c_blk in (None, 0) else c_blk
    assert c % c_blk == 0, (c, c_blk)

    cols = tile_cols(q, x.dtype.itemsize)
    p_b = math.ceil(p / b_p)
    xp = phase_planes(x, padding=padding, stride=stride, r=r, s=s, p=p,
                      rb_p=b_p, cols=cols)
    # zero rows/cols past (P, Q): the padded pixels contribute nothing
    dop = jnp.pad(do, ((0, 0), (0, p_b * b_p - p), (0, cols - q), (0, 0)))
    # dW tile constant over the inner (n, p_b) sweep -> one VMEM-resident
    # accumulation pass per (k, c) block pair.
    grid = (k // k_blk, c // c_blk, n, p_b)
    x_spec, band = band_spec(xp.shape, rb_p=b_p, r=r, stride=stride,
                             c_blk=c_blk, n_axis=2, p_axis=3, c_axis=1)
    do_tile = (1, b_p, cols, k_blk)
    dw_tile = (r, s, c_blk, k_blk)

    kern = functools.partial(_kernel_tiled, b_p=b_p, cols=cols, stride=stride,
                             r=r, s=s, accum_dtype=accum_dtype)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[x_spec,
                  pl.BlockSpec(do_tile,
                               lambda ki, ci, ni, pi: (ni, pi, 0, ki))],
        out_specs=pl.BlockSpec(dw_tile, lambda ki, ci, ni, pi: (0, 0, ci, ki)),
        out_shape=jax.ShapeDtypeStruct((r, s, c, k), accum_dtype),
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary", "arbitrary"),
            blocks=[(band, x.dtype), (do_tile, do.dtype),
                    (dw_tile, accum_dtype)]),
        interpret=interpret,
        name="conv_wu",
    )(xp, dop)
    return out.astype(x.dtype)


def _conv2d_wu_whole(x, do, *, stride, padding, r, s, b_p, k_blk,
                     accum_dtype, interpret):
    """The pre-refactor kernel: whole padded plane per image in VMEM, C and Q
    unblocked, grid (K_b, N, P_b).  Working set scales with H*W*C and
    requires b_p | P."""
    n, h, wdt, c = x.shape
    _, p, q, k = do.shape
    assert p % b_p == 0, (p, b_p)

    xp = pad_input(x, padding=padding, stride=stride, rb_p=b_p, r=r, p=p)
    hp, wp = xp.shape[1], xp.shape[2]
    p_b = p // b_p
    k_b = k // k_blk
    grid = (k_b, n, p_b)   # output tile constant over the (n, p_b) sweep

    kern = functools.partial(_kernel_whole, b_p=b_p, q=q, stride=stride,
                             r=r, s=s, accum_dtype=accum_dtype)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda ki, ni, pi: (ni, 0, 0, 0)),
            pl.BlockSpec((1, b_p, q, k_blk), lambda ki, ni, pi: (ni, pi, 0, ki)),
        ],
        out_specs=pl.BlockSpec((r, s, c, k_blk),
                               lambda ki, ni, pi: (0, 0, 0, ki)),
        out_shape=jax.ShapeDtypeStruct((r, s, c, k), accum_dtype),
        interpret=interpret,
        name="conv_wu_whole",
    )(xp, do)
    return out.astype(x.dtype)
