"""Blocking search space (paper §II-D: the per-shape specialization axis).

For a conv layer the tunable coordinates are exactly the knobs the Pallas
kernels expose:

  rb_p   output rows per microkernel (paper RB_P; MXU M-tile = rb_p*Q —
         the tiled kernels always compute full output rows, so RB_Q is
         no coordinate)
  k_blk  output-feature block (paper K_b; MXU N-tile, must divide K)
  c_blk  input-feature block (paper C_b accumulation; must divide C)
  order  grid/dryrun loop order over (N, K_b, P_b, C_b) (paper §II-C)

Feature blocks are the ones Mosaic accepts on the lane dim: 128 where it
divides the dim, else the whole dim.

``conv_candidates`` enumerates the feasible cross product — VMEM-budget
filtered, lane-aligned, divisibility-respecting — with the analytic heuristic
first, so it is both the cost-model prior and the seed the search can never
do worse than.  Kinds:

  "fwd"     conv2d_direct tiled forward: all four coordinates free (C-block
            accumulation + grid loop order)
  "bwd"     the backward-data dual conv — the same tiled forward kernel run
            on the transformed (dO, W') problem, so the same four coordinates
            are free; a separate kind so dual-shape winners get their own
            cache namespace (shapes come from ``duality.dual_conv_signatures``)
  "wu"      conv2d_wu band-streamed update pass: rb_p ceil-div (tails are
            zero-padded, no divisor constraint), c_blk free; the grid order
            is fixed (K_b, C_b, N, P_b), so order is not a coordinate
  "streams" conv2d_streams: rb_p/k_blk/c_blk/order free; whole-plane
  "q8"      conv2d_q8 tiled int8 forward: the same four coordinates as
            "fwd" but priced at 1 byte/element input-side (pass
            ``dtype_bytes=1``) — the 4x-smaller band admits taller rb_p
            under the same budget, so its candidate pool is genuinely
            different from the f32 space (own cache namespace)
"""
from __future__ import annotations

from repro.core.blocking import (VMEM_BUDGET, ConvBlocking, MatmulBlocking,
                                 aligned_block, conv_blocking_analytic,
                                 conv_working_set, divisors,
                                 matmul_blocking_analytic)

ORDERS = ("nkpc", "npkc", "knpc", "pknc")
MAX_CANDIDATES = 128


def out_dim(h: int, r: int, stride: int, padding: int) -> int:
    return (h + 2 * padding - r) // stride + 1


def _rb_candidates(p: int, *, require_divisor: bool) -> list[int]:
    if require_divisor:
        cands = divisors(p)
    else:
        # divisors (exact grids) + powers of two (ceil-div grids) + full P
        cands = set(divisors(p))
        rb = 1
        while rb < p:
            cands.add(rb)
            rb *= 2
        cands.add(p)
        cands = sorted(cands)
    if len(cands) > 12:             # spread-sample large spatial dims
        step = len(cands) / 12
        cands = sorted({cands[int(i * step)] for i in range(12)} | {cands[-1]})
    return cands


def conv_candidates(*, h: int, w: int, c: int, k: int, r: int, s: int,
                    stride: int, padding: int, dtype_bytes: int = 4,
                    kind: str = "fwd",
                    vmem_budget: int = VMEM_BUDGET) -> list[ConvBlocking]:
    """Feasible blockings, analytic seed first, deduplicated, budget-capped."""
    assert kind in ("fwd", "bwd", "wu", "streams", "q8"), kind
    p = out_dim(h, r, stride, padding)
    q = out_dim(w, s, stride, padding)
    whole = kind == "streams"       # only streams keeps the plane resident
    seed = conv_blocking_analytic(
        h=h, w=w, c=c, k=k, r=r, s=s, stride=stride, padding=padding,
        dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
        whole_plane=(True if whole else None), kind=kind)

    k_blocks = [aligned_block(k)]
    if kind == "wu":
        # band-streamed update pass: c_blk free, grid order fixed
        c_blocks = sorted({c, aligned_block(c)}, reverse=True)
        orders = (seed.order,)
    elif kind == "streams":
        c_blocks = [aligned_block(c)]
        orders = ORDERS
    else:
        # fwd/bwd/q8: full-C single-pass first, then the lane-tile C_b block
        c_blocks = sorted({c, aligned_block(c)}, reverse=True)
        orders = ORDERS
    rbs = _rb_candidates(max(p, 1), require_divisor=False)
    ws_kind = kind if kind in ("wu", "q8") else "fwd"

    pool: list[ConvBlocking] = []
    seen = {(seed.rb_p, seed.k_blk, seed.c_blk, seed.order)}
    for rb in rbs:
        for kb in k_blocks:
            for cb in c_blocks:
                ws = conv_working_set(
                    h=h, w=w, c=c, k_blk=kb, r=r, s=s, q=q, rb_p=rb,
                    padding=padding, dtype_bytes=dtype_bytes, stride=stride,
                    c_blk=cb, whole_plane=whole, kind=ws_kind)
                if ws > vmem_budget:
                    continue
                for order in orders:
                    key = (rb, kb, cb, order)
                    if key in seen:
                        continue
                    seen.add(key)
                    pool.append(ConvBlocking(rb_p=rb, k_blk=kb, c_blk=cb,
                                             order=order, vmem_bytes=ws))
    if len(pool) > MAX_CANDIDATES - 1:
        # spread-sample the (rb_p-major) pool instead of truncating its
        # prefix: a prefix cut would exhaust the budget inside the first
        # rb_p value's c_blk x order cross product and never explore the
        # register-block axis at all
        step = len(pool) / (MAX_CANDIDATES - 1)
        pool = [pool[int(i * step)] for i in range(MAX_CANDIDATES - 1)]
    return [seed] + pool


def matmul_candidates(m: int, n: int, k: int, *, dtype_bytes: int = 2,
                      vmem_budget: int = VMEM_BUDGET) -> list[MatmulBlocking]:
    """Tile candidates for the fused matmul kernel (bm/bn/bk must divide)."""
    seed = matmul_blocking_analytic(m, n, k, dtype_bytes=dtype_bytes,
                                    vmem_budget=vmem_budget)

    def largest_divisor(dim: int, cap: int) -> int:
        return max(d for d in divisors(dim) if d <= cap)

    bms = [d for d in (64, 128, 256) if m % d == 0] or [largest_divisor(m, 256)]
    bns = [d for d in (64, 128, 256) if n % d == 0] or [largest_divisor(n, 256)]
    bks = ([d for d in (128, 256, 512, 1024) if k % d == 0]
           or [largest_divisor(k, 1024)])

    def ws(bm, bn, bk):
        return (bm * bk + bk * bn) * dtype_bytes + 2 * bm * bn * 4

    # the analytic seed joins the pool only if it tiles the problem exactly —
    # callers (ops.matmul) fall back to the reference path otherwise, so a
    # persisted non-dividing winner would be a permanently rejected entry
    out, seen = [], set()
    if m % seed.bm == 0 and n % seed.bn == 0 and k % seed.bk == 0:
        out.append(seed)
        seen.add((seed.bm, seed.bn, seed.bk))
    for bm in bms:
        for bn in bns:
            for bk in bks:
                if (bm, bn, bk) in seen or ws(bm, bn, bk) > vmem_budget:
                    continue
                seen.add((bm, bn, bk))
                out.append(MatmulBlocking(bm=bm, bn=bn, bk=bk,
                                          vmem_bytes=ws(bm, bn, bk)))
    return out[:MAX_CANDIDATES] or [seed]


