"""Candidate scoring: wall-clock on real hardware, cost model everywhere else.

On a TPU ("pallas" backend with a TPU device attached) each candidate blocking
compiles and times the actual kernel — the paper's empirical specialization.
Under "interpret"/"xla" on CPU, wall time measures the interpreter (or a
different algorithm entirely), so candidates are ranked by an analytic cost
model instead:

  t_model = max(t_compute, t_memory) + n_steps * STEP_OVERHEAD

  t_compute  FLOPs / (peak * MXU tile utilization): the M-tile (rb_p*Q rows),
             N-tile (k_blk lanes) and contraction tile (c_blk) each pay a
             ceil-to-128 occupancy factor — the paper's "register block must
             fill the FMA pipeline", re-derived for a 128x128 systolic array.
  t_memory   HBM traffic from loop-order-aware block refetch counts: a block
             whose index depends on loop set S is fetched once per iteration
             of the loops at positions up to S's innermost member (§II-C cache
             blocking, computed exactly instead of assumed).  The tiled
             forward kernel's input block is the streamed *row band* (its
             index varies with P, so it refetches per row-block); the legacy
             whole-plane variant ships the full padded plane on every grid
             step (the "bytes accessed" upper-bound convention of
             ``launch.roofline``).  A C_b-blocked output tile pays the
             multi-pass term: each extra accumulation visit is modeled as a
             read-back + rewrite.
  n_steps    grid size: each step pays a fixed pipeline-fill overhead.

The model is deliberately the same family as ``benchmarks.resnet50_layers.
modeled_v5e_efficiency`` but blocking-resolved, so tuned-vs-heuristic deltas
are meaningful even offline.
"""
from __future__ import annotations

import math
import sys

from repro.core.blocking import LANE, ConvBlocking, MatmulBlocking
from repro.launch.roofline import (HBM_BW, PEAK_FLOPS, STEP_OVERHEAD_S,
                                   device_peaks, kernel_roofline)
from repro.tune.space import out_dim

STEP_OVERHEAD_US = STEP_OVERHEAD_S * 1e6

# Stable traffic-dict keys (``conv_traffic`` / ``_wu_traffic``).  The bench
# JSONs derive their persisted fields from these and the perf-gate extractors
# (repro.perfci.extract) join on the derived names — renaming one is a
# baseline-schema change and must bump perfci's SCHEMA_VERSION.
CONV_TRAFFIC_KEYS = ("flops", "util", "x_bytes", "w_bytes", "o_bytes",
                     "hbm_bytes", "n_steps", "extents")

# Stable keys of the ``chain_traffic`` decision dict (DESIGN.md §16).
CHAIN_TRAFFIC_KEYS = ("fused", "fits_vmem", "rb", "n_bands", "vmem_bytes",
                      "flops", "x_bytes", "w_bytes", "o_bytes", "hbm_bytes",
                      "intermediate_bytes", "unfused_hbm_bytes",
                      "unfused_intermediate_bytes", "n_steps", "n_layers")


def _tile_util(extent: int) -> float:
    """Occupancy of a 128-wide MXU dimension holding `extent` elements."""
    if extent <= 0:
        return 1.0
    return extent / (LANE * math.ceil(extent / LANE))


def _band_elems(*, rb_p: int, q: int, r: int, s: int, stride: int) -> int:
    """Input pixels one streamed row band carries per channel, counted in
    logical pixels: ``rb_p + (r-1)//stride`` rows of the
    min(stride, r)·min(stride, s) stride-phase planes, each a full row of
    ``q + (s-1)//stride`` columns (the kernels' sublane padding of the row
    to Q' is not charged)."""
    return ((rb_p + (r - 1) // stride) * min(stride, r) * min(stride, s)
            * (q + (s - 1) // stride))


def _refetches(dep_positions: list[int], extents: tuple[int, ...]) -> int:
    """Times a block is (re)fetched over a nested loop: once per iteration of
    every loop at or outside the innermost dependency *that actually varies*."""
    live = [p for p in dep_positions if extents[p] > 1]
    if not live:
        return 1
    inner = max(live)
    n = 1
    for p in range(inner + 1):
        n *= extents[p]
    return n


def conv_traffic(shape: dict, blk: ConvBlocking, *, minibatch: int = 1,
                 kind: str = "fwd", whole_plane: bool = False) -> dict:
    """Schedule-resolved FLOPs / HBM traffic / occupancy for one conv layer
    under blocking `blk` — the inputs of ``launch.roofline.kernel_roofline``.

    Traffic terms (all in bytes, summed over the whole launch):
      * input  — the tiled fwd/bwd kernel streams one row band per step
        (deps: N, P, C_b); ``whole_plane`` ships the padded plane on *every*
        grid step; streams keeps the plane resident per (N, C_b).
      * weight — one (r, s, C_blk, K_blk) block, resident across the P sweep
        when the loop order allows (§II-C).
      * output — one f32 tile per (N, K_b, P_b) visit; when C is blocked
        (tiled fwd with c_blk < C, or streams) every extra accumulation pass
        re-reads and rewrites the tile: the multi-pass output term.

    ``kind="q8"`` is the tiled forward with int8 byte accounting: pass a
    shape dict with ``dtype_bytes=1`` and the input-band and weight-block
    terms shrink 4x while the output term stays f32 (the §II-K asymmetry —
    which is exactly why the modeled speedup lands near the paper's 1.6x on
    bandwidth-bound layers instead of 4x).

    ``kind="wu"`` models the update pass instead: the tiled kernel streams
    an input row band *and* a dO pixel tile on every step of its
    ``(K_b, C_b, N, P_b, Q_b)`` grid and writes each (r, s, C_blk, K_blk)
    f32 dW tile exactly once (the accumulation revisits stay in VMEM); the
    legacy ``whole_plane`` variant keeps the entire padded plane resident
    across the P sweep (its block index is constant over P_b, so Pallas
    re-fetches per (k, n)) — but that residency is exactly why it cannot
    schedule once the plane approaches the VMEM budget, the §II-J
    regression the tiling removes.
    """
    h, w, c, k = shape["h"], shape["w"], shape["c"], shape["k"]
    r, s = shape["r"], shape["s"]
    stride, padding = shape["stride"], shape["padding"]
    dtype_bytes = shape.get("dtype_bytes", 4)
    p = out_dim(h, r, stride, padding)
    q = out_dim(w, s, stride, padding)
    n = minibatch
    hp, wp = h + 2 * padding + r, w + 2 * padding

    if kind == "wu":
        return _wu_traffic(h=h, w=w, c=c, k=k, r=r, s=s, stride=stride,
                           p=p, q=q, hp=hp, wp=wp, n=n, blk=blk,
                           dtype_bytes=dtype_bytes, whole_plane=whole_plane)

    tiled_fwd = kind in ("fwd", "bwd", "q8") and not whole_plane
    c_blk = c if whole_plane else blk.c_blk
    rb_p = min(blk.rb_p, p)
    p_b = math.ceil(p / rb_p)
    k_b = max(k // blk.k_blk, 1)
    c_b = max(c // c_blk, 1)
    extents = (n, k_b, p_b, c_b)

    # the legacy whole-plane fwd has a fixed grid order
    order = "nkpc" if whole_plane else blk.order
    pos = {dim: i for i, dim in enumerate(order)}
    by_dim = {"n": extents[0], "k": extents[1], "p": extents[2],
              "c": extents[3]}
    ordered = tuple(by_dim[d] for d in order)
    n_steps = extents[0] * extents[1] * extents[2] * extents[3]

    # compute: every grid step runs the full (r,s) small-GEMM chain
    flops = 2.0 * n * p * q * c * k * r * s
    util = (_tile_util(rb_p * q) * _tile_util(blk.k_blk)
            * _tile_util(c_blk))

    if tiled_fwd:
        x_bytes = _band_elems(rb_p=rb_p, q=q, r=r, s=s,
                              stride=stride) * c_blk * dtype_bytes
        x_f = _refetches([pos["n"], pos["p"], pos["c"]], ordered)
    else:
        x_bytes = hp * wp * c_blk * dtype_bytes
        if whole_plane:
            # the legacy fwd kernel ships the entire padded plane into VMEM
            # on every grid step — charge it per step (upper bound; VMEM
            # residency across the sweep cannot be assumed once the plane
            # approaches the budget, which is the regime tiling targets)
            x_f = n_steps
        else:
            x_f = _refetches([pos["n"], pos["c"]], ordered)
    w_bytes = r * s * c_blk * blk.k_blk * dtype_bytes
    o_bytes = rb_p * q * blk.k_blk * 4      # f32 tile (q8 output stays f32)
    w_f = _refetches([pos["k"], pos["c"]], ordered)
    o_f = _refetches([pos["n"], pos["k"], pos["p"]], ordered)
    revisit = max(extents[3], 1)
    # multi-pass output traffic: every extra C-block visit of an output tile
    # is a read-back + rewrite (streams accumulates through the out block;
    # the tiled fwd scratch tile is modeled the same way — conservative)
    multipass = (2 * revisit - 1) if (kind == "streams" or tiled_fwd) else 1
    o_traffic = o_bytes * o_f * multipass
    total = x_bytes * x_f + w_bytes * w_f + o_traffic
    return {
        "flops": flops,
        "util": util,
        "x_bytes": x_bytes * x_f,
        "w_bytes": w_bytes * w_f,
        "o_bytes": o_traffic,
        "hbm_bytes": total,
        "n_steps": n_steps,
        "extents": extents,
    }


def _wu_traffic(*, h, w, c, k, r, s, stride, p, q, hp, wp, n, blk,
                dtype_bytes, whole_plane) -> dict:
    """Update-pass traffic: see ``conv_traffic``.  The GEMM per step is
    dW[r,s] += X^T @ dO with M=C_blk, N=K_blk, K=pixel-block, so occupancy
    is (c_blk, k_blk, rb_p*Q)-tiled."""
    flops = 2.0 * n * p * q * c * k * r * s
    k_blk = min(blk.k_blk, k)
    if whole_plane:
        rb_p = min(blk.rb_p, p)
        p_b = math.ceil(p / rb_p)
        n_steps = (k // k_blk) * n * p_b                  # (K_b, N, P_b)
        util = _tile_util(c) * _tile_util(k_blk) * _tile_util(rb_p * q)
        # the plane's block index is constant over the P_b sweep: fetched
        # once per (k, n), resident (in VMEM, or nowhere at all) in between
        x_traffic = hp * wp * c * dtype_bytes * (k // k_blk) * n
        do_traffic = rb_p * q * k_blk * dtype_bytes * n_steps
    else:
        rb_p = min(blk.rb_p, p)
        c_blk = blk.c_blk or c
        p_b = math.ceil(p / rb_p)
        n_steps = (k // k_blk) * (c // c_blk) * n * p_b
        util = _tile_util(c_blk) * _tile_util(k_blk) * _tile_util(rb_p * q)
        # band + dO tile are re-streamed on every step ((n, p) are the
        # innermost grid axes; each C-block pass re-reads the dO tiles)
        x_traffic = (_band_elems(rb_p=rb_p, q=q, r=r, s=s, stride=stride)
                     * c_blk * dtype_bytes * n_steps)
        do_traffic = rb_p * q * k_blk * dtype_bytes * n_steps
    # each (r, s, C_blk, K_blk) f32 tile is written exactly once — the
    # (n, p, q) accumulation revisits never leave VMEM
    dw_traffic = r * s * c * k * 4
    total = x_traffic + do_traffic + dw_traffic
    return {
        "flops": flops,
        "util": util,
        "x_bytes": x_traffic,
        "w_bytes": do_traffic,      # the "weight slot" input is dO here
        "o_bytes": dw_traffic,
        "hbm_bytes": total,
        "n_steps": n_steps,
        "extents": (n, k // k_blk, p_b, 1 if whole_plane else c // (blk.c_blk or c)),
    }


def conv_cost_us(shape: dict, blk: ConvBlocking, *, minibatch: int = 1,
                 kind: str = "fwd", whole_plane: bool = False) -> float:
    """Modeled microseconds for one conv of `shape` under blocking `blk`."""
    t = conv_traffic(shape, blk, minibatch=minibatch, kind=kind,
                     whole_plane=whole_plane)
    roof = kernel_roofline(flops=t["flops"], hbm_bytes=t["hbm_bytes"],
                           util=t["util"], n_steps=0)
    return roof["step_time_s"] * 1e6 + t["n_steps"] * STEP_OVERHEAD_US


def chain_traffic(shapes: list, *, minibatch: int = 1,
                  vmem_budget: int | None = None) -> dict:
    """Price a depth-first conv->conv chain against its unfused execution
    and decide whether to fuse it (DESIGN.md §16).

    ``shapes`` is the per-layer conv shape dict list, producers first.  The
    fused price replays the exact interleaved band schedule
    (``core.streams.build_chain_schedule``) and charges, per band step, the
    per-layer ``conv_traffic`` of that band under the *full-shape* blocking:

      * layer-0 input bands come from HBM — overlapping halo rows between
        consecutive bands are charged again (refetched halos, honestly);
      * every hand-off band (FLAG_HANDOFF) is VMEM-resident — its input-read
        and output-write terms are 0 HBM bytes, the depth-first dividend;
      * weight blocks are charged per band step (they cycle out of VMEM
        while the other chain layers run), same granularity as unfused;
      * only the final layer's output bands are written back.

    Decision (the per-chain fallback rule): fuse iff the combined band
    working set fits ``vmem_budget`` (``core.blocking.chain_blocking``) AND
    the fused HBM bytes do not exceed the unfused sum — halo recompute can
    lose on adversarial geometry, and an unprofitable chain simply runs
    layer-by-layer.  On fallback the reported traffic *is* the unfused sum.

    Returns ``CHAIN_TRAFFIC_KEYS`` plus ``parts``/``unfused_parts`` (the
    per-launch ``conv_traffic`` dicts, for ``launch.roofline.chain_roofline``).
    """
    from repro.core.blocking import chain_blocking, conv_blocking_analytic
    from repro.core.streams import FLAG_HANDOFF, build_chain_schedule

    n = minibatch
    dtype_bytes = shapes[0].get("dtype_bytes", 4)
    blks, unfused_parts, dims = [], [], []
    for sh in shapes:
        blk = conv_blocking_analytic(
            h=sh["h"], w=sh["w"], c=sh["c"], k=sh["k"], r=sh["r"], s=sh["s"],
            stride=sh["stride"], padding=sh["padding"],
            dtype_bytes=sh.get("dtype_bytes", 4))
        blks.append(blk)
        unfused_parts.append(conv_traffic(sh, blk, minibatch=n))
        dims.append((out_dim(sh["h"], sh["r"], sh["stride"], sh["padding"]),
                     out_dim(sh["w"], sh["s"], sh["stride"], sh["padding"])))
    unfused_hbm = sum(p["hbm_bytes"] for p in unfused_parts)
    # unfused: every intermediate activation round-trips HBM (write + read)
    unfused_inter = sum(2.0 * dims[l][0] * dims[l][1] * shapes[l]["k"]
                        * shapes[l].get("dtype_bytes", 4) * n
                        for l in range(len(shapes) - 1))

    cb = chain_blocking(shapes, vmem_budget=vmem_budget,
                        dtype_bytes=dtype_bytes, blockings=blks)
    sched = build_chain_schedule(
        rs=[(sh["r"], sh["stride"], sh["padding"]) for sh in shapes],
        h_in=shapes[0]["h"], rb=cb.rb)

    fused = dict.fromkeys(("flops", "x_bytes", "w_bytes", "o_bytes",
                           "hbm_bytes", "n_steps"), 0.0)
    parts = []
    for i in range(len(sched)):
        l = int(sched.layer_ids[i])
        o0, o1 = int(sched.o0[i]), int(sched.o1[i])
        sh = shapes[l]
        band = dict(sh)
        # padded band buffer: exact halo recurrence rows, W pre-padded
        band["h"] = (o1 - o0 - 1) * sh["stride"] + sh["r"]
        band["w"] = sh["w"] + 2 * sh["padding"]
        band["padding"] = 0
        t = conv_traffic(band, blks[l], minibatch=n)
        handoff = bool(sched.flags[i] & FLAG_HANDOFF)
        x_hbm = t["x_bytes"] if l == 0 else 0.0        # hand-off: VMEM read
        o_hbm = 0.0 if handoff else t["o_bytes"]       # hand-off: VMEM write
        part = dict(t)
        part["x_bytes"], part["o_bytes"] = x_hbm, o_hbm
        part["hbm_bytes"] = x_hbm + t["w_bytes"] + o_hbm
        parts.append(part)
        fused["flops"] += t["flops"]
        fused["x_bytes"] += x_hbm
        fused["w_bytes"] += t["w_bytes"]
        fused["o_bytes"] += o_hbm
        fused["hbm_bytes"] += part["hbm_bytes"]
        fused["n_steps"] += t["n_steps"]

    fuse = cb.fits and fused["hbm_bytes"] <= unfused_hbm
    out = {
        "fused": fuse,
        "fits_vmem": cb.fits,
        "rb": cb.rb,
        "n_bands": cb.n_bands,
        "vmem_bytes": cb.vmem_bytes,
        "n_layers": len(shapes),
        "unfused_hbm_bytes": unfused_hbm,
        "unfused_intermediate_bytes": unfused_inter,
        "unfused_parts": unfused_parts,
    }
    if fuse:
        out.update(fused)
        out["intermediate_bytes"] = 0.0     # the depth-first invariant
        out["parts"] = parts
    else:   # fallback: the chain runs layer-by-layer — price it as such
        out["flops"] = sum(p["flops"] for p in unfused_parts)
        out["x_bytes"] = sum(p["x_bytes"] for p in unfused_parts)
        out["w_bytes"] = sum(p["w_bytes"] for p in unfused_parts)
        out["o_bytes"] = sum(p["o_bytes"] for p in unfused_parts)
        out["hbm_bytes"] = unfused_hbm
        out["n_steps"] = sum(p["n_steps"] for p in unfused_parts)
        out["intermediate_bytes"] = unfused_inter
        out["parts"] = unfused_parts
    return out


def bwd_data_traffic(shape: dict, *, minibatch: int = 1,
                     mode: str = "phase") -> dict:
    """Modeled traffic of the whole §II-I backward-data pipeline of `shape`
    under duality plan ``mode`` ("phase" | "dilate").

    Returns the per-launch ``conv_traffic`` dicts of every dual forward conv
    the plan runs (``duality.dual_conv_signatures`` with ``unique=False`` —
    one for the single-conv scenarios, one per non-empty phase for the phase
    plan, duplicates included: identical-geometry phases are still separate
    launches) plus ``extra_hbm_bytes``: the
    non-kernel HBM traffic the plan pays outside the conv launches —
    materializing the dilated dO (write + source read) for "dilate",
    re-interleaving the stride×stride dI subgrids for "phase".  Feed the
    result to ``launch.roofline.composite_roofline``.
    """
    from repro.core import duality
    from repro.core.blocking import conv_blocking_analytic

    h, w, c, k = shape["h"], shape["w"], shape["c"], shape["k"]
    r, s = shape["r"], shape["s"]
    stride, padding = shape["stride"], shape["padding"]
    dtype_bytes = shape.get("dtype_bytes", 4)
    p = out_dim(h, r, stride, padding)
    q = out_dim(w, s, stride, padding)
    sigs = duality.dual_conv_signatures(r=r, s=s, c=c, k=k, stride=stride,
                                        padding=padding, input_hw=(h, w),
                                        mode=mode, unique=False)
    parts = []
    for sg in sigs:
        blk = conv_blocking_analytic(
            h=sg["h"], w=sg["w"], c=sg["c"], k=sg["k"], r=sg["r"], s=sg["s"],
            stride=sg["stride"], padding=sg["padding"],
            dtype_bytes=dtype_bytes, kind="bwd")
        parts.append(conv_traffic(sg, blk, minibatch=minibatch, kind="bwd"))
    extra = 0.0
    generic = stride > 1 and not (r == 1 and s == 1)
    if generic and mode == "dilate":
        # write the (stride²-sparse) dilated+padded plane, read dO to fill it
        sg = sigs[0]
        extra = (sg["h"] * sg["w"] + p * q) * k * dtype_bytes * minibatch
    elif generic and mode == "phase":
        # interleave: read each phase output once, write dI once
        extra = 2.0 * h * w * c * dtype_bytes * minibatch
    return {"parts": parts, "extra_hbm_bytes": extra,
            "n_convs": len(parts), "mode": mode}


def bwd_data_cost_us(shape: dict, *, minibatch: int = 1,
                     mode: str = "phase") -> float:
    """Modeled microseconds for the full backward-data pipeline of `shape`."""
    from repro.launch.roofline import composite_roofline
    t = bwd_data_traffic(shape, minibatch=minibatch, mode=mode)
    roof = composite_roofline(t["parts"],
                              extra_hbm_bytes=t["extra_hbm_bytes"])
    return roof["cost_s"] * 1e6


def matmul_cost_us(m: int, n: int, k: int, blk: MatmulBlocking, *,
                   dtype_bytes: int = 2) -> float:
    flops = 2.0 * m * n * k
    util = (_tile_util(blk.bm) * _tile_util(blk.bn)
            * _tile_util(min(blk.bk, LANE)))
    t_comp = flops / (PEAK_FLOPS * max(util, 1e-3))
    g_m, g_n, g_k = m // blk.bm, n // blk.bn, k // blk.bk
    traffic = (g_n * (m * k) + g_m * (k * n)) * dtype_bytes + m * n * 4
    t_mem = traffic / HBM_BW
    return max(t_comp, t_mem) * 1e6 + g_m * g_n * g_k * STEP_OVERHEAD_US


# -- real-kernel timing (TPU path) -------------------------------------------

def can_measure(backend: str) -> bool:
    """Wall-clock only means something when the real kernel actually runs."""
    if backend != "pallas":
        return False
    try:
        import jax
        return jax.devices()[0].platform == "tpu"
    except Exception:  # noqa: BLE001
        return False


def measure_conv_us(shape: dict, blk: ConvBlocking, *, kind: str = "fwd",
                    minibatch: int = 1, warmup: int = 2,
                    iters: int = 5) -> float:
    """Compile and time the real kernel for one candidate (TPU only)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.conv2d_direct import conv2d_direct
    from repro.kernels.conv2d_streams import conv2d_streams_auto
    from repro.kernels.conv2d_wu import conv2d_wu

    rng = np.random.default_rng(0)
    h, w, c, k = shape["h"], shape["w"], shape["c"], shape["k"]
    r, s = shape["r"], shape["s"]
    stride, padding = shape["stride"], shape["padding"]
    x = jnp.asarray(rng.standard_normal((minibatch, h, w, c)), jnp.float32)
    wt = jnp.asarray(rng.standard_normal((r, s, c, k)) * 0.1, jnp.float32)

    if kind == "streams":
        # blocking= pins all four knobs AND skips the autotune consult —
        # re-entering the tuner mid-measurement would recurse on the same
        # not-yet-cached key.
        fn = jax.jit(lambda x, wt: conv2d_streams_auto(
            x, wt, stride=stride, padding=padding, blocking=blk))
    elif kind == "wu":
        p = out_dim(h, r, stride, padding)
        q = out_dim(w, s, stride, padding)
        do = jnp.asarray(rng.standard_normal((minibatch, p, q, k)),
                         jnp.float32)
        fn = jax.jit(lambda x, do: conv2d_wu(
            x, do, stride=stride, padding=padding, filter_rs=(r, s),
            b_p=blk.rb_p, k_blk=blk.k_blk, c_blk=blk.c_blk,
            whole_plane=False))
        wt = do
    elif kind == "q8":
        from repro.kernels.conv2d_q8 import conv2d_q8, quantize_conv_inputs
        x_q, w_q, sx, sw = quantize_conv_inputs(x, wt)
        fn = jax.jit(lambda x, wt: conv2d_q8(
            x, wt, x_scale=sx, w_scale=sw, stride=stride, padding=padding,
            rb_p=blk.rb_p, k_blk=blk.k_blk, c_blk=blk.c_blk,
            order=blk.order, whole_plane=False))
        x, wt = x_q, w_q
    else:                       # "fwd" and "bwd" (the dual IS a fwd launch)
        fn = jax.jit(lambda x, wt: conv2d_direct(
            x, wt, stride=stride, padding=padding, rb_p=blk.rb_p,
            k_blk=blk.k_blk, c_blk=blk.c_blk, order=blk.order,
            whole_plane=False))

    for _ in range(warmup):
        jax.block_until_ready(fn(x, wt))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x, wt))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def rank_conv(shape: dict, candidates: list[ConvBlocking], *,
              kind: str = "fwd", backend: str = "xla", minibatch: int = 1,
              measure_top: int = 8) -> list[tuple[float, ConvBlocking]]:
    """Score candidates; returns (score_us, blocking) sorted best-first.

    Model scores everywhere; on TPU the model shortlists `measure_top`
    candidates which are then re-ranked by real wall clock.  A candidate
    that fails to compile or run is counted and logged, and dropped from
    the ranking; when none of the shortlist runs, this raises rather than
    hand back the modeled order as if it had been measured.
    """
    scored = sorted(
        ((conv_cost_us(shape, b, minibatch=minibatch, kind=kind), b)
         for b in candidates), key=lambda t: t[0])
    if not can_measure(backend):
        return scored
    import jax
    device_peaks(jax.devices()[0].device_kind)   # unknown chip: an error
    timed, failed = [], []
    for _, b in scored[:measure_top]:
        try:
            timed.append((measure_conv_us(shape, b, kind=kind,
                                          minibatch=minibatch), b))
        except Exception as e:  # noqa: BLE001 — compile/run failure
            failed.append((b, e))
            print(f"rank_conv: {kind} {shape} candidate {b} failed: "
                  f"{type(e).__name__}: {str(e).splitlines()[0][:200]}",
                  file=sys.stderr)
    if failed:
        print(f"rank_conv: {len(failed)} of {len(failed) + len(timed)} "
              f"measured candidates failed for {kind} {shape}",
              file=sys.stderr)
    if not timed:
        raise RuntimeError(
            f"rank_conv: no {kind} candidate compiled on the chip for "
            f"{shape}") from (failed[-1][1] if failed else None)
    timed.sort(key=lambda t: t[0])
    return timed
