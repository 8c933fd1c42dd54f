"""Blocking selection — the paper's §II-B/C/D RB_P/cache-block choice,
re-derived for the TPU memory hierarchy (HBM -> VMEM -> VREG, MXU 128x128).

The paper picks register blocks to (a) hide FMA latency with independent
accumulation chains and (b) keep the working set in L1/L2.  On TPU the
analogous constraints are:
  (a) the implicit-GEMM M-tile (rb_p * Q) should be >= 128 rows so the MXU
      runs full-height passes (the "FMA latency" of the systolic array is the
      pipeline fill, amortized by tall tiles);
  (b) the per-grid-step working set (streamed input row band for the tiled
      fwd/bwd/wu kernels — or resident plane for the legacy whole-plane
      variants and streams — + weight/dO block + output/accumulator tile)
      must fit the VMEM budget;
  (c) minor dims should be multiples of 128 lanes / 8 sublanes (K, C blocks).

Two selection paths (DESIGN.md §3, §6):

  * ``conv_blocking_analytic`` / ``matmul_blocking_analytic`` — the closed-
    form heuristic above; always available, and the seed candidate + cost
    model prior for the tuner.
  * ``conv_blocking`` / ``matmul_blocking`` — the public entry points.  When
    autotuning is enabled (``repro.backend`` knob / ``REPRO_AUTOTUNE`` /
    explicit ``autotune=`` kwarg) they consult ``repro.tune``'s persistent
    per-shape cache first — "cache": cached winner or analytic fallback;
    "tune": search-and-persist on a miss — and fall back to the analytic
    answer otherwise, so callers never see a behavioral cliff.
"""
from __future__ import annotations

import dataclasses
import math
import os

# bytes/core we allow a kernel to claim; REPRO_VMEM_BUDGET forces a smaller
# budget (CI exercises the tiled kernel under pressure with it)
VMEM_BUDGET = int(os.environ.get("REPRO_VMEM_BUDGET", 16 * 1024 * 1024))
LANE = 128
MXU = 128


@dataclasses.dataclass(frozen=True)
class ConvBlocking:
    rb_p: int          # output rows per microkernel (paper RB_P; each a
                       # full output row — RB_Q is always Q on the TPU)
    k_blk: int         # output-feature block (paper's K_b vector block)
    c_blk: int         # input-feature block (C_b accumulation passes)
    order: str         # grid/dryrun loop order (paper §II-C)
    vmem_bytes: int    # modeled working set


def divisors(x: int):
    return [d for d in range(1, x + 1) if x % d == 0]


def aligned_block(dim: int) -> int:
    """Feature block (C or K) within one MXU lane tile that Mosaic accepts:
    a block on the lane dim must be a multiple of 128 or the whole dim.  So
    128 where it divides ``dim``, else the whole dim (Inception's 192
    included)."""
    return LANE if dim % LANE == 0 else dim


def tiled_conv_buffers(*, q: int, r: int, s: int, stride: int, rb_p: int,
                       c_blk: int, k_blk: int, dtype_bytes: int = 4,
                       kind: str = "fwd", cols: int | None = None):
    """(blocks, scratch) of one tiled conv kernel, as (shape, itemsize)
    pairs — the buffers the kernels in ``repro.kernels`` declare, without
    the optional epilogue operands.

    The input block is a halo'd row band of the stride-phase planes: ``rb_p
    + (r-1)//stride`` rows x min(stride, r)·min(stride, s) planes x the
    full row (``cols`` + (s-1)//stride) x ``c_blk``.  "fwd" (and "bwd",
    the dual forward launch) add the weight block, the output tile and the
    f32 accumulator scratch; "q8" streams int8 band and weights
    (``dtype_bytes=1``) with an f32 dequant row, f32 output tile and int32
    accumulator; "wu" adds the dO tile and the revisited f32 dW tile.
    ``cols`` is the output row width: by default the kernels' Q' (Q rounded
    up to the sublane tile, ``tile_cols``); Q counts logical pixels."""
    if cols is None:
        from repro.kernels.conv2d_direct import tile_cols
        cols = tile_cols(q, dtype_bytes)
    band = (1, rb_p + (r - 1) // stride, min(stride, r), min(stride, s),
            cols + (s - 1) // stride, c_blk)
    tile = (1, rb_p, cols, k_blk)
    if kind == "wu":
        return [(band, dtype_bytes), (tile, dtype_bytes),
                ((r, s, c_blk, k_blk), 4)], []
    acc = [((rb_p * cols, k_blk), 4)]
    if kind == "q8":
        return [(band, 1), ((r, s, c_blk, k_blk), 1), ((1, k_blk), 4),
                (tile, 4)], acc
    return [(band, dtype_bytes), ((r, s, c_blk, k_blk), dtype_bytes),
            (tile, dtype_bytes)], acc


def conv_working_set(*, h: int, w: int, c: int, k_blk: int, r: int, s: int,
                     q: int, rb_p: int, padding: int, dtype_bytes: int = 4,
                     stride: int = 1, c_blk: int | None = None,
                     whole_plane: bool = False,
                     kind: str = "fwd") -> int:
    """Modeled per-grid-step VMEM bytes for a conv blocking candidate.

    Tiled (default): the buffers the tiled kernel declares
    (``tiled_conv_buffers``: a full-width row band of the stride-phase
    planes, so the working set is independent of H), one copy each, in
    logical pixels — rows of Q, not the kernels' sublane-padded Q' (the
    traffic model, ``tune.measure.conv_traffic``, counts the same way).
    The kernels ask Mosaic for those buffers at Q', double-buffered and
    padded to the (8, 128) tile, plus headroom
    (``kernels.conv2d_direct.compiler_params``), so the budget is a target
    for the blocking, not the scoped-VMEM limit.  ``whole_plane=True``
    models the legacy kernels (fwd whole-plane variant, legacy wu, q8,
    streams) that keep the full padded plane resident; there it scales with
    H*W*c_blk.

    ``kind`` picks the residency model: "fwd"/"bwd" (the forward kernel —
    the bwd-data dual *is* a forward launch) hold a weight block and an
    output tile + f32 accumulator next to the input; "wu" (the update pass)
    holds a dO pixel tile and the revisited (r, s, C_blk, K_blk) f32
    weight-gradient accumulator tile instead; "q8" (the quantized forward,
    §II-K — pass ``dtype_bytes=1``) streams int8 bands/weights but keeps an
    f32 output tile + int32 accumulator, so the input side shrinks 4x while
    the output side does not.
    """
    c_blk = c if not c_blk else c_blk
    if not whole_plane:
        blocks, scratch = tiled_conv_buffers(
            q=q, r=r, s=s, stride=stride, rb_p=rb_p, c_blk=c_blk,
            k_blk=k_blk, dtype_bytes=dtype_bytes,
            kind=kind if kind in ("wu", "q8") else "fwd", cols=q)
        return sum(math.prod(sh) * b for sh, b in blocks + scratch)
    hp, wp = h + 2 * padding + r, w + 2 * padding       # padded upper bound
    x_bytes = hp * wp * c_blk * dtype_bytes
    if kind == "wu":
        do_tile = rb_p * q * k_blk * dtype_bytes
        dw_acc = r * s * c_blk * k_blk * 4           # f32 revisited tile
        return x_bytes + do_tile + dw_acc
    wblk = r * s * c_blk * k_blk * dtype_bytes
    out_bytes = 4 if kind == "q8" else dtype_bytes   # q8 stores f32 (§II-K)
    out = rb_p * q * k_blk * out_bytes
    acc = rb_p * q * k_blk * 4
    return x_bytes + wblk + out + acc


def conv_blocking_analytic(*, h: int, w: int, c: int, k: int, r: int, s: int,
                           stride: int, padding: int, dtype_bytes: int = 4,
                           vmem_budget: int = VMEM_BUDGET,
                           require_divisor: bool = False,
                           whole_plane: bool | None = None,
                           kind: str = "fwd") -> ConvBlocking:
    """Closed-form heuristic (no cache consulted).

    ``whole_plane`` (default: ``require_divisor``) selects the resident-
    plane VMEM model: the *legacy* wu kernel (which also needs rb_p | P)
    keeps the full-C padded plane in VMEM, the streams kernel a C_blk slice
    of it.  The forward path — and, with ``kind="wu"`` and
    ``require_divisor=False``, the tiled update pass — is band-streamed: the
    working set is a full-width row band, so the budget constrains the
    *band* — C stays unblocked (single accumulation pass) unless even a
    one-row band would not fit, and then RB_P alone fits the rest.  When
    nothing fits, the one-row lane-block candidate is returned with its
    (over-budget) ``vmem_bytes``.  ``kind`` selects the per-step residency
    model of ``conv_working_set`` ("bwd" — the dual forward launch — models
    as "fwd").
    """
    p = (h + 2 * padding - r) // stride + 1
    q = (w + 2 * padding - s) // stride + 1
    k_blk = aligned_block(k)
    whole = require_divisor if whole_plane is None else whole_plane
    ws_kind = kind if kind in ("wu", "q8") else "fwd"

    # c_blk is the reported blocking knob; c_model is what sits in VMEM
    # (the legacy wu kernel has no C blocking — its plane is resident at
    # full C)
    if require_divisor:
        c_blk, c_model = aligned_block(c), c
    elif whole:
        c_blk = c_model = aligned_block(c)
    else:
        c_blk = c_model = c

    def ws(rb_p: int, c_m: int) -> int:
        return conv_working_set(h=h, w=w, c=c, k_blk=k_blk, r=r, s=s, q=q,
                                rb_p=rb_p, padding=padding,
                                dtype_bytes=dtype_bytes, stride=stride,
                                c_blk=c_m, whole_plane=whole, kind=ws_kind)

    if not whole and ws(1, c_model) > vmem_budget:
        # prefer a single accumulation pass (c_blk = c); fall back to the
        # lane-aligned block when even a one-row band would blow the budget
        c_blk = c_model = aligned_block(c)

    cands = divisors(p) if require_divisor else list(range(1, p + 1))
    # smallest rb_p with a full-height MXU M-tile, then grow while VMEM
    # allows.  The band-streamed update pass keeps growing to the budget:
    # its row band is refetched once per P-block on every (K_b, C_b) pass,
    # so a taller block strictly cuts refetch traffic (and deepens the
    # pixel-block contraction) — there is no output-tile reuse to trade off.
    # The q8 forward also grows: its int8 band is 4x smaller, so the same
    # budget admits ~4x the rows — fewer grid steps and proportionally less
    # halo refetch per output row (the §II-K blocking dividend).
    grow_to_budget = kind in ("wu", "q8") and not whole
    best = cands[0]
    for rb in cands:
        if ws(rb, c_model) > vmem_budget:
            break
        best = rb
        if rb * q >= MXU and not grow_to_budget:
            break
    # §II-C: for 1x1 convs pull the C loop in (order "npkc" keeps the output
    # tile resident across C-blocks -> more output register reuse).
    order = "npkc" if (r == 1 and s == 1) else "nkpc"
    return ConvBlocking(rb_p=best, k_blk=k_blk, c_blk=c_blk, order=order,
                        vmem_bytes=ws(best, c_model))


def conv_blocking(*, h: int, w: int, c: int, k: int, r: int, s: int,
                  stride: int, padding: int, dtype_bytes: int = 4,
                  vmem_budget: int = VMEM_BUDGET,
                  require_divisor: bool = False,
                  backend: str | None = None,
                  autotune: str | None = None,
                  kind: str | None = None,
                  minibatch: int = 1) -> ConvBlocking:
    """Public blocking choice: tuned winner when available, else analytic.

    `backend`/`autotune`/`kind`/`minibatch` extend the seed signature; left
    at defaults they resolve through ``repro.backend`` (autotune defaults
    "off", preserving the seed's pure-analytic behavior and every existing
    call site).  `minibatch` is part of the tuning key: the winning blocking
    depends on how much batch-reuse amortizes weight traffic.  Kinds:
    "fwd" (tiled forward), "bwd" (the backward-data dual — same kernel,
    separate cache namespace), "wu" (band-streamed update pass; with
    ``require_divisor=True`` the legacy resident-plane variant), "streams",
    "q8" (int8 tiled forward — call with ``dtype_bytes=1``).
    """
    mode = _resolve_autotune(autotune)
    kind = kind or ("wu" if require_divisor else "fwd")
    if mode != "off" and vmem_budget == VMEM_BUDGET:
        blk = _tuned_conv(mode, h=h, w=w, c=c, k=k, r=r, s=s, stride=stride,
                          padding=padding, dtype_bytes=dtype_bytes, kind=kind,
                          backend=_resolve_backend(backend),
                          minibatch=minibatch)
        if blk is not None:
            if not require_divisor or _out_p(h, r, stride, padding) % blk.rb_p == 0:
                return blk
    return conv_blocking_analytic(h=h, w=w, c=c, k=k, r=r, s=s,
                                  stride=stride, padding=padding,
                                  dtype_bytes=dtype_bytes,
                                  vmem_budget=vmem_budget,
                                  require_divisor=require_divisor,
                                  whole_plane=(True if kind == "streams"
                                               else None),
                                  kind=kind)


# -- depth-first chain residency (DESIGN.md §16) -----------------------------


@dataclasses.dataclass(frozen=True)
class ChainBlocking:
    """Band split for a depth-first conv->conv chain.

    ``rb`` is the number of *final-layer* output rows per interleaved band
    step; upstream band heights follow from the halo recurrence.  ``fits``
    is False when even a one-row band blows the budget — the per-chain
    fallback rule (execute unfused) keys off it.
    """
    rb: int            # final-layer output rows per band step
    n_bands: int
    vmem_bytes: int    # peak per-step working set at this rb
    fits: bool


def chain_working_set(layers, *, rows_out: int, dtype_bytes: int = 4,
                      blockings=None) -> int:
    """Peak per-band-step VMEM bytes of a depth-first chain.

    ``layers`` is a list of dicts with each conv's input-plane shape
    (h, w, c) and kernel geometry (k, r, s, stride, padding), producers
    first.  ``rows_out`` is the final layer's output rows per band; each
    upstream band height follows the exact halo recurrence
    (``fusion.chain_band_rows``).  Bands are handed off eagerly — while
    layer l computes, only its input band (= layer l-1's output band),
    weight block, and output band + accumulator are live — so the chain
    peak is the max over layers of the PR-3/4 per-step residency model
    (``conv_working_set``) evaluated at that layer's band height.
    """
    from repro.core.fusion import chain_band_rows
    rs = [(L["r"], L["stride"], L["padding"]) for L in layers]
    rows = chain_band_rows(rs, rows_out)
    peak = 0
    for l, L in enumerate(layers):
        p = (L["h"] + 2 * L["padding"] - L["r"]) // L["stride"] + 1
        q = (L["w"] + 2 * L["padding"] - L["s"]) // L["stride"] + 1
        blk = (blockings[l] if blockings is not None else
               conv_blocking_analytic(h=L["h"], w=L["w"], c=L["c"], k=L["k"],
                                      r=L["r"], s=L["s"], stride=L["stride"],
                                      padding=L["padding"],
                                      dtype_bytes=dtype_bytes))
        ws = conv_working_set(h=L["h"], w=L["w"], c=L["c"], k_blk=blk.k_blk,
                              r=L["r"], s=L["s"], q=q,
                              rb_p=min(rows[l + 1], p),
                              padding=L["padding"], dtype_bytes=dtype_bytes,
                              stride=L["stride"], c_blk=blk.c_blk)
        peak = max(peak, ws)
    return peak


def chain_blocking(layers, *, vmem_budget: int | None = None,
                   dtype_bytes: int = 4, blockings=None) -> ChainBlocking:
    """Largest final-layer band height whose chain working set fits VMEM.

    The working set is monotone in ``rows_out`` (every term grows with the
    band), so binary search finds the largest fitting band; ``rb = P_final``
    degenerates to a single band (zero halo refetch).  When even one row
    does not fit, returns ``fits=False`` — the executor then runs the chain
    unfused (DESIGN.md §16 fallback rule).
    """
    vmem_budget = VMEM_BUDGET if vmem_budget is None else vmem_budget
    last = layers[-1]
    p_final = (last["h"] + 2 * last["padding"] - last["r"]) // last["stride"] + 1
    if blockings is None:
        blockings = [conv_blocking_analytic(
            h=L["h"], w=L["w"], c=L["c"], k=L["k"], r=L["r"], s=L["s"],
            stride=L["stride"], padding=L["padding"], dtype_bytes=dtype_bytes)
            for L in layers]

    def ws(rb):
        return chain_working_set(layers, rows_out=rb, dtype_bytes=dtype_bytes,
                                 blockings=blockings)

    best = 0
    lo, hi = 1, p_final
    while lo <= hi:
        mid = (lo + hi) // 2
        if ws(mid) <= vmem_budget:
            best, lo = mid, mid + 1
        else:
            hi = mid - 1
    if best == 0:
        return ChainBlocking(rb=1, n_bands=p_final, vmem_bytes=ws(1),
                             fits=False)
    return ChainBlocking(rb=best, n_bands=math.ceil(p_final / best),
                         vmem_bytes=ws(best), fits=True)


@dataclasses.dataclass(frozen=True)
class MatmulBlocking:
    bm: int
    bn: int
    bk: int
    vmem_bytes: int


def matmul_blocking_analytic(m: int, n: int, k: int, *, dtype_bytes: int = 2,
                             vmem_budget: int = VMEM_BUDGET) -> MatmulBlocking:
    bm = min(m, MXU)
    bn = min(n, MXU)
    # largest bk (multiple of LANE, divisor of k) whose blocks fit VMEM
    bk = min(k, 512)
    while k % bk:
        bk //= 2
    def ws(bk_):
        return (bm * bk_ + bk_ * bn) * dtype_bytes + 2 * bm * bn * 4
    while bk > LANE and ws(bk) > vmem_budget:
        bk //= 2
    return MatmulBlocking(bm=bm, bn=bn, bk=max(bk, 1), vmem_bytes=ws(bk))


def matmul_blocking(m: int, n: int, k: int, *, dtype_bytes: int = 2,
                    vmem_budget: int = VMEM_BUDGET,
                    backend: str | None = None,
                    autotune: str | None = None) -> MatmulBlocking:
    """Public matmul tiling: tuned winner when available, else analytic."""
    mode = _resolve_autotune(autotune)
    if mode != "off" and vmem_budget == VMEM_BUDGET:
        blk = _tuned_matmul(mode, m, n, k, dtype_bytes=dtype_bytes,
                            backend=_resolve_backend(backend))
        if blk is not None:
            return blk
    return matmul_blocking_analytic(m, n, k, dtype_bytes=dtype_bytes,
                                    vmem_budget=vmem_budget)


# -- tuner bridge (lazy imports: tune statically imports this module) --------

def _out_p(h: int, r: int, stride: int, padding: int) -> int:
    return (h + 2 * padding - r) // stride + 1


def _resolve_autotune(mode: str | None) -> str:
    if mode is not None:
        return mode
    from repro import backend as be
    return be.get_autotune()


def _resolve_backend(backend: str | None) -> str:
    if backend is not None:
        return backend
    from repro import backend as be
    return be.get_backend()


def _tuned_conv(mode: str, **kw) -> ConvBlocking | None:
    from repro import tune
    if mode == "tune":
        return tune.autotune_conv(**kw)
    return tune.lookup_conv(**kw)


def _tuned_matmul(mode: str, m, n, k, *, dtype_bytes, backend):
    from repro import tune
    if mode == "tune":
        return tune.autotune_matmul(m, n, k, dtype_bytes=dtype_bytes,
                                    backend=backend)
    return tune.lookup_matmul(m, n, k, dtype_bytes=dtype_bytes,
                              backend=backend)
