"""Direct-convolution forward Pallas kernel (paper §II-B..E,G adapted to TPU).

TPU mapping of the paper's blocked direct convolution:

  * ``VLEN`` feature-map blocking  -> channels live in the lane dimension
    (NHWC / RSCK layouts, C and K innermost).
  * register blocking ``RB_P x RB_Q`` -> an MXU M-tile of ``RB_P`` output rows
    by the full output row (M = RB_P * Q', Q' = Q rounded up to the sublane
    tile), so each grid step is one "microkernel invocation" computing an
    (RB_P*Q', K_blk) output tile.
  * cache blocking (§II-B)          -> the input is *tiled*: each grid step
    streams only the halo'd row band it reads — ``RB_P + (R-1)//stride`` rows
    x the full padded row x C_blk channels — so the VMEM working set is
    independent of H (see ``core.blocking.conv_working_set``).
  * stride                          -> the padded input is split once, in
    XLA, into stride-phase planes (``phase_planes``): tap (r, s) of a
    stride-``t`` window reads plane (r % t, s % t) at offset
    (r // t, s // t) with unit stride, so every in-kernel read is a
    contiguous static slice and a row band is a slab of the untiled leading
    axis.  The band's element offsets therefore never touch the tiled
    (cols, C) dims, whose blocks are whole or 128-lane aligned — the
    windows Mosaic's DMA accepts.
  * C_b accumulation (§II-A alg. 4) -> input channels are blocked; an f32
    VMEM scratch accumulator is zero-initialized on the first C-block visit
    of an output tile and the fused epilogue fires on the last visit — the
    same FLAG_INIT/FLAG_EPILOGUE discipline ``core.streams`` encodes into
    replay schedules, here derived statically from the grid position
    (C_b is always the innermost grid axis, so visits are contiguous).
  * the (r, s) small-GEMM chain     -> statically unrolled (r, s) loop of
    ``jax.lax.dot`` calls over VMEM slices, f32 accumulation.
  * layer fusion (§II-G)            -> bias / BN-scale-shift / residual-add /
    ReLU epilogue fused into the same kernel, applied while the tile is in
    VMEM ("hot in cache").
  * loop order (§II-C)              -> the grid is laid out per ``order``
    (a permutation of "nkpc", C innermost), trading weight-block vs
    input-band reuse exactly as in the paper.
  * two-level prefetch (§II-E)      -> the Mosaic grid pipeliner
    double-buffers the next step's blocks automatically.

The phase planes are zero-padded far enough that no band leaves the array —
the bottom padding also covers the ceil-div row grid, which is how the
paper's "second kernel variant at the P/Q boundary" (§II-H) disappears on
TPU: out-of-range output rows land in Pallas' masked out-of-bounds stores,
and the Q' - Q padding columns are sliced off after the call.

The pre-refactor variant that shipped the whole padded input plane per image
into VMEM on every grid step is kept as ``whole_plane=True`` (knob:
``REPRO_CONV_TILING=whole`` / ``repro.backend.set_conv_tiling``) for A/B
benchmarking; it only works for layers whose plane fits the VMEM budget.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@dataclasses.dataclass(frozen=True)
class FuseSpec:
    """Static description of the fused epilogue (paper §II-G L() operators)."""
    bias: bool = False
    bn: bool = False          # folded inference BN: scale * y + shift
    residual: bool = False
    relu: bool = False

    def n_extra_args(self) -> int:
        return int(self.bias) + 2 * int(self.bn) + int(self.residual)


def pad_input(x, *, padding: int, stride: int, rb_p: int, r: int, p: int):
    """Spatially pad x (N,H,W,C) for the whole-plane kernels: `padding` on
    all sides plus bottom slack so the ceil-div row grid never reads out of
    bounds.

    The bottom slack is exactly ``rows_needed - (h + padding)``: the grid's
    last row band ends at row ``(ceil(p/rb_p)*rb_p - 1)*stride + r`` of the
    padded plane, which for ``stride > 1`` is usually *less* than the
    symmetric ``h + 2*padding`` — padding past it would inflate the plane
    (and every row band) beyond what any grid step can touch.
    """
    n, h, w, c = x.shape
    p_b = math.ceil(p / rb_p)
    rows_needed = (p_b * rb_p - 1) * stride + r          # last row touched + 1
    pad_bottom = max(rows_needed - (h + padding), 0)
    return jnp.pad(x, ((0, 0), (padding, pad_bottom), (padding, padding),
                       (0, 0)))


def tile_cols(q: int, itemsize: int) -> int:
    """Output columns per tile row: Q rounded up to the sublane tile of an
    ``itemsize``-byte dtype (8 rows of 32 bits), so collapsing (rows, cols,
    C) -> (rows*cols, C) for the MXU never splits a tile."""
    pack = 8 * max(4 // itemsize, 1)
    return -(-q // pack) * pack


def vmem_buffer_bytes(shape, itemsize: int) -> int:
    """One VMEM buffer of ``shape``, its two minor dims padded to the
    (8 x 32-bit sublane, 128-lane) tile."""
    *lead, sub, lane = (1, *shape) if len(shape) == 1 else shape
    sub_tile = 8 * max(4 // itemsize, 1)
    return (math.prod(lead) * (-(-sub // sub_tile) * sub_tile)
            * (-(-lane // 128) * 128) * itemsize)


def pipelined_vmem_bytes(blocks, scratch=()) -> int:
    """Scoped VMEM one ``pallas_call`` grid step needs: every pipelined
    block double-buffered, plus the scratch, each tile-padded.  ``blocks``
    and ``scratch`` are (shape, itemsize) pairs."""
    return (2 * sum(vmem_buffer_bytes(sh, b) for sh, b in blocks)
            + sum(vmem_buffer_bytes(sh, b) for sh, b in scratch))


def phase_planes(x, *, padding: int, stride: int, r: int, s: int, p: int,
                 rb_p: int, cols: int):
    """Zero-pad x (N,H,W,C) and split it into stride-phase planes
    (N, rows, min(stride, r), min(stride, s), cols + (s-1)//stride, C):
    plane (a, b) holds padded pixel (i*stride + a, j*stride + b) at (i, j).

    Output (pp, qq)'s tap (rr, ss) then reads plane (rr % stride,
    ss % stride) at (pp + rr//stride, qq + ss//stride) — unit stride for
    every stride.  ``rows`` covers the ceil-div row grid plus the halo;
    ``cols`` is the padded output row width (``tile_cols``).  For stride 1
    this is a pad plus a free reshape."""
    n, h, w, c = x.shape
    rows = math.ceil(p / rb_p) * rb_p + (r - 1) // stride
    cols = cols + (s - 1) // stride
    hp, wp = rows * stride, cols * stride
    xp = jnp.pad(x, ((0, 0), (padding, max(hp - h - padding, 0)),
                     (padding, max(wp - w - padding, 0)), (0, 0)))
    xp = xp[:, :hp, :wp].reshape(n, rows, stride, cols, stride, c)
    xp = xp[:, :, :min(stride, r), :, :min(stride, s)]
    return xp.transpose(0, 1, 2, 4, 3, 5)


def band_spec(planes_shape, *, rb_p: int, r: int, stride: int, c_blk: int,
              n_axis: int, p_axis: int, c_axis: int):
    """BlockSpec streaming one halo'd row band per grid step: rows
    [pi*rb_p, pi*rb_p + rb_p + (r-1)//stride) of every phase plane, the full
    row, one C block.  Consecutive bands overlap by the halo, so the row
    offset is an element index — on the untiled leading axis; the tiled
    (cols, C) dims are read whole or at 128-lane aligned C offsets.
    Returns the spec and the band's shape."""
    _, _, ah, aw, cols, c = planes_shape
    shape = (1, rb_p + (r - 1) // stride, ah, aw, cols, c_blk)
    c_b = c // c_blk

    def index(*i):
        c_off = i[c_axis] * c_blk if c_b > 1 else 0
        return (i[n_axis], i[p_axis] * rb_p, 0, 0, 0, c_off)

    return pl.BlockSpec(tuple(pl.Element(d) for d in shape), index), shape


def band_tap(x_ref, rr: int, ss: int, *, rows: int, cols: int, stride: int):
    """Tap (rr, ss)'s (rows*cols, C_blk) GEMM operand, read from the band."""
    xs = x_ref[0, pl.dslice(rr // stride, rows), rr % stride, ss % stride,
               pl.dslice(ss // stride, cols), :]
    return xs.reshape(rows * cols, xs.shape[-1])


# scoped VMEM asked for on top of the blocks, for the unrolled tap chain's
# temporaries
VMEM_HEADROOM = 16 << 20


def compiler_params(semantics, *, blocks, scratch=()):
    """Mosaic parameters with an explicit scoped-VMEM limit: every pipelined
    block double-buffered plus the scratch, tile-padded, plus
    ``VMEM_HEADROOM``.  ``blocks``/``scratch`` are (shape, dtype) pairs."""
    need = pipelined_vmem_bytes(
        [(sh, jnp.dtype(dt).itemsize) for sh, dt in blocks],
        [(sh, jnp.dtype(dt).itemsize) for sh, dt in scratch])
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics),
                                vmem_limit_bytes=need + VMEM_HEADROOM)


def _unpack_fuse_refs(refs, fuse: FuseSpec):
    idx = 0
    bias_ref = scale_ref = shift_ref = res_ref = None
    if fuse.bias:
        bias_ref = refs[idx]; idx += 1
    if fuse.bn:
        scale_ref = refs[idx]; shift_ref = refs[idx + 1]; idx += 2
    if fuse.residual:
        res_ref = refs[idx]; idx += 1
    return bias_ref, scale_ref, shift_ref, res_ref, refs[idx]


def _epilogue(acc, fuse: FuseSpec, bias_ref, scale_ref, shift_ref, res_ref,
              m: int, k_blk: int, accum_dtype):
    """The fused §II-G L() chain, applied while the tile is hot in VMEM."""
    if fuse.bn:
        acc = acc * scale_ref[0, :].astype(accum_dtype)
        acc = acc + shift_ref[0, :].astype(accum_dtype)
    if fuse.bias:
        acc = acc + bias_ref[0, :].astype(accum_dtype)
    if fuse.residual:
        acc = acc + res_ref[0].reshape(m, k_blk).astype(accum_dtype)
    if fuse.relu:
        acc = jnp.maximum(acc, 0)
    return acc


def _kernel_tiled(x_ref, w_ref, *refs, fuse: FuseSpec, rb_p: int,
                  cols: int, stride: int, r: int, s: int, c_axis: int,
                  accum_dtype, out_dtype):
    """One microkernel invocation on a streamed row band: accumulate one
    C-block into the scratch tile; init on the first visit, epilogue + store
    on the last (the streams FLAG_INIT/FLAG_EPILOGUE discipline, static)."""
    refs, acc_ref = refs[:-1], refs[-1]
    bias_ref, scale_ref, shift_ref, res_ref, o_ref = \
        _unpack_fuse_refs(refs, fuse)

    ci = pl.program_id(c_axis)
    c_b = pl.num_programs(c_axis)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_blk = w_ref.shape[-1]
    acc = jnp.zeros((rb_p * cols, k_blk), dtype=accum_dtype)
    # The paper's perfectly-chained small-GEMM sequence over (r, s); the
    # band's row 0 is this step's first window row, so offsets are local.
    for rr in range(r):
        for ss in range(s):
            a = band_tap(x_ref, rr, ss, rows=rb_p, cols=cols, stride=stride)
            acc += jax.lax.dot(a.astype(accum_dtype), w_ref[rr, ss, :, :]
                               .astype(accum_dtype),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=accum_dtype)
    acc_ref[...] += acc

    @pl.when(ci == c_b - 1)
    def _finish():
        out = _epilogue(acc_ref[...], fuse, bias_ref, scale_ref, shift_ref,
                        res_ref, rb_p * cols, k_blk, accum_dtype)
        o_ref[0] = out.reshape(rb_p, cols, k_blk).astype(out_dtype)


def _kernel_whole(x_ref, w_ref, *refs, fuse: FuseSpec, rb_p: int, q: int,
                  stride: int, r: int, s: int, p_axis: int, accum_dtype,
                  out_dtype):
    """Legacy microkernel: whole padded plane resident, row selection via the
    P-block program id (kept for A/B benchmarking against the tiled path)."""
    bias_ref, scale_ref, shift_ref, res_ref, o_ref = \
        _unpack_fuse_refs(refs, fuse)

    pb = pl.program_id(p_axis)
    c = x_ref.shape[-1]
    k_blk = w_ref.shape[-1]
    acc = jnp.zeros((rb_p * q, k_blk), dtype=accum_dtype)
    row0 = pb * rb_p * stride
    for rr in range(r):
        for ss in range(s):
            xs = x_ref[0, pl.dslice(row0 + rr, rb_p, stride),
                       pl.dslice(ss, q, stride), :]          # (rb_p, q, c)
            a = xs.reshape(rb_p * q, c)
            acc += jax.lax.dot(a.astype(accum_dtype),
                               w_ref[rr, ss, :, :].astype(accum_dtype),
                               preferred_element_type=accum_dtype)
    acc = _epilogue(acc, fuse, bias_ref, scale_ref, shift_ref, res_ref,
                    rb_p * q, k_blk, accum_dtype)
    o_ref[0] = acc.reshape(rb_p, q, k_blk).astype(out_dtype)


def _grid_layout(order: str, *, n: int, k_b: int, p_b: int, c_b: int):
    """Grid extents laid out per the §II-C loop order.  ``order`` permutes
    (n, k, p, c) with C innermost (the accumulator tile lives across the
    C sweep).  Returns (grid, axis index per letter, Mosaic dimension
    semantics — every axis but the C accumulation is parallel)."""
    assert sorted(order) == sorted("nkpc"), order
    assert order.endswith("c"), "C-blocks must be innermost (accumulator)"
    ext = {"n": n, "k": k_b, "p": p_b, "c": c_b}
    axis = {ch: i for i, ch in enumerate(order)}
    semantics = ["arbitrary" if ch == "c" else "parallel" for ch in order]
    return tuple(ext[ch] for ch in order), axis, semantics


def conv2d_direct(x, w, *, stride: int = 1, padding: int = 0,
                  bias=None, scale=None, shift=None, residual=None,
                  relu: bool = False, rb_p: int = 8, k_blk: int | None = None,
                  c_blk: int | None = None, order: str = "nkpc",
                  whole_plane: bool | None = None,
                  accum_dtype=jnp.float32, interpret: bool = False,
                  name: str = "conv_fwd"):
    """Direct conv fwd.  x: (N,H,W,C), w: (R,S,C,K) -> (N,P,Q,K).

    `rb_p` is the paper's RB_P register block (output rows per microkernel;
    each covers the full output row).  `k_blk` is the output-feature block
    (paper: the vectorized K_b loop); defaults to min(K, 128) = one MXU
    N-tile.  `c_blk` blocks the input features (paper C_b; `None` =
    unblocked): the output tile is then revisited across C-block grid steps
    and accumulated in an f32 VMEM scratch.  On the chip both feature blocks
    must be the whole dim or a multiple of 128 (``core.blocking`` only
    emits such blocks).  `order` is the §II-C loop order of the grid.
    `whole_plane` selects the legacy untiled kernel (default: the
    ``repro.backend`` conv-tiling knob).  `name` names the kernel (the
    pass it serves; the legacy kernel gets ``_whole`` after it).
    """
    n, h, wdt, c = x.shape
    r, s, _, k = w.shape
    p = (h + 2 * padding - r) // stride + 1
    q = (wdt + 2 * padding - s) // stride + 1
    rb_p = min(rb_p, p)
    if k_blk is None:
        k_blk = min(k, 128)
    c_blk = c if c_blk in (None, 0) else c_blk
    assert k % k_blk == 0, (k, k_blk)
    assert c % c_blk == 0, (c, c_blk)
    if whole_plane is None:
        from repro import backend as be
        whole_plane = be.get_conv_tiling() == "whole"

    fuse = FuseSpec(bias=bias is not None, bn=scale is not None,
                    residual=residual is not None, relu=relu)
    if fuse.bn:
        assert shift is not None

    p_b = math.ceil(p / rb_p)
    k_b = k // k_blk
    c_b = c // c_blk
    out_dtype = x.dtype

    if whole_plane:
        # the legacy kernel has no C blocking or order freedom — when the
        # "whole" knob overrides a tiled blocking, those axes collapse
        return _conv2d_whole_plane(
            x, w, fuse=fuse, stride=stride, padding=padding, bias=bias,
            scale=scale, shift=shift, residual=residual, rb_p=rb_p,
            k_blk=k_blk, p=p, q=q, r=r, s=s, n=n, k=k, c=c,
            accum_dtype=accum_dtype, out_dtype=out_dtype,
            interpret=interpret, name=name + "_whole")

    cols = tile_cols(q, x.dtype.itemsize)
    xp = phase_planes(x, padding=padding, stride=stride, r=r, s=s, p=p,
                      rb_p=rb_p, cols=cols)
    grid, axis, semantics = _grid_layout(order, n=n, k_b=k_b, p_b=p_b,
                                         c_b=c_b)
    an, ak, ap, ac = (axis[d] for d in "nkpc")

    x_spec, band = band_spec(xp.shape, rb_p=rb_p, r=r, stride=stride,
                             c_blk=c_blk, n_axis=an, p_axis=ap, c_axis=ac)
    tile = (1, rb_p, cols, k_blk)
    in_specs = [x_spec, pl.BlockSpec((r, s, c_blk, k_blk),
                                     lambda *i: (0, 0, i[ac], i[ak]))]
    args = [xp, w]
    blocks = [(band, x.dtype), ((r, s, c_blk, k_blk), w.dtype),
              (tile, out_dtype)]
    for vec in (bias, scale, shift):
        if vec is not None:
            in_specs.append(pl.BlockSpec((1, k_blk), lambda *i: (0, i[ak])))
            args.append(vec.reshape(1, k))
            blocks.append(((1, k_blk), vec.dtype))
    if fuse.residual:
        in_specs.append(pl.BlockSpec(tile,
                                     lambda *i: (i[an], i[ap], 0, i[ak])))
        args.append(jnp.pad(residual, ((0, 0), (0, 0), (0, cols - q),
                                       (0, 0))))
        blocks.append((tile, residual.dtype))

    kern = functools.partial(_kernel_tiled, fuse=fuse, rb_p=rb_p, cols=cols,
                             stride=stride, r=r, s=s, c_axis=ac,
                             accum_dtype=accum_dtype, out_dtype=out_dtype)
    acc = (rb_p * cols, k_blk)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(tile, lambda *i: (i[an], i[ap], 0, i[ak])),
        out_shape=jax.ShapeDtypeStruct((n, p, cols, k), out_dtype),
        scratch_shapes=[pltpu.VMEM(acc, accum_dtype)],
        compiler_params=compiler_params(semantics, blocks=blocks,
                                        scratch=[(acc, accum_dtype)]),
        interpret=interpret,
        name=name,
    )(*args)
    return out[:, :, :q] if cols != q else out


def _conv2d_whole_plane(x, w, *, fuse, stride, padding, bias, scale, shift,
                        residual, rb_p, k_blk, p, q, r, s, n, k, c,
                        accum_dtype, out_dtype, interpret, name):
    """The pre-refactor kernel: whole padded plane per image in VMEM, C and Q
    unblocked, grid (N, K_b, P_b).  Working set scales with H*W*C."""
    xp = pad_input(x, padding=padding, stride=stride, rb_p=rb_p, r=r, p=p)
    hp, wp = xp.shape[1], xp.shape[2]
    p_b = math.ceil(p / rb_p)
    k_b = k // k_blk
    grid = (n, k_b, p_b)

    in_specs = [
        pl.BlockSpec((1, hp, wp, c), lambda ni, ki, pi: (ni, 0, 0, 0)),
        pl.BlockSpec((r, s, c, k_blk), lambda ni, ki, pi: (0, 0, 0, ki)),
    ]
    args = [xp, w]
    if fuse.bias:
        in_specs.append(pl.BlockSpec((1, k_blk), lambda ni, ki, pi: (0, ki)))
        args.append(bias.reshape(1, k))
    if fuse.bn:
        in_specs.append(pl.BlockSpec((1, k_blk), lambda ni, ki, pi: (0, ki)))
        in_specs.append(pl.BlockSpec((1, k_blk), lambda ni, ki, pi: (0, ki)))
        args.extend([scale.reshape(1, k), shift.reshape(1, k)])
    if fuse.residual:
        in_specs.append(pl.BlockSpec((1, rb_p, q, k_blk),
                                     lambda ni, ki, pi: (ni, pi, 0, ki)))
        args.append(residual)

    kern = functools.partial(_kernel_whole, fuse=fuse, rb_p=rb_p, q=q,
                             stride=stride, r=r, s=s, p_axis=2,
                             accum_dtype=accum_dtype, out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rb_p, q, k_blk),
                               lambda ni, ki, pi: (ni, pi, 0, ki)),
        out_shape=jax.ShapeDtypeStruct((n, p, q, k), out_dtype),
        interpret=interpret,
        name=name,
    )(*args)
