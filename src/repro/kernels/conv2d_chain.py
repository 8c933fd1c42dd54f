"""Depth-first chain replay — DESIGN.md §16 (paper §II-G/§II-H, one level up).

Executes a single-consumer conv->conv chain band by band: layer l+1's output
band is computed from layer l's output band while that band is still live in
VMEM scratch, so the intermediate activation never materializes in HBM.  The
interleaved step order, per-step output-row ranges, and the FLAG_HANDOFF
discipline come from ``core.streams.build_chain_schedule`` — this module is
the replay half; the band math lives in the dryrun.

Bit-exactness contract (the conformance wall in ``tests/test_chain_fusion.py``
asserts ``assert_array_equal`` against the unfused path): every band step
calls the *same* per-layer kernel the unfused path would, with the blocking
computed from the *full* layer shape.  ``conv2d_direct``'s per-output-element
f32 reduction order depends only on ``c_blk`` (C-block visits, then r, s,
dot-inner-c) — not on the band split — so pinning the full-shape blocking
makes the band-by-band result bit-identical, on the Pallas path and on the
XLA/reference fallback alike.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.blocking import conv_blocking
from repro.core.streams import FLAG_HANDOFF, build_chain_schedule
from repro.kernels import ref
from repro.kernels.conv2d_direct import conv2d_direct


def _lane_ok(c: int, k: int) -> bool:
    # mirrors core.conv.lane_ok (not imported: core.conv imports this package)
    return c % 8 == 0 and k % 8 == 0


def _band_conv(xb, L, blk, impl, residual):
    """One band micro-conv: same dispatch rule as ``core.conv.conv2d_fwd``,
    with the full-shape blocking passed explicitly.  ``xb`` arrives fully
    zero-padded (H plane edges + W), so the conv itself runs padding=0."""
    w = L["w"]
    c, k = w.shape[2], w.shape[3]
    kw = dict(stride=L["stride"], padding=0, bias=L.get("bias"),
              scale=L.get("scale"), shift=L.get("shift"),
              residual=residual, relu=L.get("relu", False))
    if impl == "xla" or not _lane_ok(c, k):
        return ref.conv2d_fused(xb, w, **kw)
    return conv2d_direct(xb, w, rb_p=blk.rb_p, k_blk=blk.k_blk,
                         c_blk=blk.c_blk, order=blk.order,
                         interpret=(impl == "interpret"), name="conv_chain",
                         **kw)


def conv2d_chain(x, layers, *, rb: int, impl: str, autotune=None):
    """Run a fused conv chain depth-first.  x: (N,H,W,C) chain input;
    ``layers``: per-conv dicts with ``w`` (R,S,C,K) and the fused-epilogue
    params (stride, padding, bias, scale, shift, residual, relu), producers
    first.  ``rb`` is the final-layer output rows per band
    (``core.blocking.chain_blocking`` picks it); returns the final layer's
    (N,P,Q,K) output, bit-identical to the unfused layer-by-layer path.
    """
    n, h, wd, _ = x.shape
    rs = [(L["w"].shape[0], L["stride"], L["padding"]) for L in layers]
    sched = build_chain_schedule(rs=rs, h_in=h, rb=rb)

    # full-shape per-layer blocking — the bit-exactness anchor (esp. c_blk)
    blks, h_ins, w_cur = [], [], wd
    h_cur = h
    for L in layers:
        r, s, c, k = L["w"].shape
        stride, pad = L["stride"], L["padding"]
        blks.append(conv_blocking(h=h_cur, w=w_cur, c=c, k=k, r=r, s=s,
                                  stride=stride, padding=pad,
                                  dtype_bytes=x.dtype.itemsize, backend=impl,
                                  autotune=autotune, kind="fwd", minibatch=n))
        h_ins.append(h_cur)
        h_cur = (h_cur + 2 * pad - r) // stride + 1
        w_cur = (w_cur + 2 * pad - s) // stride + 1

    live = {}           # layer -> (o0, o1, band) awaiting hand-off
    out_bands = []
    for i in range(len(sched)):
        l = int(sched.layer_ids[i])
        o0, o1 = int(sched.o0[i]), int(sched.o1[i])
        r, stride, pad = rs[l]
        # input rows for out rows [o0, o1), in padded coords then clipped
        a, b = o0 * stride, (o1 - 1) * stride + r
        i0, i1 = max(a - pad, 0), min(b - pad, h_ins[l])
        pt, pb = i0 + pad - a, b - pad - i1
        if l == 0:
            src = x[:, i0:i1]
        else:
            po0, _po1, prev = live[l - 1]
            src = prev[:, i0 - po0:i1 - po0]
        xb = jnp.pad(src, ((0, 0), (pt, pb), (pad, pad), (0, 0)))
        resid = layers[l].get("residual")
        yb = _band_conv(xb, layers[l], blks[l], impl,
                        None if resid is None else resid[:, o0:o1])
        if sched.flags[i] & FLAG_HANDOFF:
            live[l] = (o0, o1, yb)      # stays in VMEM; next step consumes it
        else:
            out_bands.append(yb)        # final layer: the only HBM write-back
    return jnp.concatenate(out_bands, axis=1)
