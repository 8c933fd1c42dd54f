"""DirectConv2D — the paper's contribution as a composable, differentiable
JAX module.

Forward: implementation-selected direct convolution with fused epilogue.
Backward: custom VJP that implements the paper's training pipeline —
  dI via duality (§II-I): weight transform + the same forward kernel;
  dW via the update-pass kernel (§II-J).

Implementation selection ("xla" / "interpret" / "pallas") is per-call or via
``repro.backend``; blocking comes from ``core.blocking`` unless overridden —
the per-shape JIT specialization of §II-D.  With the autotune knob enabled
("cache"/"tune", see ``repro.tune`` and DESIGN.md §6) the blocking is the
empirically tuned per-shape winner instead of the analytic heuristic.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp

from repro import backend as be
from repro import obs
from repro.core import duality
from repro.core.blocking import conv_blocking
from repro.kernels import ref
from repro.kernels.conv2d_direct import conv2d_direct
from repro.kernels.conv2d_wu import conv2d_wu


def lane_ok(c: int, k: int) -> bool:
    """True when (C, K) block cleanly for the Pallas kernels; small-C layers
    (e.g. ResNet conv1, C=3) take the XLA/im2col path — see DESIGN.md §2.
    Public so warmup/serving can report which signatures the tuned path
    covers (``graph/serving.py``)."""
    return c % 8 == 0 and k % 8 == 0


def conv2d_fwd(x, w, *, stride=1, padding=1, bias=None, scale=None,
               shift=None, residual=None, relu=False, impl=None,
               autotune=None, kind="fwd"):
    """Fused forward conv; dispatches on the selected implementation.

    `autotune` (None -> ``repro.backend`` knob) selects how the blocking is
    chosen: "off" analytic, "cache" tuned-if-cached, "tune" search+persist.
    `kind` is the tuner-cache namespace ("fwd", or "bwd" when this forward
    launch is a backward-data dual conv — same kernel, separately tuned key)
    and names the pass: the kernel and its XLA glue run under the named
    scope ``obs.PASS_OF_KIND[kind]``.
    """
    with jax.named_scope(obs.PASS_OF_KIND[kind]):
        return _conv2d_fwd(x, w, stride=stride, padding=padding, bias=bias,
                           scale=scale, shift=shift, residual=residual,
                           relu=relu, impl=impl, autotune=autotune,
                           kind=kind)


def _conv2d_fwd(x, w, *, stride, padding, bias=None, scale=None, shift=None,
                residual=None, relu=False, impl=None, autotune=None,
                kind="fwd"):
    impl = be.resolve(impl)
    n, h, wdt, c = x.shape
    r, s, _, k = w.shape
    if impl == "xla" or not lane_ok(c, k):
        return ref.conv2d_fused(x, w, stride=stride, padding=padding,
                                bias=bias, scale=scale, shift=shift,
                                residual=residual, relu=relu)
    blk = conv_blocking(h=h, w=wdt, c=c, k=k, r=r, s=s, stride=stride,
                        padding=padding, dtype_bytes=x.dtype.itemsize,
                        backend=impl, autotune=autotune, kind=kind,
                        minibatch=n)
    return conv2d_direct(x, w, stride=stride, padding=padding, bias=bias,
                         scale=scale, shift=shift, residual=residual,
                         relu=relu, rb_p=blk.rb_p, k_blk=blk.k_blk,
                         c_blk=blk.c_blk, order=blk.order,
                         interpret=(impl == "interpret"),
                         name=obs.PASS_OF_KIND[kind])


def conv2d_chain_fwd(x, layers, *, rb, impl=None, autotune=None):
    """Depth-first fused conv chain (DESIGN.md §16): run single-consumer
    conv->conv ``layers`` band-by-band so no intermediate activation
    materializes in HBM.  Per-band dispatch follows the same rule as
    ``conv2d_fwd`` (XLA/non-lane-aligned layers take the reference path),
    with each layer's blocking pinned to its full shape — which makes the
    result bit-identical to the unfused layer-by-layer execution."""
    from repro.kernels.conv2d_chain import conv2d_chain
    with jax.named_scope(obs.CONV_CHAIN):
        return conv2d_chain(x, layers, rb=rb, impl=be.resolve(impl),
                            autotune=autotune)


def conv2d_q8_fwd(x, w_q, *, x_scale, w_scale, stride=1, padding=1,
                  bias=None, scale=None, shift=None, residual=None,
                  relu=False, impl=None, autotune=None):
    """Fused quantized forward conv (§II-K): quantize the f32 activation
    against its calibrated per-tensor scale, run the int8 tiled kernel with
    a per-K-channel dequant + f32 epilogue, return f32.

    XLA / non-lane-aligned fallback: fold the premultiplied dequant scale
    into the reference epilogue's BN-scale slot — ``(acc*deq)*bn + ...`` ==
    ``acc*(deq*bn) + ...``, algebraically identical to the kernel path, so
    the fallback differs only by f32 rounding, not by quantization scheme.
    """
    with jax.named_scope(obs.CONV_Q8):
        return _conv2d_q8_fwd(x, w_q, x_scale=x_scale, w_scale=w_scale,
                              stride=stride, padding=padding, bias=bias,
                              scale=scale, shift=shift, residual=residual,
                              relu=relu, impl=impl, autotune=autotune)


def _conv2d_q8_fwd(x, w_q, *, x_scale, w_scale, stride, padding, bias, scale,
                   shift, residual, relu, impl, autotune):
    from repro.core.quantize import quantize_act
    impl = be.resolve(impl)
    n, h, wdt, c = x.shape
    r, s, _, k = w_q.shape
    x_q = quantize_act(x, x_scale)
    if impl == "xla" or not lane_ok(c, k):
        deq = (jnp.reshape(x_scale, ()).astype(jnp.float32)
               * w_scale.astype(jnp.float32))
        combined = deq if scale is None else deq * scale
        combined_shift = shift if scale is not None else \
            jnp.zeros((k,), jnp.float32)
        # int8 operands as f32: ref.conv2d_fused casts its output to the
        # input dtype, so feeding int8 directly would truncate the result
        return ref.conv2d_fused(x_q.astype(jnp.float32),
                                w_q.astype(jnp.float32), stride=stride,
                                padding=padding, bias=bias, scale=combined,
                                shift=combined_shift, residual=residual,
                                relu=relu)
    blk = conv_blocking(h=h, w=wdt, c=c, k=k, r=r, s=s, stride=stride,
                        padding=padding, dtype_bytes=1, backend=impl,
                        autotune=autotune, kind="q8", minibatch=n)
    from repro.kernels.conv2d_q8 import conv2d_q8
    return conv2d_q8(x_q, w_q, x_scale=x_scale, w_scale=w_scale,
                     stride=stride, padding=padding, bias=bias, scale=scale,
                     shift=shift, residual=residual, relu=relu,
                     rb_p=blk.rb_p, k_blk=blk.k_blk, c_blk=blk.c_blk,
                     order=blk.order,
                     interpret=(impl == "interpret"))


def conv2d_bwd_data_via_fwd(do, w, *, stride, padding, input_hw, impl=None,
                            autotune=None, mode=None):
    """dI using the §II-I duality: transform weights, run the fwd kernel.

    The generic (stride > 1, R,S > 1) case follows ``mode`` / the
    ``REPRO_BWD_DUALITY`` knob: "phase" (default) launches stride² forward
    sub-convs over the *undilated* dO — no dilated intermediate is ever
    allocated; "dilate" is the legacy materialized plan kept for A/B.
    Every forward launch tunes/looks up its blocking under kind "bwd", and
    the whole pass (weight transform, launches, interleave) runs under the
    named scope ``conv_bwd_data``.
    """
    with jax.named_scope(obs.CONV_BWD_DATA):
        return _conv2d_bwd_data_via_fwd(do, w, stride=stride, padding=padding,
                                        input_hw=input_hw, impl=impl,
                                        autotune=autotune, mode=mode)


def _conv2d_bwd_data_via_fwd(do, w, *, stride, padding, input_hw, impl,
                             autotune, mode):
    r, s = w.shape[0], w.shape[1]
    scenario, _ = duality.bwd_data_plan(r=r, s=s, stride=stride,
                                        padding=padding, input_hw=input_hw,
                                        mode=mode)
    if scenario == "phase":
        return duality.phase_bwd_data(
            do, w, stride=stride, padding=padding, input_hw=input_hw,
            conv_fn=lambda a, b, st, pd: _conv2d_fwd(
                a, b, stride=st, padding=pd, impl=impl, autotune=autotune,
                kind="bwd"))
    do2, wt, kw, post = duality.prepare_bwd_data(
        do, w, stride=stride, padding=padding, input_hw=input_hw, mode=mode)
    y = _conv2d_fwd(do2, wt, stride=kw["stride"], padding=kw["padding"],
                    impl=impl, autotune=autotune, kind="bwd")
    return post(y)


def conv2d_bwd_weights(x, do, *, stride, padding, filter_rs, impl=None,
                       autotune=None, whole_plane=None):
    """dW via the update-pass kernel (§II-J).

    The default tiled kernel streams row bands and blocks C/Q with ceil-div
    tails (no divisibility constraints); ``whole_plane`` (default: the
    ``repro.backend`` conv-tiling knob) selects the legacy resident-plane
    kernel, which still needs ``rb_p | P`` (``require_divisor``).  Runs
    under the named scope ``conv_wu``."""
    with jax.named_scope(obs.CONV_WU):
        return _conv2d_bwd_weights(x, do, stride=stride, padding=padding,
                                   filter_rs=filter_rs, impl=impl,
                                   autotune=autotune, whole_plane=whole_plane)


def _conv2d_bwd_weights(x, do, *, stride, padding, filter_rs, impl, autotune,
                        whole_plane):
    impl = be.resolve(impl)
    n, h, wdt, c = x.shape
    _, p, q, k = do.shape
    if impl == "xla" or not lane_ok(c, k):
        return ref.conv2d_bwd_weights(x, do, stride=stride, padding=padding,
                                      filter_rs=filter_rs)
    if whole_plane is None:
        whole_plane = be.get_conv_tiling() == "whole"
    blk = conv_blocking(h=h, w=wdt, c=c, k=k, r=filter_rs[0], s=filter_rs[1],
                        stride=stride, padding=padding,
                        dtype_bytes=x.dtype.itemsize,
                        require_divisor=whole_plane,
                        backend=impl, autotune=autotune, kind="wu",
                        minibatch=n)
    if whole_plane:
        return conv2d_wu(x, do, stride=stride, padding=padding,
                         filter_rs=filter_rs, b_p=blk.rb_p, k_blk=blk.k_blk,
                         whole_plane=True, interpret=(impl == "interpret"))
    return conv2d_wu(x, do, stride=stride, padding=padding,
                     filter_rs=filter_rs, b_p=blk.rb_p, k_blk=blk.k_blk,
                     c_blk=blk.c_blk, whole_plane=False,
                     interpret=(impl == "interpret"))


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def conv2d_train(x, w, stride: int, padding: int, impl: str | None):
    """Differentiable direct conv whose VJP is the paper's bwd pipeline."""
    return conv2d_fwd(x, w, stride=stride, padding=padding, impl=impl)


def _fwd(x, w, stride, padding, impl):
    return conv2d_train(x, w, stride, padding, impl), (x, w)


def _bwd(stride, padding, impl, resid, do):
    x, w = resid
    r, s, _, _ = w.shape
    di = conv2d_bwd_data_via_fwd(do, w, stride=stride, padding=padding,
                                 input_hw=(x.shape[1], x.shape[2]), impl=impl)
    dw = conv2d_bwd_weights(x, do, stride=stride, padding=padding,
                            filter_rs=(r, s), impl=impl)
    return di.astype(x.dtype), dw.astype(w.dtype)


conv2d_train.defvjp(_fwd, _bwd)
