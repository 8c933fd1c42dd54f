"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth used by tests (``assert_allclose`` against
``interpret=True`` kernel runs) and by the CPU dry-run path (the XLA-native
implementation that the 512-device lowering uses — Mosaic kernels only lower
on real TPUs).

The convolutions run at ``Precision.HIGHEST``, like the Pallas kernels: a
TPU's default f32 convolution takes bf16 passes, which would make neither an
oracle nor the f32 path of the model.

Conventions (TPU adaptation of the paper's blocked layouts, see DESIGN.md §2):
  activations  : NHWC   (C innermost = lane dimension)
  weights      : RSCK   (K innermost = lane dimension)
  conv output  : NPQK
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# Direct convolution (paper §II-A..D)
# ---------------------------------------------------------------------------

def conv2d(x, w, *, stride: int = 1, padding: int = 0,
           accum_dtype=jnp.float32):
    """Forward conv. x: (N,H,W,C), w: (R,S,C,K) -> (N,P,Q,K)."""
    out = lax.conv_general_dilated(
        x.astype(accum_dtype), w.astype(accum_dtype),
        window_strides=(stride, stride),
        padding=[(padding, padding), (padding, padding)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    )
    return out.astype(x.dtype)


def conv2d_fused(x, w, *, stride: int = 1, padding: int = 0,
                 bias=None, scale=None, shift=None, residual=None,
                 relu: bool = False, accum_dtype=jnp.float32):
    """Conv with the paper's §II-G fused epilogue:
    O = act(scale * conv(x,w) + shift + bias [+ residual]).

    ``scale``/``shift`` fold an inference-mode batchnorm; ``bias`` is the conv
    bias; ``residual`` is an eltwise skip-connection add; ``relu`` the
    activation.  All optional, composable — exactly the L() fusion set.
    """
    out = lax.conv_general_dilated(
        x.astype(accum_dtype), w.astype(accum_dtype),
        window_strides=(stride, stride),
        padding=[(padding, padding), (padding, padding)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    )
    if scale is not None:
        out = out * scale.astype(accum_dtype)
    if shift is not None:
        out = out + shift.astype(accum_dtype)
    if bias is not None:
        out = out + bias.astype(accum_dtype)
    if residual is not None:
        out = out + residual.astype(accum_dtype)
    if relu:
        out = jnp.maximum(out, 0)
    return out.astype(x.dtype)


def conv2d_bwd_data(do, w, *, stride: int = 1, padding: int = 0,
                    input_hw, in_channels=None, accum_dtype=jnp.float32):
    """dI from dO and W (paper §II-I).  do: (N,P,Q,K), w: (R,S,C,K).

    Oracle = exact VJP of the forward reference (autodiff ground truth);
    the *kernel* path implements the paper's duality transform and is
    validated against this.
    """
    n = do.shape[0]
    r, s, c, _ = w.shape
    h, wdt = input_hw
    x0 = jnp.zeros((n, h, wdt, c), dtype=accum_dtype)
    _, vjp = jax.vjp(
        lambda x: conv2d(x, w.astype(accum_dtype), stride=stride,
                         padding=padding, accum_dtype=accum_dtype), x0)
    (di,) = vjp(do.astype(accum_dtype))
    return di.astype(do.dtype)


def conv2d_bwd_weights(x, do, *, stride: int = 1, padding: int = 0,
                       filter_rs=None, accum_dtype=jnp.float32):
    """dW from I and dO (paper §II-J).  Returns (R,S,C,K).

    Oracle = exact VJP of the forward reference w.r.t. the weights.
    `filter_rs` disambiguates the filter size for strided convs.
    """
    n, h, wdt, c = x.shape
    _, p, q, k = do.shape
    if filter_rs is not None:
        r, s = filter_rs
    else:
        r = h + 2 * padding - (p - 1) * stride
        s = wdt + 2 * padding - (q - 1) * stride
    w0 = jnp.zeros((r, s, c, k), dtype=accum_dtype)
    _, vjp = jax.vjp(
        lambda w: conv2d(x.astype(accum_dtype), w, stride=stride,
                         padding=padding, accum_dtype=accum_dtype), w0)
    (dw,) = vjp(do.astype(accum_dtype))
    return dw.astype(x.dtype)


# ---------------------------------------------------------------------------
# Fused blocked matmul (LM hot path; paper's small-GEMM chain generalized)
# ---------------------------------------------------------------------------

def matmul_fused(a, b, *, bias=None, act: str = "none",
                 residual=None, accum_dtype=jnp.float32):
    """act(a @ b + bias [+ residual]).  a: (M,K), b: (K,N)."""
    out = jnp.dot(a.astype(accum_dtype), b.astype(accum_dtype),
                  preferred_element_type=accum_dtype)
    if bias is not None:
        out = out + bias.astype(accum_dtype)
    if residual is not None:
        out = out + residual.astype(accum_dtype)
    if act == "relu":
        out = jnp.maximum(out, 0)
    elif act == "gelu":
        out = jax.nn.gelu(out)
    elif act == "silu":
        out = jax.nn.silu(out)
    elif act != "none":
        raise ValueError(act)
    return out.astype(a.dtype)


# ---------------------------------------------------------------------------
# Depthwise causal conv1d (Mamba mixer; the one conv on an assigned-arch path)
# ---------------------------------------------------------------------------

def conv1d_causal(x, w, *, bias=None, act: str = "silu"):
    """x: (B,L,D), w: (KW,D) depthwise causal; left-pad KW-1."""
    kw, d = w.shape
    xp = jnp.pad(x, ((0, 0), (kw - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(kw):
        out = out + xp[:, i:i + x.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if act == "silu":
        out = jax.nn.silu(out)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked (flash-style) attention
# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal: bool = True, scale=None,
              accum_dtype=jnp.float32):
    """q: (B,Hq,L,Dh), k/v: (B,Hkv,L,Dh), GQA by head repeat. -> (B,Hq,L,Dh)."""
    b, hq, l, dh = q.shape
    hkv = k.shape[1]
    if scale is None:
        scale = dh ** -0.5
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(accum_dtype),
                        k.astype(accum_dtype)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((l, l), dtype=bool))
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(accum_dtype))
    return out.astype(q.dtype)


def attention_chunked(q, k, v, *, causal: bool = True, scale=None,
                      chunk: int = 512, accum_dtype=jnp.float32):
    """Memory-efficient attention: lax.map over query chunks, with the chunk
    body rematerialized — peak memory O(chunk × L) instead of O(L²).  This
    is the XLA-native flash formulation used by the 512-device dry-run (the
    Pallas kernel is the TPU version of the same blocking)."""
    b, hq, l, dh = q.shape
    hkv = k.shape[1]
    if scale is None:
        scale = dh ** -0.5
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    chunk = min(chunk, l)
    if l % chunk:
        return attention(q, k, v, causal=causal, scale=scale,
                         accum_dtype=accum_dtype)
    n = l // chunk
    qc = q.reshape(b, hq, n, chunk, dh).transpose(2, 0, 1, 3, 4)

    kpos = jnp.arange(l)

    @jax.checkpoint
    def body(args):
        qi, i = args
        logits = jnp.einsum("bhqd,bhkd->bhqk", qi.astype(accum_dtype),
                            k.astype(accum_dtype)) * scale
        if causal:
            qpos = i * chunk + jnp.arange(chunk)
            mask = qpos[:, None] >= kpos[None, :]
            logits = jnp.where(mask[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs,
                          v.astype(accum_dtype)).astype(q.dtype)

    oc = jax.lax.map(body, (qc, jnp.arange(n)))
    return oc.transpose(1, 2, 0, 3, 4).reshape(b, hq, l, dh)


# ---------------------------------------------------------------------------
# Grouped matmul for MoE dispatch (kernel-streams analog, paper §II-H)
# ---------------------------------------------------------------------------

def moe_gmm(tokens, weights, group_sizes):
    """Grouped matmul.  tokens: (T, D) sorted by expert; weights: (E, D, F);
    group_sizes: (E,) ints summing to T.  Row t uses expert e(t)."""
    t, d = tokens.shape
    e, _, f = weights.shape
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    row = jnp.arange(t)
    # expert id per row
    eid = jnp.sum(row[:, None] >= ends[None, :], axis=1)
    w_per_row = weights[eid]                       # (T, D, F)
    out = jnp.einsum("td,tdf->tf", tokens.astype(jnp.float32),
                     w_per_row.astype(jnp.float32))
    return out.astype(tokens.dtype)
