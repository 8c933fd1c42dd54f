"""Plain reference of ResNet v1.5 (He et al. 2016, arXiv:1512.03385, Table 1;
stride on the 3x3 conv of each bottleneck, as torchvision ``resnet50`` and
the MLPerf reference), written in ``jax.numpy`` and ``lax`` alone.

It imports nothing of the system under test.  It owns the weight layout the
benchmark hands to both sides: a dict keyed by layer name, each conv with
``w`` (R, S, C, K) and its batch norm's ``scale``, ``shift``, ``mean`` and
``var``; the classifier ``fc`` with ``w`` (C, K) and ``b``.

Departures from the published network, none of which the paper's Table 1
fixes: batch-norm epsilon 1e-5, biased batch variance, no weight decay;
the classifier is a dense layer on the global average pool.

``precision`` is ``"highest"`` (f32 products, what the configuration
states) or ``"high3"``: every conv and dot as three bf16 passes (hi*hi +
hi*lo + lo*hi), forward and backward, which is what ``Precision.HIGH``
does on a TPU.  ``high3`` is the control the limits are set against.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5
_DN = ("NHWC", "HWIO", "NHWC")
_HI = lax.Precision.HIGHEST


# -- topology -----------------------------------------------------------------

def conv_layers(cfg: dict, image_hw) -> list[dict]:
    """Every conv of the network in execution order: name, input plane
    (h, w), channels (c, k), filter (r, s), stride, padding, output plane
    (p, q) and whether a residual is added in its epilogue."""
    def out(n, f, st, pd):
        return (n + 2 * pd - f) // st + 1

    layers = []

    def add(name, h, w, c, k, r, st, pd, residual=False):
        p, q = out(h, r, st, pd), out(w, r, st, pd)
        layers.append(dict(name=name, h=h, w=w, c=c, k=k, r=r, s=r,
                           stride=st, padding=pd, p=p, q=q,
                           residual=residual))
        return p, q

    h, w = image_hw
    stem = cfg["stem"]
    h, w = add("conv1", h, w, 3, stem["width"], stem["kernel"],
               stem["stride"], stem["kernel"] // 2)
    pool = cfg["stem_pool"]
    h = out(h, pool["window"], pool["stride"], pool["padding"])
    w = out(w, pool["window"], pool["stride"], pool["padding"])
    c_in = stem["width"]
    for si, (blocks, c_mid) in enumerate(zip(cfg["stages"], cfg["widths"])):
        c_out = c_mid * cfg["expansion"]
        for b in range(blocks):
            st = 2 if (b == 0 and si > 0) else 1
            pre = f"s{si}b{b}"
            add(f"{pre}_c1", h, w, c_in, c_mid, 1, 1, 0)
            p, q = add(f"{pre}_c2", h, w, c_mid, c_mid, 3, st, 1)
            if st != 1 or c_in != c_out:
                add(f"{pre}_proj", h, w, c_in, c_out, 1, st, 0)
            add(f"{pre}_c3", p, q, c_mid, c_out, 1, 1, 0, residual=True)
            h, w, c_in = p, q, c_out
    return layers


def classifier(cfg: dict) -> tuple[int, int]:
    """(C, K) of the dense classifier."""
    return cfg["widths"][-1] * cfg["expansion"], cfg["num_classes"]


# -- weights ------------------------------------------------------------------

def init_params(cfg: dict, key) -> dict:
    """Seeded weights: He-normal convs, batch-norm affine and running
    statistics drawn near (1, 0, 0, 1) so that folding them is exercised,
    and a normal classifier.  Traced under ``jit`` by the harness."""
    params = {}
    layers = conv_layers(cfg, (cfg["image"], cfg["image"]))
    keys = jax.random.split(key, len(layers) + 1)
    for lk, lay in zip(keys[:-1], layers):
        kw, ks, kb, km, kv = jax.random.split(lk, 5)
        k = lay["k"]
        fan_in = lay["c"] * lay["r"] * lay["s"]
        params[lay["name"]] = {
            "w": jax.random.normal(kw, (lay["r"], lay["s"], lay["c"], k))
            * math.sqrt(2.0 / fan_in),
            "scale": 1.0 + 0.1 * jax.random.normal(ks, (k,)),
            "shift": 0.1 * jax.random.normal(kb, (k,)),
            "mean": 0.1 * jax.random.normal(km, (k,)),
            "var": jax.random.uniform(kv, (k,), minval=0.5, maxval=1.5),
        }
    c, k = classifier(cfg)
    kw, kb = jax.random.split(keys[-1])
    params["fc"] = {"w": jax.random.normal(kw, (c, k)) * math.sqrt(1.0 / c),
                    "b": 0.01 * jax.random.normal(kb, (k,))}
    return params


# -- arithmetic at a stated precision -----------------------------------------

def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _three_pass(op):
    """``op(a, b)`` (bilinear, f32 at HIGHEST) as three bf16 passes in the
    forward and in both cotangents."""
    @jax.custom_vjp
    def f(a, b):
        (ah, al), (bh, bl) = _split(a), _split(b)
        return op(ah, bh) + op(ah, bl) + op(al, bh)

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        (ah, al), (bh, bl), (gh, gl) = _split(a), _split(b), _split(g)

        def da(bb, gg):
            return jax.vjp(lambda t: op(t, bb), a)[1](gg)[0]

        def db(aa, gg):
            return jax.vjp(lambda t: op(aa, t), b)[1](gg)[0]
        return (da(bh, gh) + da(bl, gh) + da(bh, gl),
                db(ah, gh) + db(al, gh) + db(ah, gl))

    f.defvjp(fwd, bwd)
    return f


def _ops(precision: str):
    def conv(x, w, stride, pad):
        return lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=_DN, precision=_HI)

    def dot(a, b):
        return jnp.dot(a, b, precision=_HI)

    if precision == "highest":
        return conv, dot
    if precision == "high3":
        def conv3(x, w, stride, pad):
            return _three_pass(lambda a, b: conv(a, b, stride, pad))(x, w)
        return conv3, _three_pass(dot)
    raise ValueError(f"unknown precision {precision!r}")


# -- forward, loss, step ------------------------------------------------------

def forward(cfg: dict, params: dict, x, *, train: bool,
            precision: str = "highest"):
    """Logits of images ``x`` (N, H, W, 3).  ``train``: batch statistics,
    returned beside the logits as {layer: (mean, var)}; else the running
    statistics."""
    conv, dot = _ops(precision)
    stats = {}

    def conv_bn(name, h, lay, residual=None, relu=True):
        p = params[name]
        y = conv(h, p["w"], lay["stride"], lay["padding"])
        if train:
            mu, var = y.mean(axis=(0, 1, 2)), y.var(axis=(0, 1, 2))
            stats[name] = (mu, var)
            y = (y - mu) * lax.rsqrt(var + EPS) * p["scale"] + p["shift"]
        else:
            inv = lax.rsqrt(p["var"] + EPS)
            y = y * (p["scale"] * inv) + (p["shift"]
                                          - p["scale"] * p["mean"] * inv)
        if residual is not None:
            y = y + residual
        return jnp.maximum(y, 0) if relu else y

    by_name = {lay["name"]: lay for lay in conv_layers(
        cfg, (x.shape[1], x.shape[2]))}
    h = conv_bn("conv1", x, by_name["conv1"])
    pool = cfg["stem_pool"]
    pd = pool["padding"]
    h = lax.reduce_window(h, -jnp.inf, lax.max,
                          (1, pool["window"], pool["window"], 1),
                          (1, pool["stride"], pool["stride"], 1),
                          [(0, 0), (pd, pd), (pd, pd), (0, 0)])
    for si, blocks in enumerate(cfg["stages"]):
        for b in range(blocks):
            pre = f"s{si}b{b}"
            skip = h
            if f"{pre}_proj" in by_name:
                skip = conv_bn(f"{pre}_proj", h, by_name[f"{pre}_proj"],
                               relu=False)
            y = conv_bn(f"{pre}_c1", h, by_name[f"{pre}_c1"])
            y = conv_bn(f"{pre}_c2", y, by_name[f"{pre}_c2"])
            h = conv_bn(f"{pre}_c3", y, by_name[f"{pre}_c3"], residual=skip)
    logits = dot(h.mean(axis=(1, 2)), params["fc"]["w"]) + params["fc"]["b"]
    return (logits, stats) if train else logits


def loss(cfg, params, batch, precision="highest"):
    logits, stats = forward(cfg, params, batch["image"], train=True,
                            precision=precision)
    logp = jax.nn.log_softmax(logits)
    picked = jnp.take_along_axis(logp, batch["label"][:, None], axis=1)
    return -jnp.mean(picked), stats


def grads(cfg, params, batch, precision="highest"):
    """(loss, batch statistics, gradients) of one batch."""
    (lv, st), g = jax.value_and_grad(
        lambda p: loss(cfg, p, batch, precision), has_aux=True)(params)
    return lv, st, g


def apply(params, stats, g, *, lr: float, bn_momentum: float):
    """SGD ``p - lr * g`` on every leaf, then the running statistics
    moved toward the batch statistics by ``1 - bn_momentum``."""
    new = jax.tree.map(lambda p, d: p - lr * d, params, g)
    for name, (mu, var) in stats.items():
        new[name]["mean"] = bn_momentum * params[name]["mean"] \
            + (1 - bn_momentum) * mu
        new[name]["var"] = bn_momentum * params[name]["var"] \
            + (1 - bn_momentum) * var
    return new
