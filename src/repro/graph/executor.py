"""GxM executor: runs an ETG forward for both training and inference
serving.  Functional: params are a pytree keyed by node name.

Training: the backward/update passes come from the conv tasks' custom VJPs
(duality + update-pass kernels); BatchNorm uses batch statistics and
contributes running-stat updates.

Inference/serving: BN is folded into the conv epilogue (scale/shift) — the
fused path the paper benchmarks — and ``make_infer`` exposes it as a
jit-able entry point with a donated input buffer and optional data-parallel
``shard_map`` over a mesh.  ``graph/serving.py`` wraps it with bucketed
batching and cache warmup for the CNN serving path (``launch/serve_cnn.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.conv import (conv2d_chain_fwd, conv2d_train, conv2d_fwd,
                             conv2d_q8_fwd)
from repro.graph.etg import ETG, build_etg


def apply_bn_updates(params, stats, bn_momentum):
    """Fold freshly collected batch statistics into the running BN stats —
    in place, on a params tree the caller owns (the post-SGD tree).  Shared
    by the single-device step and the data-parallel step, where ``stats``
    arrives pre-averaged across shards (``train/distributed.py``)."""
    for name, (mu, var) in stats.items():
        params[name]["mean"] = bn_momentum * params[name]["mean"] \
            + (1 - bn_momentum) * mu
        params[name]["var"] = bn_momentum * params[name]["var"] \
            + (1 - bn_momentum) * var
    return params


def _maxpool(x, window, stride, padding):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1),
        [(0, 0), (padding, padding), (padding, padding), (0, 0)])


class GxM:
    """Graph execution model over an ETG."""

    def __init__(self, nl, *, impl: str | None = None, fuse: bool = True,
                 num_classes: int = 1000, quantized: bool | None = None):
        from repro import backend as be
        if quantized is None:
            quantized = be.get_quantize() == "int8"
        self.etg: ETG = build_etg(nl, fuse=fuse, quantized=quantized)
        self.impl = impl
        self.num_classes = num_classes
        self.quantized = quantized

    # -- parameter init -----------------------------------------------------
    def init(self, rng, dtype=jnp.float32):
        params = {}
        for t in self.etg.tasks:
            a = t.attrs
            if t.op == "conv":
                rng, k1 = jax.random.split(rng)
                fan_in = a["c"] * a["r"] * a["s"]
                w = jax.random.normal(k1, (a["r"], a["s"], a["c"], a["k"]),
                                      dtype) * math.sqrt(2.0 / fan_in)
                p = {"w": w}
                for kind, attrs in t.fused:
                    if kind == "bn":
                        p["scale"] = jnp.ones((a["k"],), dtype)
                        p["shift"] = jnp.zeros((a["k"],), dtype)
                        p["mean"] = jnp.zeros((a["k"],), dtype)   # running
                        p["var"] = jnp.ones((a["k"],), dtype)     # stats
                    elif kind == "bias":
                        p["bias"] = jnp.zeros((a["k"],), dtype)
                params[t.name] = p
            elif t.op == "bn":  # unfused BN
                params[t.name] = {"scale": jnp.ones((a["k"],), dtype),
                                  "shift": jnp.zeros((a["k"],), dtype),
                                  "mean": jnp.zeros((a["k"],), dtype),
                                  "var": jnp.ones((a["k"],), dtype)}
            elif t.op == "fc":
                rng, k1 = jax.random.split(rng)
                w = jax.random.normal(k1, (a["c"], a["k"]), dtype) \
                    * math.sqrt(1.0 / a["c"])
                params[t.name] = {"w": w, "b": jnp.zeros((a["k"],), dtype)}
        return params

    # -- depth-first chains (DESIGN.md §16) ---------------------------------
    def _task(self, name):
        by_name = getattr(self, "_task_by_name", None)
        if by_name is None:
            by_name = self._task_by_name = {t.name: t for t in self.etg.tasks}
        return by_name[name]

    def _plan_chain(self, ch, params, x):
        """Per-chain fuse/fallback decision at the chain's entry task.
        Returns the band plan, or None to run the chain layer-by-layer:
        quantized chains stay unfused (the q8 kernel has its own banding),
        as do chains whose combined band blows ``REPRO_VMEM_BUDGET`` or
        whose fused traffic would exceed the unfused sum."""
        from repro.tune.measure import chain_traffic
        if any("w_q" in params[name] for name in ch.names):
            return None
        h, w = int(x.shape[1]), int(x.shape[2])
        shapes = []
        for name in ch.names:
            a = self._task(name).attrs
            shapes.append(dict(h=h, w=w, c=a["c"], k=a["k"], r=a["r"],
                               s=a["s"], stride=a["stride"],
                               padding=a["padding"],
                               dtype_bytes=x.dtype.itemsize))
            h = (h + 2 * a["padding"] - a["r"]) // a["stride"] + 1
            w = (w + 2 * a["padding"] - a["s"]) // a["stride"] + 1
        t = chain_traffic(shapes, minibatch=int(x.shape[0]))
        return {"rb": t["rb"]} if t["fused"] else None

    def _chain_layer(self, name, params, get, folded):
        """Assemble one chain layer's kernel+epilogue dict — the same
        BN-fold / bias / residual / relu the unfused inference branch
        passes to ``conv2d_fwd``, so the fused replay is bit-identical."""
        t = self._task(name)
        p = params[name]
        a = t.attrs
        layer = dict(w=p["w"], stride=a["stride"], padding=a["padding"])
        for kind, attrs in t.fused:
            if kind == "bn":
                layer["scale"], layer["shift"] = folded(p)
            elif kind == "bias":
                layer["bias"] = p["bias"]
            elif kind == "relu":
                layer["relu"] = True
            elif kind == "add":
                layer["residual"] = get(attrs["residual"])
        return layer

    # -- forward ------------------------------------------------------------
    def forward(self, params, x, *, train: bool = True,
                collect_stats: bool = False, tap=None):
        """Inference folds the *running* BN statistics into the conv
        epilogue (scale' = g/sqrt(var+eps), shift' = b - g*mean/sqrt(var+eps))
        — the paper's §II-G fused-BN; training uses batch statistics and,
        with ``collect_stats``, also returns them for the running update.

        ``tap(name, inp)`` is called with every conv task's input tensor —
        the calibration hook (``core.quantize.calibrate_network``); it has
        side effects, so run tapped forwards eagerly, not under jit."""
        tensors = {"input": x}
        stats = {}

        def get(name):
            return tensors[name]

        def folded(p):
            inv = jax.lax.rsqrt(p["var"] + 1e-5)
            return p["scale"] * inv, p["shift"] - p["scale"] * p["mean"] * inv

        # depth-first chain fusion (DESIGN.md §16): inference-only, behind
        # the REPRO_CHAIN_FUSION knob; calibration taps need every per-layer
        # input, so a tapped forward always runs layer-by-layer
        from repro import backend as be
        chain_of = {}
        if (not train and tap is None and self.etg.chains
                and be.get_chain_fusion() == "on"):
            for ch in self.etg.chains:
                for pos, name in enumerate(ch.names):
                    chain_of[name] = (ch, pos)
        chain_plans: dict = {}

        for t in self.etg.tasks:
            if t.op == "input":
                continue
            # every op of the task, and of its transpose, carries its name
            with jax.named_scope(t.name):
                a = t.attrs
                if t.op == "conv" and t.name in chain_of:
                    ch, pos = chain_of[t.name]
                    if pos == 0:
                        # decide once per chain, at its entry (the input
                        # tensor's spatial shape is known here): fuse iff the
                        # combined band fits VMEM and fusion is profitable
                        chain_plans[ch.names] = self._plan_chain(
                            ch, params, get(t.inputs[0]))
                    plan = chain_plans[ch.names]
                    if plan is None:
                        pass                    # fallback: run layer-by-layer
                    elif pos < len(ch.names) - 1:
                        continue                # band stays live in the replay
                    else:
                        out = conv2d_chain_fwd(
                            get(self._task(ch.names[0]).inputs[0]),
                            [self._chain_layer(n2, params, get, folded)
                             for n2 in ch.names],
                            rb=plan["rb"], impl=self.impl)
                        tensors[t.name] = out
                        if "output_name" in a:
                            tensors[a["output_name"]] = out
                        continue
                if t.op == "conv":
                    inp = get(t.inputs[0])
                    if tap is not None:
                        tap(t.name, inp)
                    p = params[t.name]
                    kw = dict(stride=a["stride"], padding=a["padding"])
                    scale = shift = bias = residual = None
                    relu = False
                    for kind, attrs in t.fused:
                        if kind == "bn":
                            scale, shift = p["scale"], p["shift"]
                        elif kind == "bias":
                            bias = p["bias"]
                        elif kind == "relu":
                            relu = True
                        elif kind == "add":
                            residual = get(attrs["residual"])
                    if train:
                        if "w_q" in p:
                            raise ValueError(
                                f"conv {t.name} holds quantized weights "
                                f"(w_q); the q8 path is inference-only — "
                                f"train with the f32 params tree")
                        # training path: paper bwd pipeline via custom VJP;
                        # normalization handled outside the kernel (batch
                        # stats)
                        y = conv2d_train(inp, p["w"], a["stride"],
                                         a["padding"], self.impl)
                        if scale is not None:
                            with jax.named_scope("bn"):
                                mu = y.mean(axis=(0, 1, 2))
                                var = y.var(axis=(0, 1, 2))
                                stats[t.name] = (mu, var)
                                y = (y - mu) * jax.lax.rsqrt(var + 1e-5)
                                y = y * scale + shift
                        if bias is not None:
                            y = y + bias
                        if residual is not None:
                            y = y + residual
                        if relu:
                            y = jnp.maximum(y, 0)
                    else:
                        # inference: everything fused into the kernel epilogue,
                        # BN folded from running stats
                        if scale is not None:
                            scale, shift = folded(p)
                        if a.get("kernel_kind") == "q8" and "w_q" in p:
                            # §II-K quantized path: int8 kernel, f32 epilogue.
                            # A q8-marked task with f32 params (no w_q) falls
                            # through to the f32 kernel — the calibration
                            # pass.
                            y = conv2d_q8_fwd(inp, p["w_q"],
                                              x_scale=p["x_scale"],
                                              w_scale=p["w_scale"], bias=bias,
                                              scale=scale, shift=shift,
                                              residual=residual, relu=relu,
                                              impl=self.impl, **kw)
                        else:
                            y = conv2d_fwd(inp, p["w"], bias=bias, scale=scale,
                                           shift=shift, residual=residual,
                                           relu=relu, impl=self.impl, **kw)
                    out = y
                elif t.op == "bn":
                    y = get(t.inputs[0])
                    p = params[t.name]
                    with jax.named_scope("bn"):
                        if train:
                            mu = y.mean(axis=(0, 1, 2))
                            var = y.var(axis=(0, 1, 2))
                            stats[t.name] = (mu, var)
                        else:
                            mu, var = p["mean"], p["var"]
                        out = (y - mu) * jax.lax.rsqrt(var + 1e-5) \
                            * p["scale"] + p["shift"]
                elif t.op == "relu":
                    out = jnp.maximum(get(t.inputs[0]), 0)
                elif t.op == "add":
                    out = get(t.inputs[0]) + get(t.inputs[1])
                elif t.op == "split":
                    out = get(t.inputs[0])
                elif t.op == "concat":
                    out = jnp.concatenate([get(i) for i in t.inputs], axis=-1)
                elif t.op == "maxpool":
                    out = _maxpool(get(t.inputs[0]), a["window"], a["stride"],
                                   a["padding"])
                elif t.op == "avgpool":
                    out = get(t.inputs[0]).mean(axis=(1, 2))
                elif t.op == "fc":
                    p = params[t.name]
                    # f32 at full precision on every backend, like the conv
                    # kernels (a TPU's default f32 matmul takes bf16 passes)
                    out = jnp.dot(get(t.inputs[0]), p["w"],
                                  precision=jax.lax.Precision.HIGHEST) + p["b"]
                else:
                    raise ValueError(f"unknown op {t.op}")
                tensors[t.name] = out
                if "output_name" in a:
                    tensors[a["output_name"]] = out
        result = tensors[self.etg.tasks[-1].name]
        if collect_stats:
            return result, stats
        return result

    # -- inference serving entry ---------------------------------------------
    def infer(self, params, x):
        """Inference forward: BN folded from running stats, fused epilogues."""
        return self.forward(params, x, train=False)

    def make_infer(self, *, mesh=None, axis: str = "data",
                   donate_input: bool = True):
        """Jit'd inference entry point for the serving path.

        With ``mesh``, the batch is data-parallel sharded over ``axis`` via
        ``shard_map`` (params replicated); the caller guarantees the batch
        divides the axis size (``graph/serving.py`` buckets do).  The image
        buffer is donated — serving re-pads a fresh batch every step, so the
        executor may reuse its memory for activations.
        """
        fwd = self.infer
        if mesh is not None:
            P = jax.sharding.PartitionSpec
            fwd = jax.shard_map(fwd, mesh=mesh, in_specs=(P(), P(axis)),
                                out_specs=P(axis), check_vma=False)
        return jax.jit(fwd, donate_argnums=(1,) if donate_input else ())

    # -- loss / steps ---------------------------------------------------------
    def loss(self, params, batch, *, train=True, collect_stats=False):
        out = self.forward(params, batch["image"], train=train,
                           collect_stats=collect_stats)
        logits, stats = out if collect_stats else (out, None)
        labels = jax.nn.one_hot(batch["label"], logits.shape[-1])
        l = -jnp.mean(jnp.sum(labels * jax.nn.log_softmax(logits), -1))
        if collect_stats:
            return l, stats
        return l

    def sgd_train_step(self, params, batch, lr=0.1, *, bn_momentum=0.9):
        (loss, stats), grads = jax.value_and_grad(
            self.loss, has_aux=True)(params, batch, collect_stats=True)
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        # running BN statistics (non-gradient state)
        apply_bn_updates(new, stats, bn_momentum)
        return new, loss
