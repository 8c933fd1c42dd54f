"""Data-parallel CNN training over GxM (DESIGN.md §11).

The paper's closing claim is that the JIT-optimized conv kernels integrate
into "a lightweight multi-node graph execution model" with high efficiency
at scale.  PR 2 sharded the *inference* half of that claim; this module is
the training half: the PR-4 pipeline (tiled fwd → phase-duality dI →
band-streamed wu) runs per-shard under ``shard_map`` over the data axis of
a ``launch.mesh`` mesh, and the only cross-shard communication is the
gradient reduction between the update pass and the optimizer — exactly
where ``graph/etg.extend_nl`` marks the bwd reduction point of a fan-out
tensor.

Reduction wire format (``REPRO_GRAD_COMPRESS`` / ``grad_compress=``):

  "off"   exact f32 ``lax.pmean`` — bit-reproducible layer math per shard
  "int8"  ``optim.compress.compressed_psum`` per leaf — error-feedback int8
          quantization at 1/4 the bytes; each shard's quantization error
          lives in the train state (``state["residual"]``, one accumulator
          per shard, leading ``(n_shards,)`` axis sharded over the data
          axis) and is re-applied to the next step's gradient.

Microbatch gradient accumulation (``accum_steps``) mirrors the LM step's
§II-J pipelining: the reduction of microbatch i overlaps the compute of
i+1 under the XLA latency-hiding scheduler.

Checkpointing reuses ``train/checkpoint.py`` unchanged — leaves are
gathered on save, and ``cnn_state_shardings`` gives restore the target
placement; ``train.fault_tolerance.elastic_reshard_cnn`` re-shards a saved
state onto a narrower mesh (the residual is sum-folded so no error mass is
lost — ``optim.compress.fold_residual``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.graph.executor import apply_bn_updates
from repro.launch.mesh import data_axis_size
from repro.optim.compress import compressed_psum_tree, fold_residual


# -- train state --------------------------------------------------------------

def init_cnn_train_state_dp(params, mesh, *, grad_compress: str | None = None,
                            axis: str = "data"):
    """Sharded DP train state: replicated params + step counter, plus (int8
    reduction only) the per-shard error-feedback residual, stacked on a
    leading ``(n_shards,)`` axis and sharded over ``axis``."""
    from repro import backend as be
    compress = be.resolve_grad_compress(grad_compress)
    n = data_axis_size(mesh)
    state = {"params": params, "step": jnp.zeros((), jnp.int32)}
    if compress == "int8":
        state["residual"] = jax.tree.map(
            lambda p: jnp.zeros((n, *p.shape), jnp.float32), params)
    return jax.device_put(state, cnn_state_shardings(mesh, state, axis=axis))


def cnn_state_specs(state, *, axis: str = "data"):
    """Per-leaf PartitionSpec tree for a DP CNN train state."""
    P = jax.sharding.PartitionSpec
    specs = {"params": jax.tree.map(lambda _: P(), state["params"]),
             "step": P()}
    if "residual" in state:
        specs["residual"] = jax.tree.map(lambda _: P(axis),
                                         state["residual"])
    return specs


def cnn_state_shardings(mesh, state, *, axis: str = "data"):
    """NamedSharding tree matching ``state`` — the ``shardings=`` argument
    of ``checkpoint.restore`` (mesh-elastic restore path)."""
    P = jax.sharding.PartitionSpec
    return jax.tree.map(
        lambda spec: jax.sharding.NamedSharding(mesh, spec),
        cnn_state_specs(state, axis=axis),
        is_leaf=lambda x: isinstance(x, P))


def reshard_cnn_state(state, mesh, *, axis: str = "data"):
    """Place a (restored, unsharded) DP train state onto ``mesh``, folding
    the error-feedback residual to the new data-axis width first."""
    state = dict(state)
    if "residual" in state:
        state["residual"] = fold_residual(state["residual"],
                                          data_axis_size(mesh))
    return jax.device_put(state, cnn_state_shardings(mesh, state, axis=axis))


# -- the step -----------------------------------------------------------------

def make_cnn_train_step_dp(gxm, mesh, *, lr: float = 0.1,
                           bn_momentum: float = 0.9, accum_steps: int = 1,
                           grad_compress: str | None = None,
                           autotune: str | None = None, axis: str = "data"):
    """Data-parallel sibling of ``train.step.make_cnn_train_step``.

    Per shard: the full PR-4 training pipeline on the local slice of the
    batch (BN uses local batch statistics — classic DP).  Cross-shard: one
    gradient reduction *after* the wu pass produced local dW and *before*
    the optimizer consumes it, plus a pmean of the BN batch statistics for
    the running-stat update and of the scalar loss.  With the replicated
    params spec and exact f32 reduction, an ``n``-shard step whose shards
    see identical local batches is bit-identical to the single-device step
    (pinned in tests/test_train_dp.py).

    ``accum_steps`` splits the *local* batch into microbatches whose
    gradients (and BN statistics) are averaged — semantics pinned by the
    accum_steps=k ≡ accum_steps=1 identity test.  Returns
    ``step(state, batch) -> (state, {"loss"})``; build ``state`` with
    ``init_cnn_train_state_dp`` and shard ``batch`` over ``axis`` (the step
    is jit'd over ``shard_map``, so an unsharded host batch also works —
    jit re-shards it to the in_spec).
    """
    from repro import backend as be
    compress = be.resolve_grad_compress(grad_compress)
    P = jax.sharding.PartitionSpec

    def local_loss(params, mb):
        return gxm.loss(params, mb, collect_stats=True)

    def local_grads(params, batch):
        grad_fn = jax.value_and_grad(local_loss, has_aux=True)
        if accum_steps == 1:
            (loss, stats), grads = grad_fn(params, batch)
            return loss, stats, grads

        lead = jax.tree.leaves(batch)[0].shape[0]
        assert lead % accum_steps == 0, \
            f"per-shard batch {lead} not divisible by accum_steps " \
            f"{accum_steps}: trailing examples would be silently dropped"

        def mb_at(i):
            def sl(x):
                m = x.shape[0] // accum_steps
                return jax.lax.dynamic_slice_in_dim(x, i * m, m, 0)
            return jax.tree.map(sl, batch)

        out_sds = jax.eval_shape(local_loss, params, mb_at(0))
        zeros = lambda t: jax.tree.map(         # noqa: E731
            lambda s: jnp.zeros(s.shape, s.dtype), t)

        def micro(i, carry):
            loss_acc, stats_acc, g_acc = carry
            (l, st), g = grad_fn(params, mb_at(i))
            return (loss_acc + l,
                    jax.tree.map(jnp.add, stats_acc, st),
                    jax.tree.map(jnp.add, g_acc, g))

        init = (jnp.zeros(out_sds[0].shape, out_sds[0].dtype),
                zeros(out_sds[1]),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params))
        loss, stats, grads = jax.lax.fori_loop(0, accum_steps, micro, init)
        div = lambda t: jax.tree.map(           # noqa: E731
            lambda x: x / accum_steps, t)
        return div(loss), div(stats), div(grads)

    def dp_step(state, batch):
        # named scopes: each phase's device ops carry its name in a trace
        params = state["params"]
        with jax.named_scope("grads"):
            loss, stats, grads = local_grads(params, batch)
        # the GxM reduction point: local dW exists (wu pass done), the
        # optimizer has not run — §II-J's compute/communication seam
        with jax.named_scope("grad_allreduce"):
            if compress == "int8":
                residual = jax.tree.map(lambda r: r[0], state["residual"])
                grads, residual = compressed_psum_tree(grads, axis, residual)
                new_residual = jax.tree.map(lambda r: r[None], residual)
            else:
                grads = jax.lax.pmean(grads, axis)
            loss = jax.lax.pmean(loss, axis)
        with jax.named_scope("bn_pmean"):
            stats = jax.lax.pmean(stats, axis)
        with jax.named_scope("sgd"):
            new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            apply_bn_updates(new_params, stats, bn_momentum)
        new_state = {"params": new_params, "step": state["step"] + 1}
        if compress == "int8":
            new_state["residual"] = new_residual
        return new_state, {"loss": loss}

    state_spec = {"params": P(), "step": P()}
    if compress == "int8":
        state_spec["residual"] = P(axis)
    sharded = jax.shard_map(dp_step, mesh=mesh,
                            in_specs=(state_spec, P(axis)),
                            out_specs=(state_spec, P()), check_vma=False)
    jitted = jax.jit(sharded)

    def step(state, batch):
        if autotune is None:
            return jitted(state, batch)
        with be.use_autotune(autotune):
            return jitted(state, batch)
    return step


def shard_cnn_batch(batch, mesh, *, axis: str = "data"):
    """Place a host batch with the leading dim sharded over ``axis`` (the
    step's in_spec) so jit never gathers it through one device."""
    P = jax.sharding.PartitionSpec
    sh = jax.sharding.NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda x: jax.device_put(x, sh), batch)


# -- warmup: tune once per host, broadcast the entries ------------------------

def warmup_cnn_train_dp(gxm, mesh, *, global_batch: int,
                        image_hw=(224, 224), mode: str = "tune",
                        backend=None, cache=None, bwd_mode=None):
    """Per-host training warmup for the DP step: tune the fwd/bwd/wu
    blocking entries once at the *local* (per-shard) batch the shard_map
    body lowers to, and export them as a broadcast payload.

    In a multi-process launch only host 0 runs this; every other host
    installs the payload with ``install_warmup_entries`` instead of
    re-searching an identical space (single-controller runs are just the
    degenerate one-host case).  Returns ``(report, payload)``."""
    from repro.train.step import warmup_cnn_train
    from repro.tune.cache import default_cache
    cache = default_cache() if cache is None else cache
    report = warmup_cnn_train(gxm, image_hw=image_hw, minibatch=global_batch,
                              mode=mode, backend=backend, cache=cache,
                              bwd_mode=bwd_mode, mesh=mesh)
    payload = cache.export_entries([e["key"] for e in report if e["cached"]])
    return report, payload


def install_warmup_entries(payload, cache=None, *, persist: bool = True):
    """Receive a broadcast payload (non-zero hosts).  Returns entry count."""
    from repro.tune.cache import default_cache
    cache = default_cache() if cache is None else cache
    return cache.merge_entries(payload, persist=persist)
