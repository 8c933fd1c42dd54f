"""Runs a configuration's plain reference: once the window has closed, to
judge what the timed path produced; and, at the control's lower
precision, in the program's place (``--control``), to show that the
limits catch it."""
from __future__ import annotations

import functools

import numpy as np


def init_params(ref, cfg: dict, seed: int):
    """The run's weights, made on the device in one jitted call."""
    import jax
    from chipbench.traffic import jax_seed
    key = jax.random.PRNGKey(jax_seed(seed, 0))
    return jax.jit(functools.partial(ref.init_params, cfg))(key)


def serve_logits(ref, cfg: dict, params, images: np.ndarray, *,
                 block: int, precision: str = "highest") -> np.ndarray:
    """Reference logits of ``images`` in blocks of ``block`` rows."""
    import jax
    import jax.numpy as jnp
    fwd = jax.jit(lambda p, x: ref.forward(cfg, p, x, train=False,
                                           precision=precision))
    out = []
    for i in range(0, len(images), block):
        x = images[i:i + block]
        n = len(x)
        if n < block:
            x = np.concatenate([x, np.zeros((block - n, *x.shape[1:]),
                                            x.dtype)])
        out.append(np.asarray(fwd(params, jnp.asarray(x)))[:n])
    return np.concatenate(out) if out else np.zeros((0, 0), np.float32)


class RefTrainer:
    """The reference's data-parallel SGD step: the batch split into
    ``shards`` parts, each with its own batch statistics; loss, statistics
    and gradients averaged.  With a device for each part the parts run side
    by side (one per device); else one after another on the first.  Each
    part's gradient is its own: ``check_vma=False`` keeps ``shard_map``
    from summing the replicated weights' gradients over the parts."""

    def __init__(self, ref, cfg: dict, mix: dict, shards: int,
                 precision: str):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        self.shards = shards
        self.device = jax.devices()[0]
        grads = functools.partial(ref.grads, cfg, precision=precision)
        self._apply = jax.jit(functools.partial(
            ref.apply, lr=mix["lr"], bn_momentum=mix["bn_momentum"]))
        self._mean = jax.jit(lambda ts: jax.tree.map(
            lambda *a: sum(a) / len(a), *ts))
        self.mesh = None
        if shards > 1 and len(jax.devices()) >= shards:
            self.mesh = Mesh(np.array(jax.devices()[:shards]), ("part",))
            self._whole = NamedSharding(self.mesh, P())
            self._split = NamedSharding(self.mesh, P("part"))
            each = jax.shard_map(
                lambda p, b: jax.tree.map(lambda x: x[None], grads(p, b)),
                mesh=self.mesh, in_specs=(P(), P("part")),
                out_specs=P("part"), check_vma=False)
            self._grads = jax.jit(lambda p, b: jax.tree.map(
                lambda x: x.mean(0), each(p, b)))
        else:
            self._grads = jax.jit(grads)

    def __call__(self, params, batch):
        """(new params, loss, gradients)."""
        import jax
        if self.mesh is not None:
            params = jax.device_put(params, self._whole)
            loss, stats, g = self._grads(params,
                                         jax.device_put(batch, self._split))
            return self._apply(params, stats, g), loss, g
        params = jax.device_put(params, self.device)
        n = batch["label"].shape[0] // self.shards
        parts = []
        for i in range(self.shards):
            part = {k: jax.device_put(v[i * n:(i + 1) * n], self.device)
                    for k, v in batch.items()}
            parts.append(self._grads(params, part))
        loss, stats, g = self._mean(parts) if self.shards > 1 else parts[0]
        return self._apply(params, stats, g), loss, g


def train_steps(trainer: RefTrainer, mix: dict, params, batches: list) -> dict:
    """The reference's first ``check_steps`` steps from ``params``: losses,
    params after step 1 and after the last, and the first gradient."""
    from chipbench.train import host_tree
    losses, p1, g1 = [], None, None
    for i in range(mix["check_steps"]):
        params, loss, grads = trainer(params, batches[i])
        losses.append(float(loss))
        if i == 0:
            p1, g1 = host_tree(params), host_tree(grads)
        del grads
    return {"losses": losses, "p1": p1, "pn": host_tree(params), "g1": g1}


class ControlEngine:
    """The reference at the control's precision, standing where the
    program's ``CnnInferenceEngine`` stands: pad to a bucket, run, slice."""

    def __init__(self, ref, cfg: dict, params, buckets, precision: str):
        import jax
        self.buckets = tuple(buckets)
        self.params = params
        self._fn = jax.jit(lambda p, x: ref.forward(cfg, p, x, train=False,
                                                    precision=precision))

    def infer(self, images):
        import jax.numpy as jnp
        x = np.asarray(images, np.float32)
        n = len(x)
        bucket = min(b for b in self.buckets if b >= n)
        if n < bucket:
            x = np.concatenate([x, np.zeros((bucket - n, *x.shape[1:]),
                                            x.dtype)])
        return self._fn(self.params, jnp.asarray(x))[:n]


def control_step(trainer: RefTrainer):
    """The reference's step in the program's step's place: state
    {"params", "step"} -> (state, {"loss"})."""
    def step(state, batch):
        params, loss, _ = trainer(state["params"], batch)
        return {"params": params, "step": state["step"] + 1}, {"loss": loss}
    return step
