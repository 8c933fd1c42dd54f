"""Training cells: the program's data-parallel step
(``make_cnn_train_step_dp``, SGD) over a mesh of the cell's chips.

Set-up builds the step and its state once and drives that same object
through the checked steps on distinct seeded batches, which the window
then continues.  The batches are made on the device and cycled, so the
window measures the step and not an input pipeline.  Steps are dispatched
back to back, at most two in flight.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import traffic
from chipbench.spans import span


def make_batches(cfg: dict, mix: dict, seed: int, global_batch: int,
                 sharding=None) -> list[dict]:
    """``pool_batches`` seeded batches of ``global_batch`` images and labels,
    made on the device in one jitted call (``sharding``: of every leaf)."""
    import jax
    import jax.numpy as jnp
    hw, n = cfg["image"], mix["pool_batches"]
    key = jax.random.PRNGKey(traffic.jax_seed(seed, 6))

    def make(key):
        out = []
        for k in jax.random.split(key, n):
            ki, kl = jax.random.split(k)
            out.append({
                "image": jax.random.normal(ki, (global_batch, hw, hw, 3),
                                           jnp.float32),
                "label": jax.random.randint(kl, (global_batch,), 0,
                                            cfg["num_classes"], jnp.int32)})
        return out

    return jax.jit(make, out_shardings=sharding)(key)


def host_tree(tree):
    import jax
    return jax.tree.map(np.asarray, jax.device_get(tree))


def setup(plan: dict, params, seed: int, clock, *, step_factory=None,
          fault=None):
    """The step and its state, driven through the checked steps."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    from repro.train.distributed import (init_cnn_train_state_dp,
                                         make_cnn_train_step_dp)

    cfg, mix, chips = plan["config"], plan["mix"], plan["cell"]["chips"]
    mesh = make_host_mesh(data=chips)
    gb = mix["per_chip_batch"] * chips
    batches = make_batches(cfg, mix, seed, gb, NamedSharding(mesh, P("data")))
    clock.lap("batches")
    state = init_cnn_train_state_dp(params, mesh)
    if step_factory is None:
        step = make_cnn_train_step_dp(plan["gxm"], mesh,
                                      lr=mix["lr"],
                                      bn_momentum=mix["bn_momentum"])
    else:
        step = step_factory(mesh)
    base = step
    if fault is not None:
        step = fault(step)
    p0 = host_tree(state["params"])
    losses, p1 = [], None
    for i in range(mix["check_steps"]):
        state, metrics = step(state, batches[i])
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        if i == 0:
            p1 = host_tree(state["params"])
            clock.lap("compile")
    pn = host_tree(state["params"])
    clock.lap("warm")
    return {"step": step, "base_step": base, "state": state, "batches": batches, "mesh": mesh,
            "global_batch": gb,
            "prog": {"losses": losses, "p1": p1, "pn": pn}, "p0": p0}


def window(st: dict, plan: dict, seconds: float) -> dict:
    import jax
    step, state, batches = st["step"], st["state"], st["batches"]
    k = plan["mix"]["check_steps"]
    losses, ready = [], []
    prev = None
    n = 0
    t0 = time.perf_counter()
    while True:
        with span("train_step"):
            state, metrics = step(state, batches[(k + n) % len(batches)])
        n += 1
        if prev is not None:
            with span("block"):
                losses.append(float(jax.block_until_ready(prev)))
            ready.append(time.perf_counter())
        prev = metrics["loss"]
        if time.perf_counter() - t0 >= seconds:
            break
    with span("block"):
        jax.block_until_ready(state)
        losses.append(float(prev))
    t1 = time.perf_counter()
    st["state"] = state
    return {"t0": t0, "t_end": t1, "steps": n,
            "images": n * st["global_batch"], "losses": losses,
            "ready": ready + [t1]}


def summarize(win: dict) -> dict:
    span_s = win["t_end"] - win["t0"]
    finite = int(np.sum(np.isfinite(win["losses"])))
    gaps = np.diff(win["ready"]) * 1e3     # one step apart, after the first
    return {"train_images_per_s": win["images"] / span_s,
            "attempted": win["steps"], "failed": win["steps"] - finite,
            "window_s": span_s, "steps": win["steps"],
            "step_ms_median": float(np.median(gaps)) if len(gaps) else None,
            "step_ms_max": float(np.max(gaps)) if len(gaps) else None}
