"""Chip smoke test: ResNet-50 at full width (1000 classes, 224x224, f32,
random weights from a seed) served and trained on a TPU through the repo's
Pallas conv kernels, by the entry points a user calls.

  python chip_smoke.py              # one chip: serve phase + train phase
  python chip_smoke.py --chips 4    # four chips: data-parallel train step
                                    # and serving, each against one device

Serve phase: ``CnnInferenceEngine`` over ``make_host_mesh()`` with two
warmed buckets answers seeded requests from an ``ImageServer``; one batch's
logits are compared with the same params run through XLA's convolution at
highest matmul precision.  Train phase: three seeded steps of
``make_cnn_train_step_dp`` (forward, phase backward-data and weight-update
kernels); step 1 is compared with the same step run through XLA.

The script runs in one process and starts none.  It exits non-zero, before
printing any result, unless JAX runs on a TPU with enough devices; any
failed check raises.  Its last stdout line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
SERVE_BUCKETS = (8, 32)
SERVE_REQUESTS = 48
TRAIN_BATCH = 16            # global batch of the one-chip train phase
TRAIN_STEPS = 3
DP_BATCH = 32               # global batch of the four-chip train step
LR = 0.01                   # SGD step size of every train step here
IMAGE = 224
CLASSES = 1000

# Tolerances.  Both sides run f32 at highest matmul precision, so they
# differ only by summation order through 53 convolutions.  The parameter
# update du = params after the step - params before is ill-conditioned at
# random init (ReLU sign flips, batch-norm cancellation), so each train
# limit sits between two readings of the step against XLA-highest: the f32
# floor (params moved by one ulp) and a control that runs every conv but
# the stem as one bf16 pass, as XLA's default f32 conv does on a TPU.  On
# the CPU at 112x112, batch 16 (floor / control): loss 1.1e-6 / 2.8e-3,
# whole update 1.6e-3 / 9.9e-2, worst leaf 2.4e-2 / 1.5; PERF.md has the
# chip's readings.  A wrong tap, halo or phase moves the leaves it touches
# by O(1).
LOGIT_RTOL = 1e-3           # max|got - want| / max|want| over a batch
LOSS_RTOL = 1e-4            # |loss - loss_ref| / |loss_ref|
UPDATE_RTOL = 1e-2          # ||du - du_ref|| / ||du_ref|| over all leaves
LEAF_RTOL = 1e-1            # the same, for the worst single leaf


def device_header(need: int) -> dict:
    """Print versions and the device; exit unless JAX runs on >= need TPUs."""
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {libtpu}")
    print(f"platform {dev['platform']}  device_kind {dev['kind']}  "
          f"devices {dev['count']}")
    if dev["platform"] != "tpu" or dev["count"] < need:
        print(f"chip_smoke: needs {need} TPU device(s), JAX has "
              f"{dev['count']} {dev['platform']} device(s)", file=sys.stderr)
        sys.exit(2)
    return dev


class CompileClock:
    """Sums JAX's backend-compile durations and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def build(impl=None):
    """Full-width ResNet-50 (default backend unless ``impl`` is given)."""
    from repro.launch.serve_cnn import build_model
    m, image = build_model("resnet50", smoke=False, num_classes=CLASSES,
                           image=IMAGE, impl=impl)
    return m


def xla_reference(fn, *args):
    """``fn(*args)`` traced and run at highest matmul precision (XLA's
    default f32 convolution on a TPU takes bf16 passes)."""
    import jax
    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(fn(*args))


def count_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def conv_split(m) -> tuple[int, int]:
    """(convs the dispatch rule sends to Pallas, convs left on XLA)."""
    from repro.core.conv import lane_ok
    from repro.graph.serving import conv_shapes
    shapes = conv_shapes(m.etg, (IMAGE, IMAGE))
    n_pallas = sum(lane_ok(s["c"], s["k"]) for s in shapes)
    return n_pallas, len(shapes) - n_pallas


def serve_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import backend as be
    from repro.graph.serving import CnnInferenceEngine
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve_cnn import ImageServer

    m = build()
    backend = be.get_backend()
    print(f"serve: kernel backend resolved to {backend!r}")
    check(backend == "pallas", f"default backend is {backend!r}, not pallas")
    params = m.init(jax.random.PRNGKey(SEED))
    n_pallas, n_xla = conv_split(m)
    engine = CnnInferenceEngine(m, params, image_hw=(IMAGE, IMAGE),
                                mesh=make_host_mesh(), buckets=SERVE_BUCKETS,
                                autotune="off")
    report = engine.warmup(autotune="off")
    for bucket, secs in report["compile_s"].items():
        print(f"serve: bucket {bucket} compiled in {secs:.1f} s")
    kernels = {b: count_kernels(engine.aot_executable(b))
               for b in engine.buckets}
    print(f"serve: convs on Pallas kernels {n_pallas}, on XLA {n_xla} "
          f"(the C=3 stem); tpu_custom_call per bucket {kernels}")
    check(n_xla == 1, f"{n_xla} convs left on XLA, expected only the stem")
    check(all(v == n_pallas for v in kernels.values()),
          f"compiled kernels {kernels} != {n_pallas} Pallas convs")

    server = ImageServer(engine)
    rng = np.random.default_rng(SEED)
    remaining = SERVE_REQUESTS
    while remaining:                  # bursts, so partial buckets happen
        burst = int(rng.integers(1, min(remaining, max(SERVE_BUCKETS)) + 1))
        for _ in range(burst):
            server.submit(rng.standard_normal((IMAGE, IMAGE, 3),
                                              dtype=np.float32))
        remaining -= burst
        server.step()
    results = server.run()
    st = server.stats()
    check(len(results) == SERVE_REQUESTS,
          f"{len(results)} of {SERVE_REQUESTS} requests answered")
    check(all(np.isfinite(v) for _, v in results.values()),
          "a request was answered with a non-finite logit")
    print(f"serve: {len(results)}/{SERVE_REQUESTS} requests answered, all "
          f"finite, in {st['batches']} batches {st['by_bucket']}; smoke "
          f"figure, not a benchmark: "
          f"{st['images'] / st['serve_s']:.1f} images/s")

    x = rng.standard_normal((SERVE_BUCKETS[0], IMAGE, IMAGE, 3),
                            dtype=np.float32)
    got = np.asarray(engine.infer(x))
    want = np.asarray(xla_reference(jax.jit(build("xla").infer), params,
                                    jnp.asarray(x)))
    err = rel_err(got, want)
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    print(f"serve: logits vs XLA highest precision: max rel err {err:.3e} "
          f"(tolerance {LOGIT_RTOL:.0e}), top-1 agreement {agree:.3f} "
          f"(required 1.000)")
    check(np.isfinite(got).all(), "non-finite logits")
    check(err <= LOGIT_RTOL and agree == 1.0, "serve logits disagree")


def seeded_batch(rng, n: int) -> dict:
    import numpy as np
    return {"image": rng.standard_normal((n, IMAGE, IMAGE, 3),
                                         dtype=np.float32),
            "label": rng.integers(0, CLASSES, n).astype(np.int32)}


def compare_step(tag: str, state0, got, want) -> None:
    """Check one train step's loss and parameter update against a
    reference step from the same state."""
    import jax
    import numpy as np
    (s1, m1), (r1, mr) = got, want
    loss, loss_ref = float(m1["loss"]), float(mr["loss"])
    loss_err = abs(loss - loss_ref) / abs(loss_ref)

    paths, p0s = zip(*jax.tree_util.tree_flatten_with_path(
        state0["params"])[0])
    diff2 = ref2 = 0.0
    worst = (0.0, "")
    for path, p0, p1, q1 in zip(paths, p0s, jax.tree.leaves(s1["params"]),
                                jax.tree.leaves(r1["params"])):
        p0, p1, q1 = (np.asarray(a, np.float64) for a in (p0, p1, q1))
        d2 = float(np.sum((p1 - q1) ** 2))
        r2 = float(np.sum((q1 - p0) ** 2))
        diff2, ref2 = diff2 + d2, ref2 + r2
        worst = max(worst, (np.sqrt(d2 / max(r2, 1e-60)),
                            jax.tree_util.keystr(path)))
    err = np.sqrt(diff2 / max(ref2, 1e-60))
    print(f"{tag}: step-1 loss {loss:.6f} vs reference {loss_ref:.6f}: "
          f"rel err {loss_err:.3e} (tolerance {LOSS_RTOL:.0e}); parameter "
          f"update rel err {err:.3e} over {len(paths)} leaves (tolerance "
          f"{UPDATE_RTOL:.0e}), worst leaf {worst[1]} {worst[0]:.3e} "
          f"(tolerance {LEAF_RTOL:.0e})")
    check(np.isfinite(loss), "non-finite loss")
    check(loss_err <= LOSS_RTOL, f"{tag}: loss disagrees")
    check(err <= UPDATE_RTOL and worst[0] <= LEAF_RTOL,
          f"{tag}: parameter update disagrees")


def train_phase() -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_host_mesh
    from repro.train.distributed import (init_cnn_train_state_dp,
                                         make_cnn_train_step_dp,
                                         shard_cnn_batch)

    m = build()
    params = m.init(jax.random.PRNGKey(SEED))
    mesh = make_host_mesh()
    state0 = init_cnn_train_state_dp(params, mesh)
    step = make_cnn_train_step_dp(m, mesh, lr=LR)
    rng = np.random.default_rng(SEED + 1)
    batches = [shard_cnn_batch(seeded_batch(rng, TRAIN_BATCH), mesh)
               for _ in range(TRAIN_STEPS)]

    losses, state, first = [], state0, None
    for i, batch in enumerate(batches):
        t0 = time.perf_counter()
        state, metrics = jax.block_until_ready(step(state, batch))
        dt = time.perf_counter() - t0
        losses.append(float(metrics["loss"]))
        if i == 0:
            first = (state, metrics)
        print(f"train: step {i + 1} loss {losses[-1]:.6f}  {dt:.2f} s"
              + ("  (includes compile)" if i == 0 else ""))
    check(bool(np.isfinite(losses).all()), f"non-finite losses {losses}")

    ref_step = make_cnn_train_step_dp(build("xla"), mesh, lr=LR)
    want = xla_reference(ref_step, state0, batches[0])
    compare_step("train", state0, first, want)


def dp_phase(chips: int) -> None:
    """Four-chip data parallelism, each side against one device."""
    import jax
    import numpy as np

    from repro.graph.serving import CnnInferenceEngine
    from repro.launch.mesh import make_host_mesh
    from repro.train.distributed import (init_cnn_train_state_dp,
                                         make_cnn_train_step_dp,
                                         shard_cnn_batch)

    m = build()
    params = m.init(jax.random.PRNGKey(SEED))
    mesh, mesh1 = make_host_mesh(data=chips), make_host_mesh(data=1)
    check(mesh.shape["data"] == chips, f"mesh {dict(mesh.shape)}")

    # train: 4 shards x 8 images, fp32 pmean, vs one device running the
    # same 32 images as 4 accumulated microbatches of 8 — the same math
    # (BN statistics are per shard / per microbatch)
    rng = np.random.default_rng(SEED + 2)
    host = seeded_batch(rng, DP_BATCH)
    batch = shard_cnn_batch(host, mesh)
    placed = {s.device for s in batch["image"].addressable_shards}
    print(f"dp-train: batch shards on devices "
          f"{sorted(d.id for d in placed)}")
    check(len(placed) == chips, f"batch on {len(placed)} devices")
    state0 = init_cnn_train_state_dp(params, mesh)
    t0 = time.perf_counter()
    got = jax.block_until_ready(make_cnn_train_step_dp(m, mesh, lr=LR)(
        state0, batch))
    print(f"dp-train: {chips}-chip step (incl. compile) "
          f"{time.perf_counter() - t0:.1f} s")
    out_devs = jax.tree.leaves(got[0]["params"])[0].sharding.device_set
    check(len(out_devs) == chips, f"params on {len(out_devs)} devices")
    state1 = init_cnn_train_state_dp(params, mesh1)
    want = jax.block_until_ready(make_cnn_train_step_dp(
        m, mesh1, lr=LR, accum_steps=chips)(state1,
                                            shard_cnn_batch(host, mesh1)))
    compare_step("dp-train", state0, got, want)

    # serve: the same bucket data-parallel over 4 chips vs one chip
    x = rng.standard_normal((SERVE_BUCKETS[0], IMAGE, IMAGE, 3),
                            dtype=np.float32)
    logits = {}
    for name, msh in (("dp", mesh), ("one", mesh1)):
        eng = CnnInferenceEngine(m, params, image_hw=(IMAGE, IMAGE),
                                 mesh=msh, buckets=SERVE_BUCKETS[:1],
                                 autotune="off")
        eng.warmup(autotune="off")
        logits[name] = np.asarray(eng.infer(x))
        out_sh = eng.aot_executable(eng.buckets[0]).output_shardings
        n_dev = len(out_sh.device_set)
        print(f"dp-serve: {name} engine ran on {n_dev} device(s)")
        check(n_dev == msh.shape["data"], f"{name} engine on {n_dev}")
    err = rel_err(logits["dp"], logits["one"])
    agree = float(np.mean(logits["dp"].argmax(-1)
                          == logits["one"].argmax(-1)))
    print(f"dp-serve: {chips}-chip vs 1-chip logits max rel err {err:.3e} "
          f"(tolerance {LOGIT_RTOL:.0e}), top-1 agreement {agree:.3f}")
    check(err <= LOGIT_RTOL and agree == 1.0, "dp serving disagrees")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel train and serve "
                         "paths across four chips")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    dev = device_header(args.chips)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    phases = ([("dp", lambda: dp_phase(args.chips))] if args.chips > 1
              else [("serve", serve_phase), ("train", train_phase)])
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        print(f"{name} phase passed in {time.perf_counter() - t0:.1f} s")
    print(f"total compile {clock.seconds:.1f} s, persistent-cache hits "
          f"{clock.hits}; wall {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
