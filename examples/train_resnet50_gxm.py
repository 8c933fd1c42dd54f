"""The paper's own workload: ResNet-50 training through the GxM execution
task graph — conv kernels with the §II-I/J backward pipeline (tiled update
pass, phase-decomposed strided duality — DESIGN.md §10), §II-G fusion at
inference.  Training warmup pre-tunes the fwd + bwd (dual) + wu blocking
cache so the first step never tunes inline.

``--devices N`` materializes N fake host devices (the flag must be set
before jax imports, so argument parsing happens first) and runs the
*data-parallel* step — ``train.distributed.make_cnn_train_step_dp`` under
``shard_map`` over the mesh's data axis, gradient psum between the update
pass and the optimizer, optional ``--compress int8`` error-feedback
reduction (DESIGN.md §11).

  PYTHONPATH=src python examples/train_resnet50_gxm.py [--full] [--warmup]
  PYTHONPATH=src python examples/train_resnet50_gxm.py --devices 2
  PYTHONPATH=src python examples/train_resnet50_gxm.py --devices 2 \\
      --compress int8 --warmup
"""
import argparse
import os


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full 50-layer topology (slow on CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", action="store_true",
                    help="pre-tune fwd/bwd/wu blockings before stepping")
    ap.add_argument("--devices", type=int, default=1,
                    help="data-parallel width (fake host devices)")
    ap.add_argument("--compress", choices=("off", "int8"), default="off",
                    help="gradient-reduction wire format (DP only)")
    ap.add_argument("--batch", type=int, default=8,
                    help="global batch (split across --devices)")
    return ap.parse_args()


def main():
    args = parse_args()
    if args.devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{args.devices}").strip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.graph import GxM, resnet50
    from repro.graph.etg import build_etg
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train.step import make_cnn_train_step, warmup_cnn_train

    enable_compile_cache()

    stages = (3, 4, 6, 3) if args.full else (1, 1, 1, 1)
    nl = resnet50(num_classes=10, stages=stages)
    etg = build_etg(nl)
    print(f"ETG: {etg.stats['nodes_before']} ops -> "
          f"{etg.stats['nodes_after']} tasks after fusion; "
          f"{len(etg.kernel_cache)} distinct JIT conv kernels")

    m = GxM(nl, num_classes=10)     # default backend: pallas on a TPU
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    assert args.batch % args.devices == 0, (args.batch, args.devices)
    x = jnp.asarray(rng.standard_normal((args.batch, 64, 64, 3)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, args.batch))
    batch = {"image": x, "label": y}

    if args.devices > 1:
        from repro.launch.mesh import make_host_mesh
        from repro.train.distributed import (init_cnn_train_state_dp,
                                             make_cnn_train_step_dp,
                                             shard_cnn_batch,
                                             warmup_cnn_train_dp)
        mesh = make_host_mesh(data=args.devices)
        print(f"data-parallel over mesh {dict(mesh.shape)}; "
              f"gradient reduction: {args.compress}")
        if args.warmup:
            report, payload = warmup_cnn_train_dp(
                m, mesh, global_batch=args.batch, image_hw=(64, 64))
            print(f"warmup: {sum(e['cached'] for e in report)} "
                  f"blocking-cache entries (per-shard batch), "
                  f"{len(payload)} broadcastable")
        state = init_cnn_train_state_dp(params, mesh,
                                        grad_compress=args.compress)
        step = make_cnn_train_step_dp(
            m, mesh, lr=0.05, grad_compress=args.compress,
            autotune="cache" if args.warmup else None)
        batch = shard_cnn_batch(batch, mesh)
        for i in range(args.steps):
            state, metrics = step(state, batch)
            if i % 5 == 0:
                print(f"step {i:3d}  loss={float(metrics['loss']):.4f}")
        params = jax.device_get(state["params"])
    else:
        if args.warmup:
            report = warmup_cnn_train(m, image_hw=(64, 64),
                                      minibatch=args.batch)
            print(f"warmup: {sum(e['cached'] for e in report)} "
                  f"blocking-cache entries across kinds "
                  f"{sorted({e['kind'] for e in report})}")
        step = make_cnn_train_step(m, lr=0.05,
                                   autotune="cache" if args.warmup else None)
        for i in range(args.steps):
            params, loss = step(params, batch)
            if i % 5 == 0:
                print(f"step {i:3d}  loss={float(loss):.4f}")

    # inference with everything fused into conv epilogues (§II-G)
    logits = m.forward(params, x, train=False)
    acc = float((jnp.argmax(logits, -1) == y).mean())
    print(f"train-set accuracy after {args.steps} steps: {acc:.2f}")


if __name__ == "__main__":
    main()
