"""Bonus dry-run: the paper's own workload (ResNet-50 training through the
GxM executor) lowered on the production meshes — data-parallel over
(pod, data), weights replicated, SGD-momentum update, gradient all-reduce
implicit in the sharded autodiff.  This is Fig. 9's configuration at
256/512 chips instead of 16 nodes.

  python -m repro.launch.dryrun_cnn [--mesh single|multi] [--batch 256]
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))

import argparse  # noqa: E402
import json      # noqa: E402
import pathlib   # noqa: E402
import time      # noqa: E402

import jax       # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.graph import GxM, resnet50  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

RESNET50_FLOPS_PER_IMG = 3 * 4.1e9   # fwd+bwd+wu


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    chips = 1
    for v in mesh.shape.values():
        chips *= v
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

    m = GxM(resnet50(num_classes=1000), impl="xla", num_classes=1000)
    params_shapes = jax.eval_shape(
        lambda k: m.init(k), jax.random.PRNGKey(0))
    mom_shapes = params_shapes   # SGD momentum buffers mirror params

    def train_step(params, mom, batch):
        loss, grads = jax.value_and_grad(m.loss)(params, batch)
        new_mom = jax.tree.map(lambda v, g: 0.9 * v + g, mom, grads)
        new_params = jax.tree.map(lambda p, v: p - 0.1 * v, params, new_mom)
        return new_params, new_mom, loss

    rep = NamedSharding(mesh, P())
    param_sh = jax.tree.map(lambda _: rep, params_shapes)
    batch_sh = {"image": NamedSharding(mesh, P(batch_axes, None, None, None)),
                "label": NamedSharding(mesh, P(batch_axes))}
    batch_shapes = {
        "image": jax.ShapeDtypeStruct(
            (args.batch, args.image, args.image, 3), jnp.float32),
        "label": jax.ShapeDtypeStruct((args.batch,), jnp.int32)}

    t0 = time.time()
    lowered = jax.jit(
        train_step,
        in_shardings=(param_sh, param_sh, batch_sh),
        out_shardings=(param_sh, param_sh, None),
        donate_argnums=(0, 1),
    ).lower(params_shapes, mom_shapes, batch_shapes)
    compiled = lowered.compile()
    dt = time.time() - t0
    print(compiled.memory_analysis())
    print({k: v for k, v in compiled.cost_analysis().items()
           if k in ("flops", "bytes accessed")})

    colls = rl.parse_collectives(compiled.as_text(), default_group=chips)
    ma = compiled.memory_analysis()
    n_params = sum(x.size for x in jax.tree.leaves(params_shapes))
    rec = {
        "arch": "resnet50-gxm", "shape": f"train_{args.batch}x{args.image}",
        "mesh": args.mesh, "chips": chips, "applicable": True,
        "compile_s": round(dt, 1),
        "memory": {"total_per_device_bytes":
                   ma.argument_size_in_bytes + ma.output_size_in_bytes
                   + ma.temp_size_in_bytes - ma.alias_size_in_bytes},
        "collectives": {"count": colls.count,
                        "wire_bytes": colls.wire_bytes,
                        "by_kind": colls.by_kind},
        "n_params": n_params,
        "grad_allreduce_model_s":
            2 * (chips - 1) / chips * n_params * 4 / rl.ICI_BW,
        "compute_model_s":
            args.batch * RESNET50_FLOPS_PER_IMG / (chips * rl.PEAK_FLOPS
                                                   * 0.55),
    }
    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    (outdir / f"resnet50-gxm__train__{args.mesh}.json").write_text(
        json.dumps(rec, indent=1))
    print(json.dumps({k: v for k, v in rec.items() if k != "memory"},
                     indent=1))


if __name__ == "__main__":
    main()
