"""Paper §III serving tables: image throughput of the GxM inference path
for ResNet-50 and Inception — images/sec vs batch size and device count,
with efficiency relative to the three-term roofline model
(``launch/roofline.py``, priced for its ``MODEL_TARGET`` chip).

A CPU-only table: every worker is pinned to ``JAX_PLATFORMS=cpu`` and every
row says ``platform: cpu``.  Its times are XLA-on-CPU times; it is not a
chip measurement and must not be run or read as one.

Each device count runs in a fresh subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
imports, like ``tests/test_distributed.py``), so the multi-device column is
reproducible on any host.  Per (arch, batch, devices) cell the worker
builds a ``CnnInferenceEngine`` over ``make_host_mesh``, warms it up
(blocking cache + AOT compile), times the bucket executable, and reads the
roofline terms off the compiled HLO.  Output: CSV rows for the harness plus
one ``RESULT {json}`` document with every cell.

  PYTHONPATH=src python -m benchmarks.serve_cnn_bench          # full table
  PYTHONPATH=src python -m benchmarks.serve_cnn_bench --dry    # CI smoke
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ARCHS = ("resnet50", "inception")
DEVICE_COUNTS = (1, 2)
FULL_BATCHES = (4, 8, 16)
DRY_BATCHES = (2, 4, 8)


def _worker(args) -> None:
    """Runs inside a subprocess whose XLA_FLAGS pinned the device count."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import time_call
    from repro.graph.serving import cnn_model_flops
    from repro.launch import roofline as rl
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve_cnn import build_model
    from repro.graph.serving import CnnInferenceEngine

    ndev = len(jax.devices())
    assert ndev == args.devices, (ndev, args.devices)
    m, image = build_model(args.arch, smoke=args.dry,
                           num_classes=10 if args.dry else 1000,
                           image=args.image)
    params = m.init(jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    batches = [b for b in args.batches if b % ndev == 0]
    engine = CnnInferenceEngine(m, params, image_hw=(image, image),
                                mesh=mesh, buckets=tuple(batches))
    engine.warmup(autotune="off")        # compile-only: timings, not tuning

    rng = np.random.default_rng(0)
    rows = []
    for batch in batches:
        x = jnp.asarray(rng.standard_normal((batch, image, image, 3)),
                        jnp.float32)
        compiled = engine.aot_executable(batch)
        us = time_call(lambda v: compiled(params, v), x)
        flops = cnn_model_flops(m.etg, (image, image), batch)
        roof = rl.analyze(compiled, chips=ndev, model_flops_global=flops)
        roof_ips = batch / roof.step_time_s if roof.step_time_s else 0.0
        measured_ips = batch / (us / 1e6)
        rows.append({
            "platform": jax.devices()[0].platform,
            "roofline_target": rl.MODEL_TARGET,
            "arch": args.arch, "devices": ndev, "batch": batch,
            "image": image, "us_per_batch": round(us, 1),
            "images_per_s": round(measured_ips, 2),
            "roofline_images_per_s": round(roof_ips, 2),
            "roofline_efficiency": round(measured_ips / roof_ips, 6)
            if roof_ips else 0.0,
            "roofline_dominant": roof.dominant,
            "model_gflops_per_batch": round(flops / 1e9, 3),
        })
    print("RESULT " + json.dumps({"arch": args.arch, "devices": ndev,
                                  "rows": rows}))


def _spawn(arch: str, devices: int, batches, *, dry: bool,
           image: int) -> list[dict]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "benchmarks.serve_cnn_bench", "--worker",
           "--arch", arch, "--devices", str(devices),
           "--batches", ",".join(map(str, batches)), "--image", str(image)]
    if dry:
        cmd.append("--dry")
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=repo, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"worker {arch}x{devices} failed:\n"
                           + out.stderr[-4000:])
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])["rows"]
    raise RuntimeError(f"worker {arch}x{devices} emitted no RESULT line:\n"
                       + out.stdout[-2000:])


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry", action="store_true",
                    help="tiny topologies/images (CI smoke)")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--arch", choices=ARCHS, default="resnet50")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--batches", type=str, default="")
    ap.add_argument("--image", type=int, default=0)
    args = ap.parse_args(argv)
    args.batches = tuple(int(b) for b in args.batches.split(",") if b) or \
        (DRY_BATCHES if args.dry else FULL_BATCHES)

    if args.worker:
        _worker(args)
        return

    from benchmarks.common import emit
    table = {"batches": list(args.batches), "rows": []}
    for arch in ARCHS:
        for devices in DEVICE_COUNTS:
            rows = _spawn(arch, devices, args.batches, dry=args.dry,
                          image=args.image)
            table["rows"].extend(rows)
            for r in rows:
                emit(f"serve_{arch}_d{devices}_b{r['batch']}",
                     r["us_per_batch"],
                     f"platform={r['platform']};"
                     f"images_per_s={r['images_per_s']};"
                     f"roofline_eff={r['roofline_efficiency']};"
                     f"dominant={r['roofline_dominant']}")
    print("RESULT " + json.dumps(table))


if __name__ == "__main__":
    main()
