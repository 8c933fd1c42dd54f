"""Paper §III multi-node table: data-parallel ResNet-50 *training* over GxM
— images/sec and scaling efficiency per (device count × gradient-reduction
wire format), the training sibling of ``serve_cnn_bench``.

Writes ``BENCH_train_scaling.json`` at the repo root.  The table is the
schedule-resolved *model* of a TPU v5e (``launch.roofline.MODEL_TARGET``,
the same constants as ``benchmarks/scaling_bench.py``), computed on the
CPU — every row says ``platform: cpu``; it is not a chip measurement.  So
the file is reproducible on any host and later PRs can diff it:

  t_comp     = local_batch · 3·4.1 GFLOP / (peak · kernel_eff)
  t_allreduce= ring all-reduce of the 25.6M-param gradient at the wire
               format's bytes/param (fp32: 4, int8 compressed psum: 1)
  exposed    = max(0, t_allreduce − overlap_fraction · t_comp)

where ``overlap_fraction`` is the backward share of the step (≈2/3): the
step reduces after the wu pass, so the XLA latency-hiding scheduler can
overlap layer i's dW reduction with the remaining backward compute, but
not with the forward of the *next* step.  ``scaling_efficiency`` is
ips(n) / (n · ips(1)); the no-overlap column is the pessimistic bound.

``--dry`` additionally *runs* the real ``train.distributed`` step end to
end — tiny ResNet, {1, 2} fake host devices × {fp32, int8} reduction, each
device count in a fresh subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — and reports the
measured images/sec rows in the ``RESULT`` document (measured rows never
enter the committed JSON: wall clock is host-dependent).

  PYTHONPATH=src python -m benchmarks.train_scaling_bench          # model
  PYTHONPATH=src python -m benchmarks.train_scaling_bench --dry    # CI smoke
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

DEVICE_COUNTS = (1, 2, 4)
REDUCTIONS = ("fp32", "int8")
LIVE_DEVICE_COUNTS = (1, 2)

RESNET50_GFLOP = 4.1 * 3        # fwd+bwd+wu per image (GFLOP)
RESNET50_PARAMS = 25.6e6
LOCAL_BATCH = 32
EFF_COMPUTE = 0.55              # kernel-level efficiency (paper: 55-80%)
OVERLAP_FRACTION = 2 / 3        # bwd share of the step hides the reduction
BYTES_PER_PARAM = {"fp32": 4.0, "int8": 1.0}

OUT_PATH = pathlib.Path(__file__).resolve().parents[1] \
    / "BENCH_train_scaling.json"


def step_times_s(devices: int, reduction: str) -> tuple[float, float, float]:
    """-> (t_comp, t_allreduce, t_step) of one DP train step."""
    from repro.launch.roofline import ICI_BW, PEAK_FLOPS
    t_comp = LOCAL_BATCH * RESNET50_GFLOP * 1e9 / (PEAK_FLOPS * EFF_COMPUTE)
    if devices > 1:
        wire = RESNET50_PARAMS * BYTES_PER_PARAM[reduction]
        t_ar = (2 * (devices - 1) / devices) * wire / ICI_BW
    else:
        t_ar = 0.0
    exposed = max(0.0, t_ar - OVERLAP_FRACTION * t_comp)
    return t_comp, t_ar, t_comp + exposed


def build_report() -> dict:
    rows = []
    base_ips = {r: LOCAL_BATCH / step_times_s(1, r)[2] for r in REDUCTIONS}
    for reduction in REDUCTIONS:
        for devices in DEVICE_COUNTS:
            t_comp, t_ar, t = step_times_s(devices, reduction)
            ips = devices * LOCAL_BATCH / t
            no_overlap_ips = devices * LOCAL_BATCH / (t_comp + t_ar)
            rows.append({
                "platform": "cpu",
                "devices": devices,
                "reduction": reduction,
                "images_per_s": round(ips, 1),
                "scaling_efficiency": round(
                    ips / (devices * base_ips[reduction]), 4),
                "no_overlap_efficiency": round(
                    no_overlap_ips / (devices * base_ips[reduction]), 4),
                "compute_ms": round(t_comp * 1e3, 4),
                "allreduce_ms": round(t_ar * 1e3, 4),
                "wire_bytes_per_step": int(
                    RESNET50_PARAMS * BYTES_PER_PARAM[reduction])
                if devices > 1 else 0,
            })
    from repro.launch.roofline import MODEL_TARGET
    return {
        "modeled_target": MODEL_TARGET,
        "model": "resnet50",
        "local_batch": LOCAL_BATCH,
        "gflop_per_image": RESNET50_GFLOP,
        "params": RESNET50_PARAMS,
        "kernel_efficiency": EFF_COMPUTE,
        "overlap_fraction": round(OVERLAP_FRACTION, 4),
        "rows": rows,
    }


# -- live smoke: the real DP step on fake host devices -----------------------

def _worker(args) -> None:
    """Runs in a subprocess whose XLA_FLAGS pinned the device count."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import time_call
    from repro.graph import GxM, resnet50
    from repro.launch.mesh import make_host_mesh
    from repro.train.distributed import (init_cnn_train_state_dp,
                                         make_cnn_train_step_dp,
                                         shard_cnn_batch)

    ndev = len(jax.devices())
    assert ndev == args.devices, (ndev, args.devices)
    m = GxM(resnet50(num_classes=10, stages=(1, 1, 1, 1)), num_classes=10)
    params = m.init(jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    rng = np.random.default_rng(0)
    n = args.local_batch * ndev
    batch = shard_cnn_batch(
        {"image": jnp.asarray(rng.standard_normal((n, 32, 32, 3)),
                              jnp.float32),
         "label": jnp.asarray(rng.integers(0, 10, size=(n,)))}, mesh)
    rows = []
    for reduction in REDUCTIONS:
        compress = "int8" if reduction == "int8" else "off"
        state = init_cnn_train_state_dp(params, mesh, grad_compress=compress)
        step = make_cnn_train_step_dp(m, mesh, lr=0.02,
                                      grad_compress=compress)
        state, metrics = step(state, batch)       # compile + correctness
        loss = float(metrics["loss"])
        assert np.isfinite(loss), (reduction, loss)
        us = time_call(step, state, batch, warmup=1, iters=3)
        rows.append({"platform": jax.devices()[0].platform,
                     "devices": ndev, "reduction": reduction,
                     "global_batch": n, "loss": round(loss, 4),
                     "us_per_step": round(us, 1),
                     "images_per_s": round(n / (us / 1e6), 2)})
    print("RESULT " + json.dumps({"devices": ndev, "rows": rows}))


def _spawn(devices: int, *, local_batch: int) -> list[dict]:
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "benchmarks.train_scaling_bench",
           "--worker", "--devices", str(devices),
           "--local-batch", str(local_batch)]
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=repo, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"worker x{devices} failed:\n" + out.stderr[-4000:])
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])["rows"]
    raise RuntimeError(f"worker x{devices} emitted no RESULT line:\n"
                       + out.stdout[-2000:])


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry", action="store_true",
                    help="also run the live DP-step smoke on fake devices")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--local-batch", type=int, default=2)
    args = ap.parse_args(argv)
    if args.worker:
        _worker(args)
        return

    from benchmarks.common import bench_out_path, emit
    report = build_report()
    out_path = bench_out_path(OUT_PATH)
    out_path.write_text(json.dumps(report, indent=1, sort_keys=True) + "\n")
    for r in report["rows"]:
        emit(f"train_scaling_model_n{r['devices']:02d}_{r['reduction']}", 0.0,
             f"platform={r['platform']};"
             f"imgs_per_s={r['images_per_s']};"
             f"eff={r['scaling_efficiency']};"
             f"no_overlap_eff={r['no_overlap_efficiency']}")
    emit("train_scaling_bench_json", 0, f"wrote={out_path}")

    measured = []
    if args.dry:
        base = None
        for devices in LIVE_DEVICE_COUNTS:
            rows = _spawn(devices, local_batch=args.local_batch)
            for r in rows:
                if r["devices"] == 1 and r["reduction"] == "fp32":
                    base = r["images_per_s"]
                if base:
                    r["measured_scaling_efficiency"] = round(
                        r["images_per_s"] / (r["devices"] * base), 4)
                measured.append(r)
                emit(f"train_scaling_live_d{r['devices']}_{r['reduction']}",
                     r["us_per_step"],
                     f"platform={r['platform']};"
                     f"images_per_s={r['images_per_s']};loss={r['loss']}")
    print("RESULT " + json.dumps({**report, "measured": measured}))


if __name__ == "__main__":
    main()
