"""A cell small enough for the CPU: ResNet v1.5 at its published widths
with one bottleneck per stage, 32x32 images and 10 classes, driven
through the whole harness without the chip gate.

Its limits are this size's own, set between CPU readings of sound runs
(serve logit_err <= 1.7e-6; train grad_gap <= 1.9e-6) and of the control,
three bf16 passes (logit_err >= 1.9e-5; grad_gap >= 2.7e-3).  At this
size batch norm runs over 4 values in the last stage, so the later steps'
loss and change swing from seed to seed on sound runs as on the control
(loss_err up to 6.8e-4, change_gap up to 1.9e-2 on sound runs); their
limits sit above those swings and catch only gross faults."""
from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import bench  # noqa: E402

LIMITS = {"serve": {"logit_err": 6e-6},
          "train": {"loss_err": 1e-2, "grad_gap": 1e-4, "change_gap": 0.2}}
MIXES = {
    "server": {"kind": "server", "rate_per_s": 40.0, "max_bucket": 4,
               "pool_images": 8, "sample": 1000},
    "offline": {"kind": "offline", "bucket": 4, "pool_images": 8,
                "sample": 1000},
    "train": {"kind": "train", "per_chip_batch": 4, "pool_batches": 4,
              "lr": 0.01, "bn_momentum": 0.9, "check_steps": 3},
}
E2E = {"server": ("serve_p99_ms", "ms"),
       "offline": ("serve_images_per_s", "images/s"),
       "train": ("train_images_per_s", "images/s")}


def plan(kind: str, chips: int = 1) -> dict:
    cfg = bench.load_config("resnet50")
    cfg.update(image=32, num_classes=10, stages=[1, 1, 1, 1], ref_block=4,
               limits=LIMITS)
    name, unit = E2E[kind]
    e2e = [{"name": "setup_s", "unit": "s"}, {"name": name, "unit": unit}]
    return {"cell": {"name": f"tiny.{kind}", "config": "tiny",
                     "traffic": kind, "chips": chips},
            "config": cfg, "mix": dict(MIXES[kind]), "here": bench.HERE,
            "end_to_end": e2e, "per_layer": []}


def run(kind: str, *, seed: int = 2**31 + 7, chips: int = 1, keep=None,
        **kw) -> dict:
    from chipbench.run import run as run_cell
    return run_cell(plan(kind, chips), seed, 1.0, False, require_tpu=False,
                    compile_cache=False, keep=keep, **kw)
