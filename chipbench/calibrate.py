"""Readings for the limits of ``correct``: one cell run on many seeds in
one process, the program built once, as the program, as the control and
with faults planted.  Prints one JSON line per mode and seed with the
compared numbers and their detail.

  python3 chipbench/calibrate.py --workload resnet50.train \\
      --seeds 1,2,3 --seconds 1 --modes program,control:2,half_batch:2

A mode is ``program``, ``control`` (the reference at the control's lower
precision in the program's place) or a fault of ``chipbench/faults.py``;
``mode:n`` runs it on the first ``n`` seeds only.
Not used by a measured run; it exists to set and re-check the limits in
each configuration's file.
"""
from __future__ import annotations

import argparse
import io
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def main(argv=None) -> int:
    from chipbench import bench, faults, run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--modes", default="program",
                    help="comma-separated: program, control or a fault "
                         f"({', '.join(faults.ALL)}), each optionally "
                         "':n', the first n seeds only")
    args = ap.parse_args(argv)
    modes = [(m.split(":")[0], int(m.split(":")[1]) if ":" in m else None)
             for m in args.modes.split(",")]
    bad = [m for m, _ in modes
           if m not in ("program", "control", *faults.ALL)]
    if bad:
        ap.error(f"unknown modes {bad}")
    plan = bench.resolve(args.workload)
    keep: dict = {}
    seeds = [int(s) for s in args.seeds.split(",")]
    for mode, n in modes:
        control = mode == "control"
        fault = mode if mode in faults.ALL else None
        for seed in seeds[:n]:
            err = io.StringIO()
            real, sys.stderr = sys.stderr, err
            try:
                res = run.run(plan, seed, args.seconds, False, keep=keep,
                              control=control, fault=fault)
            finally:
                sys.stderr = real
            detail = [ln for ln in err.getvalue().splitlines()
                      if ln.startswith(("check detail", "window:",
                                        "compiles in window", "garbage",
                                        "reference and check"))]
            print(json.dumps({"seed": seed, "mode": mode,
                              "correct": res["correct"],
                              "checks": {k: v["value"] for k, v in
                                         res["checks"].items()},
                              "detail": detail}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
