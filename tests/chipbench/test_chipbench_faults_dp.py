"""A whole data-parallel training run over four CPU devices at a small
size: sound, it is correct; with the exchange between devices left out,
it is not.  Runs in a child process that asks for four host devices."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

CHILD = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import chipbench_tiny as tiny
out = {}
for fault in (None, "no_exchange"):
    res = tiny.run("train", chips=4, fault=fault)
    out[str(fault)] = {"correct": res["correct"], "checks": res["checks"],
                       "count": res["device"]["count"]}
print(json.dumps(out))
"""


def test_dp_run_and_the_missing_exchange():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, "-c", CHILD, str(HERE)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["None"]["count"] == 4
    assert out["None"]["correct"], out["None"]["checks"]
    assert not out["no_exchange"]["correct"]
