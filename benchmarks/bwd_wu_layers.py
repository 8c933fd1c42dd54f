"""Paper Fig. 5 (a)/(b): backward-data (via duality) and weight-update
passes — machine-readable training-pass perf trajectory.

Writes ``BENCH_bwd_wu.json`` at the repo root — for the full ResNet-50
(paper Table I, *real* shapes, the 224×224 stem included — the seed bench
capped layers at h ≤ 56 and extrapolated) and Inception-v3 conv tables:

  wu        tiled (band-streamed, C/Q-blocked, ceil-div tails) vs legacy
            (whole padded plane shipped per grid step, rb_p | P) update
            pass, each under its own analytic blocking — the runtime A/B
            of the ``REPRO_CONV_TILING`` knob;
  bwd_data  phase-decomposed (stride² sub-convs over undilated dO) vs
            dilate (materialized dilated dO) duality plans — the runtime
            A/B of the ``REPRO_BWD_DUALITY`` knob.  Single-conv scenarios
            (stride 1 / 1x1) cost identically under both plans.

Numbers come from the schedule-resolved roofline model
(``repro.tune.measure.conv_traffic`` / ``bwd_data_traffic`` +
``launch.roofline.kernel_roofline`` / ``composite_roofline``) so the file is
reproducible on any host; ``--measure`` additionally wall-clocks the XLA
reference path per layer for a host-speed column.
``tests/test_bwd_wu_bench.py`` pins tiled ≤ legacy and phase ≤ dilate on
every benchmarked layer.
"""
import json
import pathlib
import sys

from benchmarks.common import bench_out_path, emit
from benchmarks.conv_fwd_bench import layer_tables
from repro.configs.shapes import STEM_CONV
from repro.core.blocking import (VMEM_BUDGET, conv_blocking_analytic,
                                 conv_working_set)
from repro.core.conv import lane_ok
from repro.core.duality import bwd_data_plan
from repro.core.wu_strategy import choose_wu_strategy
from repro.launch.roofline import composite_roofline, kernel_roofline
from repro.tune.measure import (STEP_OVERHEAD_US, bwd_data_traffic,
                                conv_traffic)
from repro.tune.space import out_dim

MINIBATCH = 4
OUT_PATH = pathlib.Path(__file__).resolve().parents[1] / "BENCH_bwd_wu.json"


def bench_tables() -> dict[str, list[dict]]:
    """The fwd-bench tables plus the lane-padded stem regression shape —
    the layer the seed bench could never run un-extrapolated."""
    tables = layer_tables()
    stem = {f: STEM_CONV[f] for f in ("h", "w", "c", "k", "r", "s",
                                      "stride", "padding")}
    tables["regression"] = [dict(name=STEM_CONV["name"], **stem)]
    return tables


def _wu_variant(shape: dict, blk, *, whole: bool) -> dict:
    """Modeled cost/traffic of the update pass under one input strategy,
    each with its own analytic blocking (what the knob actually runs)."""
    t = conv_traffic(shape, blk, minibatch=MINIBATCH, kind="wu",
                     whole_plane=whole)
    roof = kernel_roofline(flops=t["flops"], hbm_bytes=t["hbm_bytes"],
                           util=t["util"], n_steps=t["n_steps"],
                           step_overhead_s=STEP_OVERHEAD_US * 1e-6)
    q = out_dim(shape["w"], shape["s"], shape["stride"], shape["padding"])
    vmem = conv_working_set(
        h=shape["h"], w=shape["w"], c=shape["c"], k_blk=blk.k_blk,
        r=shape["r"], s=shape["s"], q=q, rb_p=blk.rb_p,
        padding=shape["padding"], stride=shape["stride"],
        c_blk=None if whole else blk.c_blk, whole_plane=whole, kind="wu")
    return {
        "blocking": {"rb_p": blk.rb_p,
                     "k_blk": blk.k_blk, "c_blk": shape["c"] if whole
                     else blk.c_blk},
        "cost_us": round(roof["cost_s"] * 1e6, 3),
        "hbm_bytes": int(t["hbm_bytes"]),
        "hbm_input_bytes": int(t["x_bytes"]),
        "hbm_dout_bytes": int(t["w_bytes"]),
        "roofline_efficiency": round(roof["efficiency"], 4),
        "dominant": roof["dominant"],
        "vmem_working_set": int(vmem),
        "fits_vmem": bool(vmem <= VMEM_BUDGET),
        "grid_steps": int(t["n_steps"]),
    }


def _bwd_variant(shape: dict, *, mode: str) -> dict:
    t = bwd_data_traffic(shape, minibatch=MINIBATCH, mode=mode)
    roof = composite_roofline(t["parts"], extra_hbm_bytes=t["extra_hbm_bytes"],
                              step_overhead_s=STEP_OVERHEAD_US * 1e-6)
    return {
        "cost_us": round(roof["cost_s"] * 1e6, 3),
        "hbm_bytes": int(roof["hbm_bytes"]),
        "extra_hbm_bytes": int(t["extra_hbm_bytes"]),
        "flops": roof["flops"],
        "n_convs": t["n_convs"],
        "roofline_efficiency": round(roof["efficiency"], 4),
    }


def layer_record(shape: dict, *, measure: bool = False) -> dict:
    geom = dict(h=shape["h"], w=shape["w"], c=shape["c"], k=shape["k"],
                r=shape["r"], s=shape["s"], stride=shape["stride"],
                padding=shape["padding"])
    tiled_blk = conv_blocking_analytic(**geom, kind="wu")
    legacy_blk = conv_blocking_analytic(**geom, require_divisor=True,
                                        kind="wu")
    p = out_dim(shape["h"], shape["r"], shape["stride"], shape["padding"])
    q = out_dim(shape["w"], shape["s"], shape["stride"], shape["padding"])
    scen, _ = bwd_data_plan(r=shape["r"], s=shape["s"],
                            stride=shape["stride"],
                            padding=shape["padding"],
                            input_hw=(shape["h"], shape["w"]), mode="phase")
    strat = choose_wu_strategy(n=256, c=shape["c"], k=shape["k"],
                               h=shape["h"], w=shape["w"], p=p, q=q,
                               r=shape["r"], s=shape["s"], n_workers=256)
    rec = {
        "layer": shape["name"],
        "shape": geom,
        "path": "direct" if lane_ok(shape["c"], shape["k"]) else "im2col",
        "duality_scenario": scen,
        "wu_strategy": strat.strategy,
        "wu": {
            "tiled": _wu_variant(shape, tiled_blk, whole=False),
            "whole_plane": _wu_variant(shape, legacy_blk, whole=True),
        },
        "bwd_data": {
            "phase": _bwd_variant(shape, mode="phase"),
            "dilate": _bwd_variant(shape, mode="dilate"),
        },
    }
    if measure:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.common import time_call
        from repro.core.conv import (conv2d_bwd_data_via_fwd,
                                     conv2d_bwd_weights)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(
            (MINIBATCH, shape["h"], shape["w"], shape["c"])), jnp.float32)
        do = jnp.asarray(rng.standard_normal(
            (MINIBATCH, p, q, shape["k"])), jnp.float32)
        w = jnp.asarray(rng.standard_normal(
            (shape["r"], shape["s"], shape["c"], shape["k"])) * 0.05,
            jnp.float32)
        bwd = jax.jit(lambda do, w: conv2d_bwd_data_via_fwd(
            do, w, stride=shape["stride"], padding=shape["padding"],
            input_hw=(shape["h"], shape["w"]), impl="xla"))
        wu = jax.jit(lambda x, do: conv2d_bwd_weights(
            x, do, stride=shape["stride"], padding=shape["padding"],
            filter_rs=(shape["r"], shape["s"]), impl="xla"))
        rec["host_xla_bwd_us"] = round(time_call(bwd, do, w), 1)
        rec["host_xla_wu_us"] = round(time_call(wu, x, do), 1)
    return rec


def build_report(*, measure: bool = False) -> dict:
    tables = {}
    for tname, layers in bench_tables().items():
        tables[tname] = [layer_record(sh, measure=measure) for sh in layers]
    return {
        "minibatch": MINIBATCH,
        "vmem_budget": VMEM_BUDGET,
        "model": "tpu-v5e roofline (repro.tune.measure.conv_traffic / "
                 "bwd_data_traffic)",
        "tables": tables,
    }


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else (argv or [])
    report = build_report(measure="--measure" in argv)
    out_path = bench_out_path(OUT_PATH)
    out_path.write_text(json.dumps(report, indent=1, sort_keys=True) + "\n")
    for tname, recs in report["tables"].items():
        for rec in recs:
            wt, wl = rec["wu"]["tiled"], rec["wu"]["whole_plane"]
            bp, bd = rec["bwd_data"]["phase"], rec["bwd_data"]["dilate"]
            emit(f"bwd_wu_{tname}_{rec['layer']}_wu", wt["cost_us"],
                 f"legacy_us={wl['cost_us']};"
                 f"hbm_ratio={wt['hbm_bytes'] / max(wl['hbm_bytes'], 1):.4f};"
                 f"ws_ratio={wt['vmem_working_set'] / wl['vmem_working_set']:.3f};"
                 f"wu_strategy={rec['wu_strategy']}")
            emit(f"bwd_wu_{tname}_{rec['layer']}_bwd", bp["cost_us"],
                 f"dilate_us={bd['cost_us']};"
                 f"hbm_ratio={bp['hbm_bytes'] / max(bd['hbm_bytes'], 1):.4f};"
                 f"duality={rec['duality_scenario']};n_convs={bp['n_convs']}")
    emit("bwd_wu_bench_json", 0, f"wrote={out_path}")


if __name__ == "__main__":
    main()
