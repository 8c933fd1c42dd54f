"""Forward-conv perf trajectory: tiled vs whole-plane, machine-readable.

Writes ``BENCH_conv_fwd.json`` at the repo root — per-layer images/sec and
roofline efficiency for the ResNet-50 (paper Table I) and Inception-v3
conv tables, under the *same* per-shape blocking, for both forward input
strategies:

  tiled   row-band streaming + C_b accumulation (the default kernel)
  whole   the legacy whole-plane kernel (input plane shipped per grid step)

Numbers come from the schedule-resolved roofline model
(``repro.tune.measure.conv_traffic`` + ``launch.roofline.kernel_roofline``)
so the file is reproducible on any host; ``--measure`` additionally
wall-clocks the XLA reference path per layer for a host-speed column.
Subsequent PRs diff this file to prove regressions/improvements.
"""
import json
import pathlib
import sys

from benchmarks.common import bench_out_path, emit
from repro.core.blocking import VMEM_BUDGET, conv_blocking_analytic, \
    conv_working_set
from repro.core.conv import lane_ok
from repro.graph.serving import conv_shapes, distinct_conv_signatures
from repro.graph.topology import RESNET50_LAYERS, inception_v3
from repro.launch.roofline import kernel_roofline
from repro.tune.measure import STEP_OVERHEAD_US, conv_traffic
from repro.tune.space import out_dim

MINIBATCH = 4
INCEPTION_IMAGE = (299, 299)
OUT_PATH = pathlib.Path(__file__).resolve().parents[1] / "BENCH_conv_fwd.json"


def layer_tables() -> dict[str, list[dict]]:
    """The two benchmark conv tables as tuning-shape dicts."""
    resnet = []
    for lid, l in sorted(RESNET50_LAYERS.items()):
        resnet.append(dict(name=f"L{lid:02d}", h=l["h"], w=l["w"], c=l["c"],
                           k=l["k"], r=l["r"], s=l["s"], stride=l["stride"],
                           padding=l["r"] // 2))
    from repro.graph.etg import build_etg
    etg = build_etg(inception_v3(num_classes=1000))
    sigs = distinct_conv_signatures(conv_shapes(etg, INCEPTION_IMAGE))
    inception = [dict(name=f"I{i:02d}", **sg) for i, sg in enumerate(sigs)]
    return {"resnet50": resnet, "inception_v3": inception}


def _variant(shape: dict, blk, *, whole: bool) -> dict:
    """Modeled cost/traffic/efficiency of one layer under one input
    strategy (same blocking — the runtime A/B the tiling knob performs)."""
    t = conv_traffic(shape, blk, minibatch=MINIBATCH, kind="fwd",
                     whole_plane=whole)
    roof = kernel_roofline(flops=t["flops"], hbm_bytes=t["hbm_bytes"],
                           util=t["util"], n_steps=t["n_steps"],
                           step_overhead_s=STEP_OVERHEAD_US * 1e-6)
    q = out_dim(shape["w"], shape["s"], shape["stride"], shape["padding"])
    vmem = conv_working_set(
        h=shape["h"], w=shape["w"], c=shape["c"], k_blk=blk.k_blk,
        r=shape["r"], s=shape["s"], q=q, rb_p=blk.rb_p,
        padding=shape["padding"], stride=shape["stride"],
        c_blk=None if whole else blk.c_blk, whole_plane=whole)
    return {
        "cost_us": round(roof["cost_s"] * 1e6, 3),
        "images_per_sec": round(MINIBATCH / roof["cost_s"], 1),
        "hbm_bytes": int(t["hbm_bytes"]),
        "hbm_input_bytes": int(t["x_bytes"]),
        "hbm_output_bytes": int(t["o_bytes"]),
        "roofline_efficiency": round(roof["efficiency"], 4),
        "dominant": roof["dominant"],
        "vmem_working_set": int(vmem),
        "fits_vmem": bool(vmem <= VMEM_BUDGET),
        "grid_steps": int(t["n_steps"]),
    }


def layer_record(shape: dict, *, measure: bool = False) -> dict:
    blk = conv_blocking_analytic(
        h=shape["h"], w=shape["w"], c=shape["c"], k=shape["k"], r=shape["r"],
        s=shape["s"], stride=shape["stride"], padding=shape["padding"])
    rec = {
        "layer": shape["name"],
        "shape": {f: shape[f] for f in ("h", "w", "c", "k", "r", "s",
                                        "stride", "padding")},
        "path": "direct" if lane_ok(shape["c"], shape["k"]) else "im2col",
        "blocking": {"rb_p": blk.rb_p, "k_blk": blk.k_blk,
                     "c_blk": blk.c_blk, "order": blk.order},
        "tiled": _variant(shape, blk, whole=False),
        "whole_plane": _variant(shape, blk, whole=True),
    }
    if measure:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.common import time_call
        from repro.kernels import ref
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(
            (MINIBATCH, shape["h"], shape["w"], shape["c"])), jnp.float32)
        w = jnp.asarray(rng.standard_normal(
            (shape["r"], shape["s"], shape["c"], shape["k"])) * 0.1,
            jnp.float32)
        fn = jax.jit(lambda x, w: ref.conv2d(
            x, w, stride=shape["stride"], padding=shape["padding"]))
        rec["host_xla_us"] = round(time_call(fn, x, w), 1)
    return rec


def build_report(*, measure: bool = False) -> dict:
    tables = {}
    for tname, layers in layer_tables().items():
        tables[tname] = [layer_record(sh, measure=measure) for sh in layers]
    return {
        "minibatch": MINIBATCH,
        "vmem_budget": VMEM_BUDGET,
        "model": "tpu-v5e roofline (repro.tune.measure.conv_traffic)",
        "inception_image": list(INCEPTION_IMAGE),
        "tables": tables,
    }


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else (argv or [])
    report = build_report(measure="--measure" in argv)
    out_path = bench_out_path(OUT_PATH)
    out_path.write_text(json.dumps(report, indent=1, sort_keys=True) + "\n")
    for tname, recs in report["tables"].items():
        for rec in recs:
            t, wp = rec["tiled"], rec["whole_plane"]
            emit(f"conv_fwd_{tname}_{rec['layer']}_tiled", t["cost_us"],
                 f"imgs_s={t['images_per_sec']};eff={t['roofline_efficiency']};"
                 f"hbm_ratio={t['hbm_bytes'] / max(wp['hbm_bytes'], 1):.3f};"
                 f"ws_ratio={t['vmem_working_set'] / wp['vmem_working_set']:.3f};"
                 f"whole_fits_vmem={int(wp['fits_vmem'])}")
    emit("conv_fwd_bench_json", 0, f"wrote={out_path}")


if __name__ == "__main__":
    main()
