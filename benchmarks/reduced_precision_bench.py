"""Paper Fig. 8 (§II-K reduced precision), TPU serving edition: int8
weights with f32 accumulation.  Measures quantization error on a real
smoke model and reports the modeled decode speedup per arch (bytes-bound
roofline: < 2x because KV/activations stay bf16 — the same reason the
paper's int16 kernels got 1.6x, not 2x).

``build_report()`` is the machine-checkable half (pinned by
``tests/test_reduced_precision_bench.py``): the analytic per-arch decode
roofline, quantized vs not — modeled speedup must be > 1 (halving weight
bytes always helps a bytes-bound decode) and < 2 (only the weights
shrink).  ``main()`` additionally runs the numerical-drift measurement on
a real smoke model.

``build_q8_report()`` is the CNN half (the paper's actual Fig. 8 subject):
the schedule-resolved tiled int8 forward vs the tiled f32 forward over the
ResNet-50 / Inception-v3 conv tables, under each path's own analytic
blocking — int8 bands are 4x smaller, so the q8 blocking re-spends the
freed VMEM on taller row bands (``kind="q8"`` grow-to-budget) on top of
the 4x input/weight byte shrink.  Written to ``BENCH_q8_infer.json`` and
gated by ``repro.perfci`` (the ISSUE floor: >= 1.6x on every
bandwidth-bound ResNet-50 layer).  A layer counts as *bandwidth-bound*
only when HBM time is the largest term of its f32 modeled cost — above
compute time *and* above the aggregate grid-step overhead: int8 cannot
speed up launch overhead, so overhead-bound 7x7 tails (L19) report their
honest ratio but stay out of the floor's denominator.
"""
import json
import pathlib

from repro.configs import SHAPES, get_config
from repro.launch import analytic as A

ARCHS = ("qwen3-8b", "jamba-1.5-large-398b", "dbrx-132b")
SHAPE_NAME = "decode_32k"
CHIPS = 256
MODEL_PAR = 16
DATA_PAR = 16

Q8_OUT_PATH = pathlib.Path(__file__).resolve().parents[1] \
    / "BENCH_q8_infer.json"


def build_report() -> dict:
    shape = SHAPES[SHAPE_NAME]
    rows = []
    for arch in ARCHS:
        c = get_config(arch)
        base = A.analytic_roofline(c, shape, chips=CHIPS,
                                   model_par=MODEL_PAR, data_par=DATA_PAR)
        q = A.analytic_roofline(c, shape, chips=CHIPS, model_par=MODEL_PAR,
                                data_par=DATA_PAR, quantized=True)
        rows.append({
            "arch": arch,
            "base_step_us": round(base.step_time_s * 1e6, 3),
            "quantized_step_us": round(q.step_time_s * 1e6, 3),
            "modeled_speedup": round(base.step_time_s / q.step_time_s, 4),
            "base_dominant": base.dominant,
            "quantized_dominant": q.dominant,
        })
    return {"shape": SHAPE_NAME, "chips": CHIPS, "model_par": MODEL_PAR,
            "data_par": DATA_PAR, "rows": rows}


def _q8_variant(args: dict, minibatch: int, *, kind: str,
                dtype_bytes: int) -> tuple[dict, dict]:
    """(record, roofline) for one layer under one precision's own analytic
    blocking — the same model stack as ``conv_fwd_bench._variant``."""
    from repro.core.blocking import (VMEM_BUDGET, conv_blocking_analytic,
                                     conv_working_set)
    from repro.launch.roofline import kernel_roofline
    from repro.tune.measure import STEP_OVERHEAD_US, conv_traffic
    from repro.tune.space import out_dim
    blk = conv_blocking_analytic(**args, dtype_bytes=dtype_bytes, kind=kind)
    t = conv_traffic(dict(args, dtype_bytes=dtype_bytes), blk,
                     minibatch=minibatch, kind=kind)
    roof = kernel_roofline(flops=t["flops"], hbm_bytes=t["hbm_bytes"],
                           util=t["util"], n_steps=t["n_steps"],
                           step_overhead_s=STEP_OVERHEAD_US * 1e-6)
    q = out_dim(args["w"], args["s"], args["stride"], args["padding"])
    vmem = conv_working_set(
        h=args["h"], w=args["w"], c=args["c"], k_blk=blk.k_blk, r=args["r"],
        s=args["s"], q=q, rb_p=blk.rb_p, padding=args["padding"],
        stride=args["stride"], c_blk=blk.c_blk, dtype_bytes=dtype_bytes,
        kind=kind)
    rec = {
        "cost_us": round(roof["cost_s"] * 1e6, 3),
        "hbm_bytes": int(t["hbm_bytes"]),
        "roofline_efficiency": round(roof["efficiency"], 4),
        "dominant": roof["dominant"],
        "vmem_working_set": int(vmem),
        "fits_vmem": bool(vmem <= VMEM_BUDGET),
        "grid_steps": int(t["n_steps"]),
        "rb_p": blk.rb_p,
    }
    return rec, roof


def _analytic_q8_speedup(args: dict, minibatch: int) -> float:
    """Blocking-free ideal-traffic speedup: minimal x/w/o bytes at each
    precision (f32 out in both), rooflined with no refetch, no overhead.
    The measured table must agree with this up to schedule effects — the
    drift band ``tests/test_reduced_precision_bench.py`` pins."""
    from repro.launch.roofline import kernel_roofline
    from repro.tune.space import out_dim
    p = out_dim(args["h"], args["r"], args["stride"], args["padding"])
    q = out_dim(args["w"], args["s"], args["stride"], args["padding"])
    x_e = minibatch * args["h"] * args["w"] * args["c"]
    w_e = args["r"] * args["s"] * args["c"] * args["k"]
    o_e = minibatch * p * q * args["k"]
    flops = 2.0 * o_e * args["c"] * args["r"] * args["s"]
    f32 = kernel_roofline(flops=flops, hbm_bytes=4 * (x_e + w_e + o_e),
                          n_steps=0, step_overhead_s=0.0)
    q8 = kernel_roofline(flops=flops, hbm_bytes=x_e + w_e + 4 * o_e,
                         n_steps=0, step_overhead_s=0.0)
    return f32["cost_s"] / q8["cost_s"]


def build_q8_report() -> dict:
    from benchmarks.conv_fwd_bench import MINIBATCH, layer_tables
    from repro.core.blocking import VMEM_BUDGET
    from repro.core.conv import lane_ok
    tables = {}
    summary = {}
    for tname, layers in layer_tables().items():
        recs, bw_speedups = [], []
        for sh in layers:
            args = {f: sh[f] for f in ("h", "w", "c", "k", "r", "s",
                                       "stride", "padding")}
            if not lane_ok(sh["c"], sh["k"]):
                # small-C stem: the q8 kernel never runs (im2col fallback)
                recs.append({"layer": sh["name"], "shape": args,
                             "path": "im2col"})
                continue
            f32, f32_roof = _q8_variant(args, MINIBATCH, kind="fwd",
                                        dtype_bytes=4)
            q8, q8_roof = _q8_variant(args, MINIBATCH, kind="q8",
                                      dtype_bytes=1)
            overhead_s = f32_roof["cost_s"] - f32_roof["step_time_s"]
            bandwidth_bound = (f32_roof["dominant"] == "memory"
                               and f32_roof["memory_s"] >= overhead_s)
            speedup = round(f32_roof["cost_s"] / q8_roof["cost_s"], 4)
            if bandwidth_bound:
                bw_speedups.append(speedup)
            recs.append({
                "layer": sh["name"], "shape": args, "path": "direct",
                "f32": f32, "q8": q8, "speedup": speedup,
                "analytic_speedup": round(
                    _analytic_q8_speedup(args, MINIBATCH), 4),
                "bandwidth_bound": bandwidth_bound,
            })
        tables[tname] = recs
        summary[tname] = {
            "min_bw_speedup": round(min(bw_speedups), 4) if bw_speedups
            else None,
            "bandwidth_bound_layers": len(bw_speedups),
        }
    return {
        "minibatch": MINIBATCH,
        "vmem_budget": VMEM_BUDGET,
        "model": "tpu-v5e roofline (repro.tune.measure.conv_traffic, "
                 "int8 x/w bytes, f32 out)",
        "tables": tables,
        "summary": summary,
    }


def main_q8(argv=None) -> None:
    """Emit the CNN int8-vs-f32 table + write BENCH_q8_infer.json."""
    from benchmarks.common import bench_out_path, emit
    report = build_q8_report()
    out_path = bench_out_path(Q8_OUT_PATH)
    out_path.write_text(json.dumps(report, indent=1, sort_keys=True) + "\n")
    for tname, recs in report["tables"].items():
        for rec in recs:
            if rec.get("path") != "direct":
                continue
            emit(f"q8_infer_{tname}_{rec['layer']}", rec["q8"]["cost_us"],
                 f"speedup={rec['speedup']:.2f}x;"
                 f"analytic={rec['analytic_speedup']:.2f}x;"
                 f"bw_bound={int(rec['bandwidth_bound'])};"
                 f"rbp={rec['f32']['rb_p']}->{rec['q8']['rb_p']}")
    for tname, s in report["summary"].items():
        emit(f"q8_infer_{tname}_summary", 0,
             f"min_bw_speedup={s['min_bw_speedup']};"
             f"bw_layers={s['bandwidth_bound_layers']}")
    emit("q8_infer_bench_json", 0, f"wrote={out_path}")


def main():
    import jax
    import jax.numpy as jnp

    from benchmarks.common import emit, time_call
    from repro.configs import smoke_config
    from repro.core.quantize import dequantize, quantize_int8
    from repro.nn import transformer as T

    # numerical error on a real (smoke) model + decode logits drift
    cfg = smoke_config(get_config("qwen2-1.5b"))
    params, _ = T.init_lm(jax.random.PRNGKey(0), cfg)
    qp = quantize_int8(params, min_size=64)
    deq = dequantize(qp, jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    lf, _ = T.forward(params, cfg, tokens=toks)
    lq, _ = T.forward(deq, cfg, tokens=toks)
    drift = float(jnp.abs(jax.nn.softmax(lf) - jax.nn.softmax(lq)).max())
    f = jax.jit(lambda p, t: T.forward(p, cfg, tokens=t)[0])
    us = time_call(f, deq, toks)
    emit("int8_weights_fwd", us, f"softmax_drift={drift:.4f}")

    # modeled decode speedup per arch (memory-roofline ratio)
    for r in build_report()["rows"]:
        emit(f"int8_decode_model_{r['arch']}", r["quantized_step_us"],
             f"speedup={r['modeled_speedup']:.2f}x;"
             f"dominant={r['quantized_dominant']}")

    # the CNN tiled-int8 table (§II-K proper) + its perf-gate artifact
    main_q8()


if __name__ == "__main__":
    main()
