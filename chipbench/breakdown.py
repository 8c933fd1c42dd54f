"""Where a cell's time goes, by the names the program gives its work.

  python3 chipbench/breakdown.py --workload <cell> --seed <n> --seconds <s>
  python3 chipbench/breakdown.py --config resnet50 --traffic server ...

Builds the cell as ``run.py`` does (or, on one chip, a configuration and
mix with no cell in BENCHMARK.json), then runs an untraced window and a traced one,
each between snapshots of the program's phase counters (``repro.obs``).
``--harness-spans`` adds a traced window with the program's spans off, the
harness's alone, to show what the program's spans cost.  No reference
check: a diagnosis, not a benchmark run.

Prints on stderr the device time and roofline share of the top 20 (GxM
task, conv pass or role) rows of the first traced window, and on stdout
one JSON line: each window's summary and phase counters, the trace's
reduction by scope (``scopes.reduce``) beside ``trace.reduce``'s, the
longest serving step's phases, and ``metrics``, the per-layer numbers the
program's spans, scopes and counters give:

  host_ms_per_batch   host ms a step outside the device's work and wait
                      (serve.take + stack + engine.pad + put + serve.post),
                      untraced window
  conv_roofline_<p>   ideal time of the kernels' pass p (fwd, bwd, wu) over
                      Mosaic time under that pass, training
  bn_xla_share        XLA time under ``bn`` / busy
  conv_glue_share     XLA time under a conv pass, XLA convs apart / busy
  mosaic_pass_share   Mosaic time under one of the conv passes / Mosaic
  mosaic_task_share   Mosaic time under a GxM task / Mosaic
  xla_scoped_share    XLA time under any program scope / XLA
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

HOST_PHASES = ("serve.take", "serve.stack", "engine.pad", "engine.put",
               "serve.post")


def plan_of(args) -> dict:
    from chipbench import bench
    if args.workload:
        return bench.resolve(args.workload)
    return {"cell": {"name": f"{args.config}.{args.traffic}",
                     "config": args.config, "traffic": args.traffic,
                     "chips": 1},
            "config": bench.load_config(args.config),
            "mix": bench.load_mix(args.traffic), "end_to_end": [],
            "per_layer": [], "here": bench.HERE}


def host_ms_per_batch(phases: dict) -> float | None:
    steps = phases.get("serve.step", {}).get("count", 0)
    if not steps:
        return None
    return 1e3 * sum(phases.get(k, {}).get("s", 0.0)
                     for k in HOST_PHASES) / steps


def longest_step(program: list) -> dict | None:
    """The longest ``serve.step`` span and the seconds of each span inside
    it, by name."""
    steps = [s for s in program if s[0] == "serve.step"]
    if not steps:
        return None
    _, a, d = max(steps, key=lambda s: s[2])
    inner: dict[str, float] = {}
    for name, s, dd in program:
        if name != "serve.step" and a <= s and s + dd <= a + d:
            inner[name] = inner.get(name, 0.0) + dd * 1e-9
    return {"s": d * 1e-9, "phases": inner}


def metrics(red: dict, busy_s: float, ctx: dict) -> dict:
    """The per-layer numbers of one traced window (see the module doc)."""
    from chipbench import counts
    out = {}
    dev, xla, mosaic = red["device_s"], red["xla_s"], red["mosaic_s"]
    if ctx["train"]:
        for short, pas in (("fwd", "conv_fwd"), ("bwd", "conv_bwd_data"),
                           ("wu", "conv_wu")):
            t = red["mosaic_by_pass"].get(pas)
            kind = "fwd_train" if short == "fwd" else short
            if t:
                ideal = ctx["steps"] * counts.kernel_ideal_s(
                    ctx["layers"], (kind,), ctx["batch"], ctx["peak"])
                out[f"conv_roofline_{short}"] = 100.0 * ideal / t
    if busy_s > 0 and dev > 0:
        out["bn_xla_share"] = 100.0 * red["bn_s"] / busy_s
        out["conv_glue_share"] = 100.0 * red["glue_s"] / busy_s
    if mosaic > 0:
        named = sum(v for k, v in red["mosaic_by_pass"].items()
                    if k != "none")
        out["mosaic_pass_share"] = 100.0 * named / mosaic
        out["mosaic_task_share"] = 100.0 * red["mosaic_task_s"] / mosaic
    if xla > 0:
        out["xla_scoped_share"] = 100.0 * red["xla_scoped_s"] / xla
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default=None)
    ap.add_argument("--config", default=None)
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--harness-spans", action="store_true",
                    help="also trace a window with the program's spans off")
    args = ap.parse_args(argv)
    if not args.workload and not (args.config and args.traffic):
        ap.error("give --workload, or --config and --traffic")
    print(json.dumps(run(plan_of(args), args.seed, args.seconds,
                         harness_spans=args.harness_spans)), flush=True)
    return 0


def run(plan: dict, seed: int, seconds: float, *, harness_spans=False,
        require_tpu=True, compile_cache=True) -> dict:
    from chipbench import bench, device, refrun, scopes, spans, trace
    from chipbench.run import log, program_hlo
    clock = device.Clock()
    cell, cfg, mix = plan["cell"], plan["config"], plan["mix"]
    dev = device.gate(cell["chips"], require_tpu=require_tpu)
    import jax
    from repro import obs
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ref = bench.family(cfg, "ref")
    gxm = plan["gxm"] = bench.family(cfg, "sut").build(cfg)
    params = refrun.init_params(ref, cfg, seed)
    train = mix["kind"] == "train"
    if train:
        from chipbench import train as drv
        st = drv.setup(plan, params, seed, clock)
        devices = st["mesh"].devices.flatten().tolist()
    else:
        from chipbench import serve as drv
        st = drv.setup(plan, params, seed, clock)
        devices = [jax.devices()[0]]
    out = {"cell": cell["name"], "seed": seed, "device": dev,
           "setup_s": clock.since_start()}

    def window():
        obs.reset()
        before = None if train else st["server"].stats()
        win = drv.window(st, plan, seconds) if train \
            else drv.window(st, plan, seed, seconds)
        summary = drv.summarize(win) if train else drv.summarize(
            win, drv.counters_since(before, st["server"].stats()))
        return {"summary": summary, "phases": obs.counters()}

    out["untraced"] = window()
    hlo = program_hlo(st, mix["kind"])
    tasks = {t.name for t in gxm.etg.tasks}
    layers = ref.conv_layers(cfg, (cfg["image"], cfg["image"]))
    # off the chip (the CPU tests) the v5e's peaks stand in
    peak = device.PEAKS.get(dev["kind"], device.PEAKS["TPU v5 lite"])
    runs = [("traced", None)] + ([("traced_harness_spans", False)]
                                 if harness_spans else [])
    for label, program_spans in runs:
        tdir = tempfile.mkdtemp(prefix="chipbench-breakdown-")
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        obs.enable(program_spans)
        spans.enable(True)
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with spans.span("window"):
                got = window()
        finally:
            jax.profiler.stop_trace()
            spans.enable(False)
            obs.enable(None)
        try:
            rec = scopes.load(trace.find_xplane(tdir), hlo)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        rec["devices"] = dict(sorted(rec["devices"].items())[:len(devices)])
        base = trace.reduce(rec)
        red = scopes.reduce(rec, tasks)
        got["trace"] = base
        got["scopes"] = red and {k: v for k, v in red.items() if k != "rows"}
        got["longest_step"] = longest_step(rec["program"])
        if red is not None and base is not None:
            s = got["summary"]
            images = (s["steps"] * mix["per_chip_batch"] if train else
                      sum(int(b) * n for b, n in s["by_bucket"].items()))
            ctx = {"train": train, "steps": s.get("steps"),
                   "batch": mix.get("per_chip_batch"), "layers": layers,
                   "peak": peak}
            got["metrics"] = metrics(red, base["busy_s"], ctx)
            got["table"] = scopes.table(red, layers, images, peak,
                                        train=train)
            if label == "traced":
                log("task, pass or role, device s, % of device time, "
                    "% of roofline:")
                for row in got["table"]:
                    log("  " + "  ".join(str(v) for v in row))
        out[label] = got
    hm = host_ms_per_batch(out["untraced"]["phases"])
    out["metrics"] = {**out["traced"].get("metrics", {}),
                      **({"host_ms_per_batch": hm} if hm else {})}
    return out


if __name__ == "__main__":
    sys.exit(main())
