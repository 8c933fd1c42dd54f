"""Chip benchmark of the repo's CNN serving and training paths.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
      --trace <0|1>

Runs one cell of BENCHMARK.json on the chip(s) it names: loads, warms up
every shape the window uses, measures for ``--seconds``, checks what the
timed path produced against the configuration's plain reference, and
prints one JSON line last on stdout: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``.  The measured window is never traced: with ``--trace 1``
a second window of the same length follows it under the profiler, and
the device's metrics come from that one.  The numbers compared, each with its limit, are the last
lines on stderr and the line's last key, ``checks``.

It exits non-zero, printing no result, unless JAX runs on a TPU with the
cell's chips.  ``--control`` puts the reference at the control's lower
precision in the program's place and ``--fault`` plants a fault under the
timed path; both exist to show that ``correct`` turns false, and a
measured run uses neither.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _paths() -> None:
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def check_params(sut_params_shape, params) -> None:
    """The reference's weight layout is the program's."""
    import jax
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                        sut_params_shape)
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    if want != got:
        raise RuntimeError("chipbench: the reference's weight layout is not "
                           "the program's")


def run(plan: dict, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, control: bool = False,
        fault: str | None = None, clock=None, keep: dict | None = None,
        compile_cache: bool = True, keep_trace: str | None = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``keep`` carries the built program from one run to the next in one
    process (``calibrate.py``); a measured run starts without it.
    ``compile_cache=False`` leaves JAX's persistent cache as it is (the
    benchmark's own tests on the CPU)."""
    from chipbench import device
    clock = clock or device.Clock()
    cell, cfg, mix = plan["cell"], plan["config"], plan["mix"]
    dev = device.gate(cell["chips"], require_tpu=require_tpu)
    clock.lap("import")

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from chipbench import bench, check, faults, refrun, spans
    cache_dir = None
    if compile_cache:
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    watch = device.CompileWatch()
    ref = bench.family(cfg, "ref")
    sut = bench.family(cfg, "sut")
    keep = {} if keep is None else keep
    if "gxm" not in keep:
        keep["gxm"] = sut.build(cfg)
    gxm = plan["gxm"] = keep["gxm"]
    params = refrun.init_params(ref, cfg, seed)
    check_params(jax.eval_shape(gxm.init, jax.random.PRNGKey(0)), params)
    clock.lap("init")

    kind = mix["kind"]
    if kind == "train":
        from chipbench import train as drv
        step_factory = None
        # a fault planted while the step is traced needs a step of its own
        skey = f"step.{fault}" if fault in faults.TRACE_FAULTS else "step"
        if control:
            trainer = keep.get("control") or refrun.RefTrainer(
                ref, cfg, mix, cell["chips"], "high3")
            keep["control"] = trainer
            step_factory = lambda mesh: refrun.control_step(trainer)  # noqa
        elif skey in keep:
            step_factory = lambda mesh: keep[skey]  # noqa: E731
        with (faults.TRACE_FAULTS[fault]() if fault in faults.TRACE_FAULTS
              else contextlib.nullcontext()):
            st = drv.setup(plan, params, seed, clock,
                           step_factory=step_factory,
                           fault=faults.STEP_FAULTS.get(fault))
        if not control:
            keep[skey] = st["base_step"]
    else:
        from chipbench import serve as drv
        factory = None
        if control:
            factory = lambda b: refrun.ControlEngine(  # noqa: E731
                ref, cfg, params, b, "high3")
        elif "engine" in keep:
            def factory(b):
                keep["engine"].params = params
                return keep["engine"]
        st = drv.setup(plan, params, seed, clock, engine_factory=factory)
        if not control:
            keep["engine"] = st["engine"]
        if fault in faults.ENGINE_FAULTS:
            st["recorder"].engine = faults.ENGINE_FAULTS[fault](
                st["recorder"].engine)
    setup_s = clock.since_start()
    setup_split = dict(clock.phases)
    devices = st["mesh"].devices.flatten().tolist() if kind == "train" \
        else [jax.devices()[0]]

    def window():
        return drv.window(st, plan, seconds) if kind == "train" \
            else drv.window(st, plan, seed, seconds)

    def summarize(win, before=None):
        if kind == "train":
            return drv.summarize(win)
        return drv.summarize(win, drv.counters_since(
            before, st["server"].stats()))

    # -- the measured window, never traced ---------------------------------
    watch.mark()
    gcw = device.GcWatch()
    gcw.on = True
    win = window()
    gcw.close()
    in_window = watch.delta()
    summary = summarize(win)
    mem = device.memory_stats(devices)
    memory_peak = device.memory_peak_bytes(mem)

    # -- with --trace 1, a second window under the profiler ----------------
    # The device's metrics come from it, the host-clock ones from the
    # measured window: tracing slows a serving host several-fold.
    traced, hlo = None, []
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        before = None if kind == "train" else st["server"].stats()
        spans.enable(True)
        watch.mark()
        # the harness's spans on the host (level 1), no Python tracer
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        with spans.span("window"):
            twin = window()
        jax.profiler.stop_trace()
        spans.enable(False)
        traced = summarize(twin, before)
        traced["compiles"] = watch.delta()["compiles"]
        del twin
        if not control:
            hlo = program_hlo(st, kind)

    # -- the reference, once the program's state is freed ----------------------
    clock.lap("window")
    if kind == "train":
        prog, p0 = st["prog"], st["p0"]
        gb = st["global_batch"]
        del st, win
        gc.collect()
        trainer = keep.get("reference") or refrun.RefTrainer(
            ref, cfg, mix, cell["chips"], "highest")
        keep["reference"] = trainer
        batches = drv.make_batches(cfg, mix, seed, gb)
        want = refrun.train_steps(trainer, mix, refrun.init_params(
            ref, cfg, seed), batches)
        numbers = check.train_numbers(p0, prog, want, mix["lr"])
        limits = cfg["limits"]["train"]
    else:
        rows = drv.served_rows(win)
        pool = st["pool"]
        del st
        gc.collect()
        chosen = check_sample(rows, win, seed, mix["sample"])
        images = pool[[rows[k][1] for k in chosen]]
        want = refrun.serve_logits(ref, cfg, params, images,
                                   block=cfg["ref_block"])
        got = (np.stack([rows[k][2] for k in chosen]) if chosen
               else np.zeros((0, 0)))
        numbers = {"logit_err": check.logit_err(got, want)
                   if chosen else float("inf")}
        limits = cfg["limits"]["serve"]
    correct, checks = check.verdict(numbers, limits)
    correct = correct and summary["failed"] == 0
    clock.lap("reference")

    # -- the line ---------------------------------------------------------------
    result = {"correct": bool(correct), "attempted": summary["attempted"],
              "failed": summary["failed"]}
    metrics, breakdown = {}, None
    if trace:
        ctx = {"summary": summary, "traced": traced, "cell": cell,
               "config": cfg, "mix": mix,
               "chips": cell["chips"], "device_kind": dev["kind"],
               "ref": ref, "trace": None}
        from chipbench import trace as tr
        try:
            red = tr.reduce_dir(tdir, hlo, n_devices=len(devices),
                                keep=keep_trace)
        except Exception as e:  # noqa: BLE001 — metrics left out, said why
            log(f"chipbench: trace not read: {type(e).__name__}: {e}")
            red = None
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx["trace"] = red
        for m in plan["per_layer"]:
            v = bench.metric_reader(m["name"], plan["here"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if red is not None:
            dev = {**dev, "busy_s": red["busy_s"],
                   "window_s": red["window_s"]}
            breakdown = red["breakdown"]
    else:
        e2e = {**summary, "setup_s": setup_s}
        for m in plan["end_to_end"]:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = {**dev, "memory_peak_bytes": memory_peak}
    if breakdown is not None:
        result["breakdown"] = breakdown

    # -- what a reader of stderr needs, the compared numbers last ----------------
    log(f"chipbench: {cell['name']} seed {seed} seconds {seconds} trace "
        f"{int(trace)}{' control' if control else ''}"
        f"{' fault ' + fault if fault else ''}; compile cache {cache_dir}")
    log("setup split (s): " + json.dumps(
        {k: round(v, 3) for k, v in setup_split.items()}))
    log(f"compiles in window: {in_window['compiles']} (traces "
        f"{in_window['traces']}, cache hits {in_window['cache_hits']}); "
        f"whole run: {watch.total['compiles']} compiles "
        f"{watch.total['compile_s']:.1f} s, "
        f"{watch.total['cache_hits']} persistent-cache hits")
    log("garbage collections in window (generation: count, total ms, "
        "longest ms): " + json.dumps(gcw.summary()))
    log("window: " + json.dumps({k: v for k, v in summary.items()},
                                default=str))
    if traced is not None:
        log("traced window: " + json.dumps(traced, default=str))
    log("memory stats after the window: " + json.dumps(mem))
    log(f"reference and check: {clock.phases.get('reference', 0):.1f} s")
    extra = {k: v for k, v in numbers.items() if k.startswith("_")}
    if extra:
        log("check detail: " + json.dumps(extra))
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    watch.close()
    result["checks"] = checks
    return result


def check_sample(rows, win, seed, k) -> list[int]:
    """Indices into ``rows`` to compare: a seeded sample, with the request
    that waited longest in it."""
    from chipbench import traffic
    if not rows:
        return []
    chosen = traffic.sample(seed, range(len(rows)), k)
    lat = [win["done"][r] - win["due"][r] for r, _, _ in rows]
    worst = int(np.argmax(lat))
    if worst not in chosen:
        chosen = sorted(chosen[1:] + [worst]) if len(chosen) >= k \
            else sorted(chosen + [worst])
    return chosen


def program_hlo(st: dict, kind: str) -> list[str]:
    """HLO texts of the programs the window ran: the engine's executable
    per bucket, or the train step lowered again for its state and batch
    (a persistent-cache hit)."""
    if kind == "train":
        jitted = [c.cell_contents for c in st["step"].__closure__ or ()
                  if hasattr(c.cell_contents, "lower")]
        if not jitted:
            return []
        return [jitted[0].lower(st["state"], st["batches"][0]).compile()
                .as_text()]
    eng = st["engine"]
    return [eng.aot_executable(b).as_text() for b in eng.buckets]


def main(argv=None) -> int:
    from chipbench import faults
    from chipbench.device import Clock
    clock = Clock()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="the reference at the control's precision in the "
                         "program's place")
    ap.add_argument("--fault", choices=faults.ALL, default=None,
                    help="plant a fault under the timed path "
                         "(chipbench/faults.py)")
    ap.add_argument("--keep-trace", default=None,
                    help="keep the trace's record and layout in this "
                         "directory")
    args = ap.parse_args(argv)
    from chipbench import bench
    try:
        plan = bench.resolve(args.workload)
    except (KeyError, FileNotFoundError, ValueError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print("chipbench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    result = run(plan, args.seed, args.seconds, bool(args.trace),
                 control=args.control, fault=args.fault,
                 clock=clock, keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


_paths()
import numpy as np  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
