"""Continuous-batching CNN image-recognition server over the GxM executor —
the serving side of the paper's image-throughput story (DESIGN.md §8).

  PYTHONPATH=src python -m repro.launch.serve_cnn --arch resnet50 --smoke

Requests (single images) land in a queue; the scheduler drains it in
batches: each batch is padded up to the *minimal* bucket of a fixed ladder,
so every step hits one jitted, autotune-warmed, AOT-compiled executor
(``graph/serving.py``), data-parallel sharded across the local devices via
``shard_map`` over ``launch.mesh.make_host_mesh``.  Startup warmup
pre-populates the per-shape blocking cache (``repro.tune``) and compiles
every bucket, so the request path never tunes, traces, or compiles.

``--fleet N`` runs the resilient multi-replica mode instead (DESIGN.md
§15): N replicas sharing the warmed engine pair (f32 + int8 twin) behind
``serve.FleetRouter`` — deadlines, hedging, health eviction + respawn,
load shed, degrade-to-int8 — against the seeded replica-fault schedule
from ``REPRO_SERVE_CHAOS=<seed>`` / ``--fleet-chaos-seed``.

This is the CNN/image sibling of the LM decode server in
``launch/serve.py``.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import time

import jax
import numpy as np

from repro import obs
from repro.graph import GxM, inception_v3, resnet50
from repro.graph.serving import CnnInferenceEngine, pick_bucket
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


class ImageServer:
    """Continuous-batching scheduler over a ``CnnInferenceEngine``.

    ``submit`` enqueues one image and returns a request id; ``step`` serves
    one padded bucket off the queue head; ``run`` drains the queue.  Results
    map request id -> (top-1 class, top-1 logit).

    ``step`` runs in host phases (``obs.SPANS``) inside ``serve.step``:
    ``serve.take`` (queue pops), ``serve.stack``, ``serve.fetch`` (the
    engine's call, in which its ``engine.pad`` and, per chunk of the batch
    fed to the device, ``engine.chunk`` / ``engine.put`` / ``engine.run``
    nest, then the wait for the device and the copy of the logits,
    wherever a wrapper of the engine makes it) and ``serve.post``.
    Each is a span while a trace is collected and always a counter
    (``stats()["phases"]``), as is each request's ``serve.queue_wait``,
    from submit to take.  ``serve_s`` runs from take to logits.
    """

    def __init__(self, engine: CnnInferenceEngine, *, clock=None):
        self.engine = engine
        self.clock = clock if clock is not None else time.perf_counter
        self.queue: collections.deque = collections.deque()
        self.results: dict[int, tuple[int, float]] = {}
        self._next_rid = 0
        self._counters = {"batches": 0, "images": 0, "padded_lanes": 0,
                          "by_bucket": collections.Counter(), "serve_s": 0.0}
        self.latencies_s: list[float] = []

    def submit(self, image) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append((rid, image, self.clock()))
        return rid

    def step(self) -> int:
        """Serve up to one largest-bucket batch from the queue head; returns
        the number of requests served (0 when the queue is empty)."""
        if not self.queue:
            return 0
        st = self._counters
        take = min(len(self.queue), max(self.engine.buckets))
        bucket = pick_bucket(take, self.engine.buckets)
        batch = st["batches"]
        with obs.phase("serve.step", batch=batch, n=take, bucket=bucket):
            with obs.phase("serve.take", batch=batch):
                reqs = [self.queue.popleft() for _ in range(take)]
                t0 = self.clock()
                for _, _, t_enq in reqs:
                    obs.add("serve.queue_wait", int((t0 - t_enq) * 1e9))
            with obs.phase("serve.stack", batch=batch):
                images = np.stack([img for _, img, _ in reqs])
            with obs.phase("serve.fetch", batch=batch):
                logits = np.asarray(self.engine.infer(images))
            t1 = self.clock()
            with obs.phase("serve.post", batch=batch):
                st["serve_s"] += t1 - t0
                for (rid, _, t_enq), row in zip(reqs, logits):
                    top1 = int(np.argmax(row))
                    self.results[rid] = (top1, float(row[top1]))
                    self.latencies_s.append(t1 - t_enq)
                st["batches"] += 1
                st["images"] += take
                st["padded_lanes"] += bucket - take
                st["by_bucket"][bucket] += 1
        return take

    def run(self) -> dict[int, tuple[int, float]]:
        while self.queue:
            self.step()
        return dict(self.results)

    def stats(self) -> dict:
        """Counter snapshot plus the enqueue->complete latency summary
        (queue wait included — that is what a client experiences, not just
        the executor's serve time), and under ``phases`` the process's
        phase counters (``obs.counters()``: count and host seconds of each
        serving phase; take ``obs.since`` of two snapshots for a window)."""
        st = dict(self._counters)
        st["by_bucket"] = dict(st["by_bucket"])
        st["phases"] = obs.counters()
        lat = np.sort(np.asarray(self.latencies_s, dtype=np.float64))
        st["latency"] = {
            "count": int(lat.size),
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3)
            if lat.size else 0.0,
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3)
            if lat.size else 0.0,
            "max_ms": round(float(lat[-1]) * 1e3, 3) if lat.size else 0.0,
        }
        return st


def build_model(arch: str, *, smoke: bool, num_classes: int,
                image: int = 0, impl=None):
    """Topology + default image size per arch (tiny variants for --smoke)."""
    if arch == "resnet50":
        nl = resnet50(num_classes,
                      stages=(1, 1, 1, 1) if smoke else (3, 4, 6, 3))
        image = image or (32 if smoke else 224)
    elif arch == "inception":
        nl = inception_v3(num_classes)
        image = image or (48 if smoke else 224)
    else:
        raise ValueError(f"unknown arch {arch!r}")
    return GxM(nl, impl=impl, num_classes=num_classes), image


def run_fleet(args, engine, q8_engine, image: int) -> dict:
    """The resilient multi-replica mode: N replicas sharing the warmed
    engine pair behind ``serve.FleetRouter``, replaying Poisson arrivals
    against the ``REPRO_SERVE_CHAOS``-seeded fault schedule."""
    from repro.serve import (FleetRouter, Replica, ServeChaosEngine,
                             ServeChaosSchedule, poisson_arrivals)
    names = [f"r{i}" for i in range(args.fleet)]
    make_replica = lambda name: Replica(  # noqa: E731
        name, infer_fn=engine.infer,
        q8_infer_fn=q8_engine.infer if q8_engine is not None else None)
    arrivals = poisson_arrivals(0, n=args.requests, rate_per_s=1.5)
    horizon = max(t for t, _ in arrivals)
    chaos = None
    if args.fleet_chaos_seed is not None:
        schedule = ServeChaosSchedule.generate(
            args.fleet_chaos_seed, horizon_s=horizon, replicas=names)
        chaos = ServeChaosEngine(schedule)
        print(f"chaos: seed {args.fleet_chaos_seed}, "
              f"{len(schedule.events)} events over {horizon:.0f}s")
    rng = np.random.default_rng(0)
    image_fn = lambda _i: rng.standard_normal(  # noqa: E731
        (image, image, 3)).astype(np.float32)
    router = FleetRouter([make_replica(n) for n in names], chaos=chaos,
                         deadline_s=args.deadline,
                         replica_factory=make_replica,
                         burst_image_fn=image_fn)
    report = router.run([(t, image_fn(0)) for t, _ in arrivals])
    report.pop("events")
    summary = {"arch": args.arch, "fleet": args.fleet,
               "chaos_seed": args.fleet_chaos_seed, **report}
    print(json.dumps(summary))
    assert all(r.result is not None for r in router.requests.values()
               if r.status == "done")
    assert report["slo_handled_rate"] == 1.0, \
        "an admitted request busted its deadline without degrading"
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=("resnet50", "inception"),
                    default="resnet50")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny topology + image size (CI / local CPU)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--image", type=int, default=0,
                    help="input H=W (0: per-arch default)")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--classes", type=int, default=0,
                    help="classifier width (0: 10 smoke / 1000 full)")
    ap.add_argument("--autotune", choices=("off", "cache", "tune"),
                    default="tune", help="blocking-cache warmup mode")
    ap.add_argument("--fleet", type=int, default=0,
                    help="serve from N replicas behind the resilient "
                         "FleetRouter (0: single-engine batching)")
    ap.add_argument("--fleet-chaos-seed", type=int,
                    default=(int(os.environ["REPRO_SERVE_CHAOS"])
                             if os.environ.get("REPRO_SERVE_CHAOS")
                             else None),
                    help="inject a seeded replica-fault schedule "
                         "(serve/chaos.py) into --fleet mode; also "
                         "settable via REPRO_SERVE_CHAOS=<seed>")
    ap.add_argument("--deadline", type=float, default=6.0,
                    help="--fleet per-request deadline (simulated seconds)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    classes = args.classes or (10 if args.smoke else 1000)
    m, image = build_model(args.arch, smoke=args.smoke, num_classes=classes,
                           image=args.image)
    params = m.init(jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    engine = CnnInferenceEngine(m, params, image_hw=(image, image),
                                mesh=mesh, max_batch=args.max_batch)

    t0 = time.perf_counter()
    report = engine.warmup(autotune=args.autotune)
    warm_s = time.perf_counter() - t0
    print(f"warmup: {report['conv_signatures']} conv signatures "
          f"({report['pallas_path_signatures']} on the tuned kernel path), "
          f"{report['tune_entries']} blocking-cache entries, "
          f"buckets {report['buckets']} compiled in {warm_s:.1f}s")

    if args.fleet:
        mq, _ = build_model(args.arch, smoke=args.smoke,
                            num_classes=classes, image=args.image)
        # quantized=True re-marks mq's ETG: the int8 degrade twin
        q8_engine = CnnInferenceEngine(mq, params, image_hw=(image, image),
                                       mesh=mesh, max_batch=args.max_batch,
                                       quantized=True)
        q8_engine.warmup(autotune="off")
        return run_fleet(args, engine, q8_engine, image)

    # arrivals in random-size bursts so partial buckets (and therefore
    # pad-to-bucket) actually happen — the continuous-batching shape
    server = ImageServer(engine)
    rng = np.random.default_rng(0)
    remaining = args.requests
    while remaining:
        burst = int(rng.integers(1, min(remaining, args.max_batch) + 1))
        for _ in range(burst):
            server.submit(rng.standard_normal((image, image, 3),
                                              dtype=np.float32))
        remaining -= burst
        server.step()
    results = server.run()

    st = server.stats()
    ips = st["images"] / st["serve_s"] if st["serve_s"] else 0.0
    summary = {
        "arch": args.arch, "devices": len(jax.devices()),
        "data_shards": engine.num_shards, "image": image,
        "requests": len(results), "batches": st["batches"],
        "pad_fraction": round(st["padded_lanes"]
                              / max(st["images"] + st["padded_lanes"], 1), 3),
        "by_bucket": st["by_bucket"],
        "latency_p99_ms": st["latency"]["p99_ms"],
        "images_per_s": round(ips, 1),
    }
    print(json.dumps(summary))
    assert len(results) == args.requests
    return summary


if __name__ == "__main__":
    main()
