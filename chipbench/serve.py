"""Serving cells: the program's ``ImageServer`` over a warmed
``CnnInferenceEngine``, driven from one thread.

``server``: open-loop arrivals of single images at the mix's fixed rate;
every request that is due is enqueued, the server steps, and the loop
sleeps only when the queue is empty.  Latency runs from the due time to
the logits on the host.  ``offline``: a backlog keeps the queue at one
bucket or more through the window, so every step is a full bucket.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import traffic
from chipbench.spans import span


class Recorder:
    """Stands between ``ImageServer`` and the engine and keeps the logits
    each step produced, as the host received them."""

    def __init__(self, engine):
        self.engine = engine
        self.buckets = engine.buckets
        self.out: list[np.ndarray] = []

    def infer(self, images):
        y = np.asarray(self.engine.infer(images))
        self.out.append(y)
        return y


def buckets(mix: dict) -> tuple[int, ...]:
    if mix["kind"] == "offline":
        return (mix["bucket"],)
    from repro.graph.serving import make_buckets
    return make_buckets(mix["max_bucket"])


def setup(plan: dict, params, seed: int, clock, *, engine_factory=None):
    """Engine warmed for the mix's buckets and every batch size the window
    can send, the server over it, and the image pool."""
    import jax
    from repro.graph.serving import CnnInferenceEngine
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve_cnn import ImageServer

    cfg, mix = plan["config"], plan["mix"]
    hw = cfg["image"]
    pool = traffic.image_pool(seed, mix["pool_images"], hw)
    clock.lap("pool")
    if engine_factory is None:
        engine = CnnInferenceEngine(plan["gxm"], params, image_hw=(hw, hw),
                                    mesh=make_host_mesh(data=1),
                                    buckets=buckets(mix), autotune="off")
        engine.warmup(autotune="off")
    else:
        engine = engine_factory(buckets(mix))
    clock.lap("compile")
    sizes = range(1, max(engine.buckets) + 1) if mix["kind"] == "server" \
        else engine.buckets
    for n in sizes:                 # pad and slice programs of each size
        jax.block_until_ready(engine.infer(pool[:n]))
    clock.lap("warm")
    rec = Recorder(engine)
    return {"engine": engine, "recorder": rec,
            "server": ImageServer(rec), "pool": pool}


def window(state: dict, plan: dict, seed: int, seconds: float,
           drain_s: float = 60.0) -> dict:
    """Run the measured window; returns per-request records and counters."""
    mix = plan["mix"]
    server, rec, pool = state["server"], state["recorder"], state["pool"]
    clock = time.perf_counter
    if mix["kind"] == "server":
        due = traffic.arrival_times(seed, mix["rate_per_s"], seconds)
    else:
        due = None
    n_req = len(due) if due is not None else 1 << 20
    pick = traffic.picks(seed, min(n_req, 1 << 16), len(pool))
    req = {"due": [], "submit": [], "pool": []}
    steps = []                       # (first rid, n, t_start, t_done)
    first_out = len(rec.out)
    i = 0
    rids: list[int] = []

    def submit(t_due):
        nonlocal i
        with span("submit"):
            rids.append(server.submit(pool[pick[i % len(pick)]]))
        req["due"].append(t_due)
        req["submit"].append(clock())
        req["pool"].append(int(pick[i % len(pick)]))
        i += 1

    t0 = clock()
    t_end = t0 + seconds
    while True:
        now = clock()
        if due is not None:
            while i < len(due) and t0 + due[i] <= now:
                submit(t0 + due[i])
            if not server.queue:
                if i >= len(due):
                    break
                with span("wait_arrival"):
                    time.sleep(max(t0 + due[i] - clock(), 0.0))
                continue
            if now > t_end + drain_s:
                break
        else:
            if now >= t_end:
                break
            while len(server.queue) < mix["bucket"]:
                submit(now)
        rid0 = server.queue[0][0]
        ts = clock()
        with span("step"):
            n = server.step()
        te = clock()
        steps.append((rid0, n, ts, te))
    n_sub = len(req["due"])
    base = rids[0] if rids else 0          # ids run on, one per submit
    start = np.full(n_sub, np.nan)
    done = np.full(n_sub, np.nan)
    for rid0, n, ts, te in steps:
        start[rid0 - base:rid0 - base + n] = ts
        done[rid0 - base:rid0 - base + n] = te
    logits = np.concatenate(rec.out[first_out:]) if rec.out[first_out:] \
        else np.zeros((0, 0), np.float32)
    return {"t0": t0, "t_end": steps[-1][3] if steps else t0,
            "seconds": seconds, "kind": mix["kind"],
            "due": np.asarray(req["due"]), "submit": np.asarray(req["submit"]),
            "start": start, "done": done, "pool_idx": np.asarray(req["pool"]),
            "logits": logits, "steps": steps, "buckets": rec.buckets,
            "n_due_in_window": len(due) if due is not None else None}


def counters_since(before: dict | None, now: dict) -> dict:
    """``ImageServer`` counters of what ran after ``before`` was taken."""
    if before is None:
        return now
    by = {b: n - before["by_bucket"].get(b, 0)
          for b, n in now["by_bucket"].items()}
    return {"images": now["images"] - before["images"],
            "padded_lanes": now["padded_lanes"] - before["padded_lanes"],
            "by_bucket": {b: n for b, n in by.items() if n}}


def summarize(win: dict, counters: dict) -> dict:
    """End-to-end numbers and the per-layer counters of one window."""
    out = {}
    lat = win["done"] - win["due"]
    served = np.isfinite(lat)
    if win["kind"] == "server":
        n = win["n_due_in_window"]
        # a request never answered counts as over every limit
        lat_all = np.where(served, lat, np.inf)[:n]
        out["serve_p99_ms"] = float(np.quantile(lat_all, 0.99,
                                                method="higher")) * 1e3
        out["attempted"] = int(n)
        out["failed"] = int(np.sum(~served[:n]))
        wait = win["start"][:n] - win["due"][:n]
        out["queue_wait_p99_ms"] = float(np.quantile(
            np.where(np.isfinite(wait), wait, np.inf), 0.99,
            method="higher")) * 1e3
        late = win["submit"][:n] - win["due"][:n]
        out["lateness_p99_ms"] = float(np.quantile(late, 0.99)) * 1e3
        out["lateness_max_ms"] = float(np.max(late)) * 1e3
        out["answered_in_window"] = int(np.sum(
            win["done"][:n] <= win["t0"] + win["seconds"]))
        out["backlog_at_close"] = int(np.sum(
            (win["due"] <= win["t0"] + win["seconds"])
            & ~(win["done"] <= win["t0"] + win["seconds"])))
    else:
        images = int(np.sum(served))
        span_s = win["t_end"] - win["t0"]
        out["serve_images_per_s"] = images / span_s
        out["attempted"] = images
        out["failed"] = 0
        out["window_s"] = span_s
    hist, took = {}, {}
    long = max(win["steps"], key=lambda st: st[3] - st[2], default=None)
    if long is not None:        # the longest step: ms, images, s into window
        out["longest_step"] = [round((long[3] - long[2]) * 1e3, 3), long[1],
                               round(long[2] - win["t0"], 3)]
    for _, n, ts, te in win["steps"]:
        hist[n] = hist.get(n, 0) + 1
        b = min((b for b in win["buckets"] if b >= n), default=n)
        took.setdefault(b, []).append((te - ts) * 1e3)
    out["batch_sizes"] = dict(sorted(hist.items()))
    # host-clock time of one step, by the bucket it ran in: median and max
    out["step_ms_by_bucket"] = {b: [round(float(np.median(v)), 3),
                                    round(float(np.max(v)), 3)]
                                for b, v in sorted(took.items())}
    c = counters
    lanes = c["images"] + c["padded_lanes"]
    out["pad_share"] = 100.0 * c["padded_lanes"] / lanes if lanes else None
    out["by_bucket"] = dict(sorted(c["by_bucket"].items()))
    return out


def served_rows(win: dict):
    """(request index, pool index, logits) of every answered request."""
    ok = np.flatnonzero(np.isfinite(win["done"]))
    return [(int(r), int(win["pool_idx"][r]), win["logits"][k])
            for k, r in enumerate(ok)]
