"""The chip: the gate that refuses to run without one, its published peaks,
the peak memory it reports, and a watch on what JAX compiles."""
from __future__ import annotations

import os
import sys
import time

# Published per-chip peaks, keyed by the ``device_kind`` JAX reports.
# TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM, 16 GB HBM per chip).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of a chip; a kind the table does not hold is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to chipbench/device.py "
                       f"with their source") from None


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``), so that set-up
    counts the interpreter's own start."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


def gate(chips: int, *, require_tpu: bool = True) -> dict:
    """The device record of the result line; exits with code 3 unless JAX
    runs on a TPU with at least ``chips`` devices."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu and (dev["platform"] != "tpu" or dev["count"] < chips):
        print(f"chipbench: the cell needs {chips} TPU chip(s); JAX has "
              f"{dev['count']} {dev['platform']} device(s)", file=sys.stderr)
        sys.exit(3)
    return dev


def memory_stats(devices) -> dict:
    """The runtime's memory statistics of the fullest of ``devices`` (by
    ``memory_peak_bytes``); empty where the backend keeps none, as the CPU
    does."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(stats, key=memory_peak_bytes, default={})


def memory_peak_bytes(stats: dict) -> int:
    """Peak device memory from the runtime's statistics (0 where the backend
    keeps none): the peak of the buffers in use plus the peak reserved for
    the programs' temporaries, which the TPU runtime keeps apart and leaves
    out of ``peak_bytes_in_use``.  The reservation is held while a program
    is loaded, so the two peaks overlap."""
    return int(stats.get("peak_bytes_in_use", 0)) \
        + int(stats.get("peak_bytes_reserved", 0))


class CompileWatch:
    """Counts traces and backend compiles and sums their seconds, through
    ``jax.monitoring``; ``mark()`` starts a new count (the window's)."""

    _EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
               "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax
        self.total = {"traces": 0, "compiles": 0, "compile_s": 0.0,
                      "cache_hits": 0}
        self.since = dict(self.total)
        self._on = True

        def on_duration(name, secs, **_):
            kind = self._EVENTS.get(name)
            if kind and self._on:
                self.total[kind] += 1
                if kind == "compiles":
                    self.total["compile_s"] += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits" and self._on:
                self.total["cache_hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> None:
        self.since = dict(self.total)

    def delta(self) -> dict:
        return {k: self.total[k] - self.since[k] for k in self.total}

    def close(self) -> None:
        self._on = False


class GcWatch:
    """Python's garbage collections while on: per generation the count,
    the total and the longest pause in ms (a host stall the window's
    latencies and rates would show)."""

    def __init__(self):
        import gc
        self.on = False
        self.by_gen: dict[int, list] = {}
        self._t = 0.0

        def cb(phase, info):
            if not self.on:
                return
            if phase == "start":
                self._t = time.perf_counter()
                return
            ms = (time.perf_counter() - self._t) * 1e3
            c = self.by_gen.setdefault(info["generation"], [0, 0.0, 0.0])
            c[0] += 1
            c[1] += ms
            c[2] = max(c[2], ms)

        self._cb = cb
        gc.callbacks.append(cb)

    def summary(self) -> dict:
        return {g: [c[0], round(c[1], 3), round(c[2], 3)]
                for g, c in sorted(self.by_gen.items())}

    def close(self) -> None:
        import gc
        self.on = False
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)


class Clock:
    """Named set-up phases on the host clock."""

    def __init__(self):
        self.t0 = time.perf_counter() - process_age_s()
        self.phases: dict[str, float] = {}
        self._last = self.t0

    def lap(self, name: str) -> None:
        now = time.perf_counter()
        self.phases[name] = self.phases.get(name, 0.0) + now - self._last
        self._last = now

    def since_start(self) -> float:
        return time.perf_counter() - self.t0
