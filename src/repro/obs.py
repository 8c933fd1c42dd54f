"""The program's own tracing: host spans, phase counters and the names the
device ops carry.

Host spans (``span``, ``phase``) are ``jax.profiler.TraceAnnotation``s:
their events land in the profiler's host plane, on the clock the device
ops are aligned to, and are written out with the trace.  They are
recorded while a profiler trace is being collected in this process
(``jax.profiler.start_trace`` / ``jax.profiler.trace``); ``enable`` turns
them on for a trace collected from outside (``jax.profiler.start_server``)
or off under any trace.  Off, ``span`` returns one shared no-op context.

Counters are always on: ``phase`` adds its host time (``clock``, in ns) to
a process-wide counter of its name, and ``add`` counts any other duration.
``counters()`` is a snapshot, ``since(before)`` the difference of two.

Device ops are named at trace time only, by ``jax.named_scope`` and the
``name=`` of each ``pallas_call``: the conv passes below, each GxM task,
``bn`` (train-mode statistics) and the data-parallel step's ``grads``,
``grad_allreduce``, ``bn_pmean`` and ``sgd``.  The names land in the HLO
``op_name`` metadata and in the kernels' instruction names, which the
profiler's device events carry; they cost nothing at run time.
"""
from __future__ import annotations

import time

try:                                    # the profiler's session, if any
    from jax._src import profiler as _jprof
    _SESSION = getattr(_jprof, "_profile_state", None)
except ImportError:                     # pragma: no cover
    _SESSION = None

# host spans of the serving path: ``serve.step`` encloses the others,
# ``serve.fetch`` the ``engine.*`` ones, and ``engine.chunk`` (once per
# chunk of the batch fed to the device) that chunk's ``engine.put`` and
# ``engine.run``; each is also a counter, as is ``serve.queue_wait`` (a
# request's submit to take)
SPANS = ("serve.step", "serve.take", "serve.stack", "engine.pad",
         "engine.chunk", "engine.put", "engine.run", "serve.fetch",
         "serve.post")

# the conv passes: each names its kernels and the scope of its XLA glue
CONV_FWD, CONV_BWD_DATA, CONV_WU = "conv_fwd", "conv_bwd_data", "conv_wu"
CONV_Q8, CONV_CHAIN = "conv_q8", "conv_chain"
# the pass a forward-kernel launch serves, by its blocking kind
PASS_OF_KIND = {"fwd": CONV_FWD, "bwd": CONV_BWD_DATA}

clock = time.perf_counter_ns
_forced: bool | None = None
_counts: dict[str, list[int]] = {}      # name -> [count, total ns, max ns]


class _Null:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


def enable(on: bool | None) -> None:
    """Spans always (True), never (False), or while this process collects
    a profiler trace (None, the default)."""
    global _forced
    _forced = on


def tracing() -> bool:
    if _forced is not None:
        return _forced
    return _SESSION is not None and _SESSION.profile_session is not None


def span(name: str, **args):
    """A host span named ``name`` with ``args`` as its event's stats, or the
    shared no-op context while nothing is traced."""
    if not tracing():
        return _NULL
    import jax
    return jax.profiler.TraceAnnotation(name, **args)


def add(name: str, ns: int) -> None:
    c = _counts.get(name)
    if c is None:
        c = _counts[name] = [0, 0, 0]
    c[0] += 1
    c[1] += ns
    if ns > c[2]:
        c[2] = ns


class phase:
    """``with phase(name, **args):`` — a span, and a count of the host time
    the block took, always."""
    __slots__ = ("name", "args", "t0", "sp")

    def __init__(self, name: str, **args):
        self.name, self.args = name, args

    def __enter__(self):
        self.sp = span(self.name, **self.args)
        self.sp.__enter__()
        self.t0 = clock()
        return self

    def __exit__(self, *exc):
        add(self.name, clock() - self.t0)
        return self.sp.__exit__(*exc)


def reset() -> None:
    """Counters start again from zero (a diagnosis's window)."""
    _counts.clear()


def counters() -> dict[str, dict]:
    """Snapshot: {name: {"count", "s", "max_s"}} over the process."""
    return {k: {"count": c[0], "s": c[1] * 1e-9, "max_s": c[2] * 1e-9}
            for k, c in _counts.items()}


def since(before: dict, now: dict) -> dict[str, dict]:
    """Counts and seconds between two snapshots (``max_s`` is the
    process's, not the interval's)."""
    out = {}
    for k, c in now.items():
        b = before.get(k, {"count": 0, "s": 0.0})
        if c["count"] - b["count"]:
            out[k] = {"count": c["count"] - b["count"], "s": c["s"] - b["s"],
                      "max_s": c["max_s"]}
    return out
