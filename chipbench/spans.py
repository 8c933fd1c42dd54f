"""Host spans of the harness, written into the profiler's trace when a run
traces (``--trace 1``) and free otherwise."""
from __future__ import annotations

import contextlib

NAMES = ("window", "submit", "step", "wait_arrival", "train_step", "block")
_on = False


def enable(on: bool) -> None:
    global _on
    _on = on


def span(name: str):
    if not _on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)
