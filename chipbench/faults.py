"""Faults planted under the timed path, to show that ``correct`` catches
each fault a cell can have.  Used by the benchmark's tests and by
``run.py --fault`` on the chip; never by a measured run."""
from __future__ import annotations

import contextlib

import numpy as np


def stale_state(step):
    """A step that returns its state unchanged."""
    def f(state, batch):
        return state, step(state, batch)[1]
    return f


def half_batch(step):
    """Half of the batch left out; the mean taken over the rest."""
    def f(state, batch):
        half = batch["label"].shape[0] // 2
        return step(state, {k: v[:half] for k, v in batch.items()})
    return f


@contextlib.contextmanager
def no_exchange():
    """The exchange between chips left out: while the step is traced, the
    mean over the data axis returns each chip's own value."""
    import jax
    real = jax.lax.pmean
    jax.lax.pmean = lambda x, axis_name, **_: x
    try:
        yield
    finally:
        jax.lax.pmean = real


class AlteredAnswer:
    """An answer altered where it is produced: in each batch the first
    request's top logit and its lowest change places."""

    def __init__(self, engine):
        self.engine = engine
        self.buckets = engine.buckets

    def infer(self, images):
        y = np.array(self.engine.infer(images))
        hi, lo = int(np.argmax(y[0])), int(np.argmin(y[0]))
        y[0, hi], y[0, lo] = y[0, lo], y[0, hi]
        return y


STEP_FAULTS = {"stale_state": stale_state, "half_batch": half_batch}
ENGINE_FAULTS = {"altered_answer": AlteredAnswer}
TRACE_FAULTS = {"no_exchange": no_exchange}
ALL = (*STEP_FAULTS, *ENGINE_FAULTS, *TRACE_FAULTS)
