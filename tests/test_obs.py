"""The program's own tracing (``repro.obs``): the span switch, the serving
path's phase counters, and the names the device ops of a training step
carry."""
import collections
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.conv import lane_ok
from repro.graph import GxM, resnet50
from repro.graph.serving import CnnInferenceEngine
from repro.launch.mesh import make_host_mesh
from repro.launch.serve_cnn import ImageServer
from repro.train.distributed import make_cnn_train_step_dp


@pytest.fixture(autouse=True)
def _follow_the_profiler():
    obs.enable(None)
    yield
    obs.enable(None)


def _tiny(impl=None):
    return GxM(resnet50(10, stages=(1, 1, 1, 1)), num_classes=10, impl=impl)


# -- the switch ---------------------------------------------------------

def test_span_is_one_shared_noop_when_off():
    assert not obs.tracing()            # no trace collected in this process
    a, b = obs.span("serve.step"), obs.span("engine.put", n=3, bucket=4)
    assert a is b
    with a:
        pass
    obs.enable(False)
    assert obs.span("serve.step") is a


def test_span_is_a_trace_annotation_when_on(monkeypatch):
    made = []

    class Annotation:
        def __init__(self, name, **args):
            made.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    obs.enable(True)
    assert isinstance(obs.span("serve.step"), jax.profiler.TraceAnnotation)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with obs.phase("serve.stack", batch=7):
        pass
    assert made == [("serve.stack", {"batch": 7})]


def test_spans_follow_a_trace_this_process_collects(monkeypatch):
    class State:
        profile_session = None

    state = State()
    monkeypatch.setattr(obs, "_SESSION", state)
    assert not obs.tracing()
    state.profile_session = object()            # jax.profiler.start_trace
    assert obs.tracing()
    obs.enable(False)                           # off under any trace
    assert not obs.tracing()


def test_counters_are_on_without_a_trace(monkeypatch):
    ticks = itertools.count(0, 250)
    monkeypatch.setattr(obs, "clock", lambda: next(ticks))
    before = obs.counters()
    for _ in range(3):
        with obs.phase("engine.put"):
            pass
    obs.add("serve.queue_wait", 5_000)
    got = obs.since(before, obs.counters())
    assert got["engine.put"]["count"] == 3
    assert got["engine.put"]["s"] == pytest.approx(3 * 250e-9)
    assert got["serve.queue_wait"]["count"] == 1


# -- the serving path's phases ------------------------------------------

def test_server_phases_counted_once_per_step(monkeypatch):
    """Seven requests over buckets (2, 4): two steps.  Under an injected
    clock every phase is counted once a step, the step's phases sum to at
    most the step and the engine's to at most the fetch that encloses them,
    and each request's queue wait is counted."""
    m = _tiny()
    eng = CnnInferenceEngine(m, m.init(jax.random.PRNGKey(0)),
                             image_hw=(32, 32), mesh=make_host_mesh(),
                             buckets=(2, 4), autotune="off")
    eng.warmup(autotune="off")
    ticks = itertools.count(0, 1000)
    monkeypatch.setattr(obs, "clock", lambda: next(ticks))
    server = ImageServer(eng)
    images = np.random.default_rng(0).standard_normal(
        (7, 32, 32, 3)).astype(np.float32)
    before = obs.counters()
    for img in images:
        server.submit(img)
    server.run()
    st = server.stats()
    got = obs.since(before, st["phases"])
    assert st["batches"] == 2 and len(server.results) == 7
    for name in obs.SPANS:
        assert got[name]["count"] == 2, name
    # engine.chunk encloses its chunk's engine.put and engine.run
    assert got["engine.put"]["s"] + got["engine.run"]["s"] \
        <= got["engine.chunk"]["s"]
    engine = got["engine.pad"]["s"] + got["engine.chunk"]["s"]
    assert 0 < engine <= got["serve.fetch"]["s"]
    step = sum(got[f"serve.{n}"]["s"] for n in ("take", "stack", "fetch",
                                                "post"))
    assert 0 < step <= got["serve.step"]["s"]
    assert got["serve.queue_wait"]["count"] == 7


# -- the device ops' names ---------------------------------------------

def _dp_step_lowered(g, **kw):
    mesh = make_host_mesh(data=1)
    params = jax.eval_shape(g.init, jax.random.PRNGKey(0))
    state = {"params": params, "step": jax.ShapeDtypeStruct((), jnp.int32)}
    batch = {"image": jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32),
             "label": jax.ShapeDtypeStruct((2,), jnp.int32)}
    step = make_cnn_train_step_dp(g, mesh, lr=0.01, **kw)
    jitted = [c.cell_contents for c in step.__closure__
              if hasattr(c.cell_contents, "lower")][0]
    return jitted.lower(state, batch)


def test_train_step_ops_carry_task_and_pass_names():
    """The DP step in interpret mode: every conv task's forward runs under
    its task and ``conv_fwd``; its backward-data and weight update under
    ``transpose(...)`` with ``conv_bwd_data`` and ``conv_wu``, where the
    kernels are the passes' (``pallas_call`` named by the pass)."""
    g = _tiny("interpret")
    text = _dp_step_lowered(g).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    convs = [t for t in g.etg.tasks if t.op == "conv"]
    assert len(convs) == 17

    def has(*parts, back=False):
        return any(all(p in n for p in parts) and
                   (("transpose(" in n) == back) for n in names)

    for t in convs:
        a = t.attrs
        task = f"jvp({t.name})"
        assert has(task, "/conv_fwd/"), t.name
        assert has(task, "/conv_wu/", back=True), t.name
        assert has(task, "/bn/") and has(task, "/bn/", back=True), t.name
        if lane_ok(a["c"], a["k"]):
            assert has(task, "/conv_fwd/conv_fwd/pallas_call"), t.name
            assert has(task, "/conv_bwd_data/conv_bwd_data/pallas_call",
                       back=True), t.name
            assert has(task, "/conv_wu/conv_wu/pallas_call",
                       back=True), t.name
    for scope in ("grads", "grad_allreduce", "bn_pmean", "sgd"):
        assert any(f"jit(dp_step)/{scope}/" in n for n in names), scope


def _opcodes(text):
    ops = collections.Counter()
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([\w\-]+)\(",
                     line.split(", metadata=")[0])
        if m:
            ops[m.group(1)] += 1
    return ops


def test_names_change_no_compiled_op(monkeypatch):
    """The scopes give metadata only: the compiled step has the same ops
    with every ``jax.named_scope`` a no-op."""
    named = _opcodes(_dp_step_lowered(_tiny("xla")).compile().as_text())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: __import__("contextlib").nullcontext())
    bare = _opcodes(_dp_step_lowered(_tiny("xla")).compile().as_text())
    assert sum(named.values()) > 100
    assert named == bare
