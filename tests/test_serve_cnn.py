"""CNN serving path: bucket selection, pad-to-bucket bit-exactness, warmup
population of the blocking cache, and the continuous-batching scheduler."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import backend as be
from repro.graph import GxM, resnet50
from repro import obs
from repro.graph import serving
from repro.graph.serving import (CnnInferenceEngine, cnn_model_flops,
                                 conv_shapes, distinct_conv_signatures,
                                 feed_chunk, make_buckets, pick_bucket,
                                 round_buckets)
from repro.launch.mesh import make_host_mesh
from repro.launch.serve_cnn import ImageServer
from repro.tune.cache import TuneCache, conv_key


def _tiny(num_classes=10):
    nl = resnet50(num_classes=num_classes, stages=(1, 1, 1, 1))
    m = GxM(nl, num_classes=num_classes)
    params = m.init(jax.random.PRNGKey(0))
    return m, params


def _engine(m, params, **kw):
    kw.setdefault("image_hw", (32, 32))
    kw.setdefault("mesh", make_host_mesh())
    kw.setdefault("max_batch", 8)
    return CnnInferenceEngine(m, params, **kw)


# -- bucketing ---------------------------------------------------------------

def test_make_buckets_ladder_and_shard_multiples():
    assert make_buckets(16) == (1, 2, 4, 8, 16)
    assert make_buckets(12) == (1, 2, 4, 8, 16)       # next power of two
    assert make_buckets(16, num_shards=2) == (2, 4, 8, 16)
    assert all(b % 4 == 0 for b in make_buckets(32, num_shards=4))


def test_round_buckets_rounds_up_to_shard_multiples():
    # a caller ladder that doesn't divide num_shards rounds UP (never
    # truncates capacity) and dedups collisions
    assert round_buckets((2, 6), 4) == (4, 8)
    assert round_buckets((1, 2, 3, 4), 2) == (2, 4)
    assert round_buckets((3, 5, 8), 1) == (3, 5, 8)    # no-op on 1 shard


def test_engine_rounds_explicit_buckets_up(monkeypatch):
    m, params = _tiny()
    eng = _engine(m, params, buckets=(3, 6))
    # the host mesh's shard count varies by CI job (fake-device flags)
    assert eng.buckets == round_buckets((3, 6), eng.num_shards)
    # a 4-shard mesh must round the explicit ladder up, not assert
    import repro.launch.mesh as mesh_mod
    monkeypatch.setattr(mesh_mod, "data_axis_size", lambda mesh: 4)
    eng2 = _engine(m, params, buckets=(3, 6))
    assert eng2.buckets == (4, 8)


def test_pick_bucket_is_minimal():
    buckets = (2, 4, 8, 16)
    assert pick_bucket(1, buckets) == 2
    assert pick_bucket(2, buckets) == 2
    assert pick_bucket(3, buckets) == 4
    assert pick_bucket(5, buckets) == 8
    assert pick_bucket(16, buckets) == 16


def test_pick_bucket_rejects_oversized_batch():
    # silently serving at max(buckets) would truncate lanes — must raise
    with pytest.raises(ValueError, match="exceeds largest bucket"):
        pick_bucket(99, (2, 4, 8, 16))


# -- shape inference ---------------------------------------------------------

def test_conv_shapes_cover_every_conv_task():
    m, _ = _tiny()
    shapes = conv_shapes(m.etg, (32, 32))
    convs = [t for t in m.etg.tasks if t.op == "conv"]
    assert len(shapes) == len(convs)
    by_name = {s["name"]: s for s in shapes}
    # the stem conv sees the raw image plane
    assert by_name["conv1"]["h"] == 32 and by_name["conv1"]["c"] == 3
    # every spatial extent must be positive and strides propagate
    assert all(s["h"] > 0 and s["w"] > 0 for s in shapes)
    assert cnn_model_flops(m.etg, (32, 32), 4) == \
        2 * cnn_model_flops(m.etg, (32, 32), 2)


# -- padded lanes are invisible ----------------------------------------------

def test_padded_batch_bit_exact_vs_unbatched_forward(rng):
    m, params = _tiny()
    eng = _engine(m, params)
    eng.warmup(autotune="off")
    x = rng.standard_normal((3, 32, 32, 3)).astype(np.float32)
    got = np.asarray(eng.infer(x))                    # pads 3 -> bucket 4
    ref = np.asarray(m.forward(params, jnp.asarray(x), train=False))
    np.testing.assert_array_equal(got, ref)
    # lane independence: what fills the padded lane cannot leak into real
    # lanes (inference has no cross-batch ops — BN is folded)
    fn = eng.aot_executable(4)
    junk = 100 * rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
    with_zeros = fn(params, jnp.asarray(np.concatenate([x, 0 * junk])))
    with_junk = fn(params, jnp.asarray(np.concatenate([x, junk])))
    np.testing.assert_array_equal(np.asarray(with_zeros)[:3],
                                  np.asarray(with_junk)[:3])


def test_infer_rejects_oversized_batch(rng):
    m, params = _tiny()
    eng = _engine(m, params, buckets=(2, 4))
    x = rng.standard_normal((5, 32, 32, 3)).astype(np.float32)
    with pytest.raises(ValueError):
        eng.infer(x)


# -- warmup ------------------------------------------------------------------

def test_warmup_populates_tune_cache_for_every_signature(tmp_path):
    m, params = _tiny()
    eng = _engine(m, params, buckets=(2, 4))
    cache = TuneCache(str(tmp_path / "cache.json"))
    report = eng.warmup(autotune="tune", cache=cache, compile_buckets=False)
    sigs = distinct_conv_signatures(eng.conv_shapes())
    assert report["conv_signatures"] == len(sigs)
    backend = be.resolve(m.impl)
    for sh in sigs:
        for bucket in eng.buckets:
            key = conv_key(kind="fwd", dtype_bytes=4, backend=backend,
                           minibatch=eng.local_batch(bucket), **sh)
            assert cache.lookup(key) is not None, key
    # one entry per signature × per-device bucket batch, all reported
    assert report["tune_entries"] == len(sigs) * len(eng.buckets)
    assert report["kernel_cache_entries"] == len(m.etg.kernel_cache)


def test_compiled_buckets_consult_tuner_cache(monkeypatch):
    """The request-path executables must be traced under the engine's
    autotune scope, so the blockings warmup persisted are actually used
    (not the analytic heuristic)."""
    import repro.tune as tune
    looked_up = []
    real = tune.lookup_conv

    def spy(**kw):
        looked_up.append(kw["minibatch"])
        return real(**kw)

    monkeypatch.setattr(tune, "lookup_conv", spy)
    m, params = _tiny()
    m.impl = "interpret"        # xla path never consults conv_blocking
    eng = _engine(m, params, buckets=(2,))
    eng.warmup(autotune="off")  # compile-only; engine scope is "cache"
    # lookups happen at the per-shard batch (bucket / data shards)
    assert looked_up and set(looked_up) == {eng.local_batch(2)}, looked_up


def test_warmup_compiles_every_bucket(rng):
    m, params = _tiny()
    eng = _engine(m, params, buckets=(2, 4))
    report = eng.warmup(autotune="off")
    assert set(report["compile_s"]) == {2, 4}
    for b in (2, 4):
        assert eng.aot_executable(b) is eng._compiled[b]


# -- pipelined host-to-device feed -------------------------------------------

F32_224 = 224 * 224 * 3 * 4           # bytes of one 224x224x3 f32 image


@pytest.mark.parametrize("bucket,image_bytes,shards,want", [
    (128, F32_224, 1, 16),            # the offline bucket: 8 chunks of 16
    (128, F32_224, 4, 16),
    (64, F32_224, 1, 16),             # the server ladder's largest rung
    (16, F32_224, 1, 16),             # fits already: one chunk
    (16, F32_224, 4, 16),
    (128, F32_224 // 2, 1, 32),       # bf16 images
    (64, 448 * 448 * 3 * 4, 1, 4),    # 448x448 f32
    (120, F32_224, 1, 15),            # largest divisor under the bytes
    (120, F32_224, 4, 12),            # ... that is a multiple of the shards
    (8, 3 << 20, 8, 8),               # no divisor is a shard multiple
    (4, 25 << 20, 1, 4),              # one image is over the bytes
])
def test_feed_chunk_plan(bucket, image_bytes, shards, want):
    c = feed_chunk(bucket, image_bytes, shards)
    assert c == want
    assert bucket % c == 0 and c % shards == 0
    assert c == bucket or c * image_bytes <= serving.FEED_CHUNK_BYTES


def _chunked_engine(monkeypatch, m, params):
    """Bucket 8 of 32x32 f32 images fed as 4 chunks of 2; bucket 2 whole."""
    monkeypatch.setattr(serving, "FEED_CHUNK_BYTES", 2 * 32 * 32 * 3 * 4)
    eng = _engine(m, params, mesh=make_host_mesh(data=1), buckets=(2, 8))
    assert eng.chunks == {2: 2, 8: 2}
    eng.warmup(autotune="off")
    return eng


def test_chunked_feed_matches_each_chunks_forward(monkeypatch, rng):
    """n=5 in bucket 8: chunks [0:2], [2:4] and [4:5] with the only padded
    lane; a chunk of zeros past the last image is not sent.  Each chunk's
    logits are the model's forward on that chunk's images, and the result
    matches the unchunked engine up to XLA-CPU's batch-size-dependent
    sums."""
    m, params = _tiny()
    eng = _chunked_engine(monkeypatch, m, params)
    assert eng.aot_executable(8) is eng._compiled[2]
    assert eng.aot_executable(8) is eng.aot_executable(2)
    assert 8 not in eng._compiled
    assert set(eng._joins) == {(2, 2), (2, 3), (2, 4)}   # warmed joins
    x = rng.standard_normal((5, 32, 32, 3)).astype(np.float32)
    sent = []
    real_put = jnp.asarray
    monkeypatch.setattr(serving.jnp, "asarray",
                        lambda a: (sent.append(np.array(a)), real_put(a))[1])
    got = np.asarray(eng.infer(x))
    monkeypatch.setattr(serving.jnp, "asarray", real_put)
    assert got.shape == (5, 10)
    assert [s.shape[0] for s in sent] == [2, 2, 2]
    for s in sent[:-1]:
        assert np.all(np.any(s != 0, axis=(1, 2, 3)))
    assert np.all(sent[-1][1] == 0) and np.any(sent[-1][0] != 0)
    fwd = m.make_infer(mesh=None, donate_input=False)
    want = np.concatenate([np.asarray(fwd(params, jnp.asarray(s)))
                           for s in sent])[:5]
    np.testing.assert_array_equal(got, want)
    monkeypatch.setattr(serving, "FEED_CHUNK_BYTES", 1 << 30)
    one = _engine(m, params, mesh=make_host_mesh(data=1), buckets=(8,))
    assert one.chunks == {8: 8}
    np.testing.assert_allclose(got, np.asarray(one.infer(x)), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("n,chunks", [(8, 4), (7, 4), (5, 3), (2, 1), (1, 1)])
def test_chunk_counter_per_call(monkeypatch, rng, n, chunks):
    """``engine.chunk`` is entered once per chunk sent: 4 for a full bucket
    8, fewer for a partial one, 1 on the unchunked bucket 2; put and run
    once per chunk, pad once per call."""
    m, params = _tiny()
    eng = _chunked_engine(monkeypatch, m, params)
    x = rng.standard_normal((n, 32, 32, 3)).astype(np.float32)
    before = obs.counters()
    jax.block_until_ready(eng.infer(x))
    got = obs.since(before, obs.counters())
    for name in ("engine.chunk", "engine.put", "engine.run"):
        assert got[name]["count"] == chunks, name
    assert got["engine.pad"]["count"] == 1


# -- quantized serving (§II-K end to end) ------------------------------------

def _tiny_q8(impl="interpret"):
    nl = resnet50(num_classes=10, stages=(1, 1, 1, 1))
    m = GxM(nl, num_classes=10, impl=impl, quantized=True)
    return m, m.init(jax.random.PRNGKey(0))


def test_quantized_engine_top1_stable_vs_f32(rng):
    """A quantized=True engine on the interpret backend (the real int8
    Pallas kernels) must keep the fp32 top-1 on a fixed batch, and stay
    within the calibration error band on the logits."""
    m32, params = _tiny()
    m32.impl = "interpret"
    x = rng.standard_normal((4, 32, 32, 3)).astype(np.float32)
    ref_logits = np.asarray(m32.forward(params, jnp.asarray(x), train=False))

    mq, _ = _tiny_q8()          # same init seed -> identical f32 weights
    eng = _engine(mq, params, buckets=(4,))
    assert eng.quantized
    report = eng.warmup(autotune="off")
    assert report["quantized"] and eng.qparams is not None
    got = np.asarray(eng.infer(x))
    np.testing.assert_array_equal(np.argmax(got, axis=-1),
                                  np.argmax(ref_logits, axis=-1))
    rel = np.max(np.abs(got - ref_logits)) / (np.max(np.abs(ref_logits))
                                              + 1e-9)
    assert rel < 0.1, rel


def test_quantized_padded_lanes_invisible(rng):
    """Pad-to-bucket on the q8 path: junk in the padded lane must not
    perturb a single bit of the real lanes (per-tensor activation scales
    are calibration constants, not batch statistics)."""
    mq, params = _tiny_q8()
    eng = _engine(mq, params, buckets=(4,))
    eng.warmup(autotune="off")
    x = rng.standard_normal((3, 32, 32, 3)).astype(np.float32)
    got = np.asarray(eng.infer(x))                   # pads 3 -> bucket 4
    fn = eng.aot_executable(4)
    junk = 100 * rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
    with_zeros = fn(eng._run_params, jnp.asarray(np.concatenate([x, 0 * junk])))
    with_junk = fn(eng._run_params, jnp.asarray(np.concatenate([x, junk])))
    np.testing.assert_array_equal(np.asarray(with_zeros)[:3],
                                  np.asarray(with_junk)[:3])
    np.testing.assert_array_equal(got, np.asarray(with_zeros)[:3])


def test_calibration_deterministic_for_fixed_seed():
    """Same params + same synthetic calibration seed -> bit-equal scales
    (pure max-reduction over rng-seeded batches); a different seed must
    actually change the data the scales see."""
    mq, params = _tiny_q8(impl=None)   # calibration runs the f32 xla path
    a = _engine(mq, params, buckets=(2,)).calibrate(batches=2, batch=2,
                                                    seed=0)
    b = _engine(mq, params, buckets=(2,)).calibrate(batches=2, batch=2,
                                                    seed=0)
    assert set(a) == set(b) and len(a) > 0
    for name in a:
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
    c = _engine(mq, params, buckets=(2,)).calibrate(batches=2, batch=2,
                                                    seed=1)
    assert any(float(a[n]) != float(c[n]) for n in a)


def test_quantized_engine_train_guard():
    """The quantized params tree is inference-only: the executor must
    refuse to run a training forward over w_q leaves."""
    mq, params = _tiny_q8(impl=None)
    eng = _engine(mq, params, buckets=(2,))
    eng.calibrate(batches=1, batch=2, seed=0)
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    with pytest.raises(ValueError, match="inference-only"):
        mq.forward(eng.qparams, x, train=True)


# -- continuous-batching scheduler -------------------------------------------

def test_server_serves_all_requests_and_counts_padding(rng):
    m, params = _tiny()
    eng = _engine(m, params, buckets=(2, 4))
    eng.warmup(autotune="off")
    server = ImageServer(eng)
    images = rng.standard_normal((7, 32, 32, 3)).astype(np.float32)
    rids = [server.submit(img) for img in images]
    results = server.run()
    assert set(results) == set(rids)
    # 7 requests -> one bucket-4 batch (4 reqs) + bucket-4 batch (3 reqs,
    # 1 padded lane)
    st = server.stats()
    assert st["images"] == 7
    assert st["padded_lanes"] == 1
    # every request's enqueue->complete latency is recorded
    assert st["latency"]["count"] == 7
    assert st["latency"]["p99_ms"] >= st["latency"]["p50_ms"] >= 0.0
    # scheduler results match the direct forward
    logits = np.asarray(m.forward(params, jnp.asarray(images), train=False))
    for rid, img_logits in zip(rids, logits):
        top1, val = results[rid]
        assert top1 == int(np.argmax(img_logits))
        assert val == float(img_logits[top1])


def test_server_latency_includes_queue_wait(rng):
    """Latency is enqueue->complete under an injectable clock: a request
    stuck behind a full bucket waits one extra step, and stats() reports
    exactly that."""
    from repro.core.simtime import SimClock
    m, params = _tiny()
    eng = _engine(m, params, buckets=(2,))
    eng.warmup(autotune="off")
    clk = SimClock()
    server = ImageServer(eng, clock=lambda: (clk.sleep(1.0), clk.time())[1])
    for img in rng.standard_normal((3, 32, 32, 3)).astype(np.float32):
        server.submit(img)                 # enqueued at t=1, 2, 3
    server.run()
    st = server.stats()["latency"]
    assert st["count"] == 3
    # step 1 serves reqs 0,1 (clock reads at t=4 and t=5); step 2 serves
    # req 2 (reads at t=6 and t=7) -> latencies 4, 3, 4 seconds
    assert sorted(server.latencies_s) == [3.0, 4.0, 4.0]
