"""Autotuner (repro.tune): cache round-trip, cold-cache fallback, candidate
space invariants, and numerical parity of tuned vs heuristic blockings."""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro import backend as be
from repro import tune
from repro.core.blocking import (VMEM_BUDGET, conv_blocking,
                                 conv_blocking_analytic, matmul_blocking,
                                 matmul_blocking_analytic)
from repro.graph.topology import RESNET50_LAYERS
from repro.kernels import ref
from repro.kernels.conv2d_direct import conv2d_direct

L4 = RESNET50_LAYERS[4]            # 56x56 c64 k64 3x3 — the sample layer


def _cache(tmp_path):
    return tune.TuneCache(str(tmp_path / "blockings.json"))


# -- cache -------------------------------------------------------------------

def test_cache_roundtrip(tmp_path):
    c = _cache(tmp_path)
    key = tune.conv_key(kind="fwd", h=14, w=14, c=256, k=256, r=3, s=3,
                        stride=1, padding=1, dtype_bytes=4, backend="xla")
    c.store(key, dict(rb_p=4, k_blk=128, c_blk=128, order="nkpc",
                      vmem_bytes=123), source="model", score_us=7.5)
    # a fresh instance over the same file must see the entry
    c2 = tune.TuneCache(c.path)
    entry = c2.lookup(key)
    assert entry is not None
    assert entry["blocking"]["rb_p"] == 4
    assert entry["source"] == "model"
    assert entry["version"] == tune.CACHE_VERSION


def test_cache_version_mismatch_discarded(tmp_path):
    c = _cache(tmp_path)
    c.store("some|key", dict(rb_p=1), source="model", score_us=1.0)
    blob = json.loads(open(c.path).read())
    blob["version"] = tune.CACHE_VERSION + 1
    open(c.path, "w").write(json.dumps(blob))
    assert tune.TuneCache(c.path).lookup("some|key") is None


def test_cache_torn_file_is_cold(tmp_path):
    path = tmp_path / "blockings.json"
    path.write_text("{not json")
    assert tune.TuneCache(str(path)).lookup("k") is None


def test_autotune_conv_persists_and_hits(tmp_path):
    c = _cache(tmp_path)
    kw = dict(h=L4["h"], w=L4["w"], c=L4["c"], k=L4["k"], r=L4["r"],
              s=L4["s"], stride=L4["stride"], padding=1, kind="fwd",
              backend="xla")
    assert tune.lookup_conv(**kw, cache=c) is None          # cold
    blk = tune.autotune_conv(**kw, cache=c)
    assert tune.lookup_conv(**kw, cache=c) == blk           # warm, same proc
    assert tune.TuneCache(c.path).lookup(                   # warm, "new proc"
        tune.conv_key(dtype_bytes=4, **kw)) is not None


def test_cached_entry_rejected_under_forced_budget(tmp_path, monkeypatch):
    """The cache key has no VMEM-budget coordinate: an entry tuned under the
    default 16 MiB must not serve a process with REPRO_VMEM_BUDGET forced
    smaller — lookup revalidates vmem_bytes and falls back to analytic."""
    c = _cache(tmp_path)
    kw = dict(h=14, w=14, c=256, k=256, r=3, s=3, stride=1, padding=1,
              kind="fwd", backend="xla")
    key = tune.conv_key(dtype_bytes=4, **kw)
    c.store(key, dict(rb_p=4, k_blk=128, c_blk=256, order="nkpc",
                      vmem_bytes=2 << 20), source="model", score_us=1.0)
    assert tune.lookup_conv(**kw, cache=c) is not None
    monkeypatch.setattr(tune, "VMEM_BUDGET", 1 << 20)
    assert tune.lookup_conv(**kw, cache=c) is None


# -- blocking integration ----------------------------------------------------

def test_cold_cache_falls_back_to_heuristic(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "cold.json"))
    kw = dict(h=28, w=28, c=128, k=128, r=3, s=3, stride=1, padding=1)
    with be.use_autotune("cache"):
        got = conv_blocking(**kw)
    assert got == conv_blocking_analytic(**kw)
    mm = matmul_blocking(256, 256, 1024)
    with be.use_autotune("cache"):
        assert matmul_blocking(256, 256, 1024) == mm


def test_autotune_off_is_seed_behavior():
    kw = dict(h=56, w=56, c=64, k=256, r=1, s=1, stride=1, padding=0)
    assert conv_blocking(**kw) == conv_blocking_analytic(**kw)
    assert (matmul_blocking(512, 512, 2048)
            == matmul_blocking_analytic(512, 512, 2048))


def test_tune_mode_used_by_conv_blocking(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "t.json"))
    kw = dict(h=14, w=14, c=256, k=256, r=3, s=3, stride=1, padding=1)
    with be.use_autotune("tune"):
        tuned = conv_blocking(**kw, backend="interpret")
        # the persisted winner must now serve "cache" mode too
    with be.use_autotune("cache"):
        assert conv_blocking(**kw, backend="interpret") == tuned


# -- candidate space ---------------------------------------------------------

def test_candidates_respect_constraints():
    cands = tune.conv_candidates(h=L4["h"], w=L4["w"], c=L4["c"], k=L4["k"],
                                 r=L4["r"], s=L4["s"], stride=L4["stride"],
                                 padding=1, kind="streams")
    assert len(cands) > 1
    assert cands[0] == conv_blocking_analytic(
        h=L4["h"], w=L4["w"], c=L4["c"], k=L4["k"], r=L4["r"], s=L4["s"],
        stride=L4["stride"], padding=1,
        whole_plane=True)       # seed first, under the streams VMEM model
    for b in cands:
        assert b.vmem_bytes <= VMEM_BUDGET
        assert L4["k"] % b.k_blk == 0
        assert L4["c"] % b.c_blk == 0
        assert b.order in tune.space.ORDERS


def test_wu_candidates_free_cblk_rbq_and_tails():
    """The tiled update pass freed the wu space: rb_p is ceil-div (dO is
    zero-padded, so non-divisors of P are legal candidates) and c_blk is a
    search coordinate, while every tile is a full row (RB_Q is no
    coordinate: the kernel streams full-width bands) — all within the VMEM
    budget under the band-based wu residency model, each priced at what
    the kernel asks for."""
    p = 14
    cands = tune.conv_candidates(h=14, w=14, c=256, k=256, r=3, s=3,
                                 stride=1, padding=1, kind="wu")
    assert any(p % b.rb_p for b in cands)               # non-divisor rb_p
    assert len({b.c_blk for b in cands}) > 1            # C_b freed
    assert all(not hasattr(b, "rb_q") for b in cands)   # full rows only
    from repro.core.blocking import conv_working_set
    for b in cands:
        assert 256 % b.c_blk == 0 and 256 % b.k_blk == 0
        ws = conv_working_set(h=14, w=14, c=256, k_blk=b.k_blk, r=3, s=3,
                              q=p, rb_p=b.rb_p, padding=1, c_blk=b.c_blk,
                              kind="wu")
        assert b.vmem_bytes == ws <= VMEM_BUDGET


def test_bwd_kind_candidates_and_key_namespace():
    """Kind "bwd" (the dual forward conv) searches the fwd space but keys a
    separate cache namespace."""
    kw = dict(h=14, w=14, c=256, k=64, r=3, s=3, stride=1, padding=2)
    cands = tune.conv_candidates(**kw, kind="bwd")
    assert cands[0] == conv_blocking_analytic(**kw)     # fwd-model seed
    assert tune.conv_key(kind="bwd", **kw, dtype_bytes=4, backend="xla") \
        != tune.conv_key(kind="fwd", **kw, dtype_bytes=4, backend="xla")


def test_cost_model_orders_by_occupancy():
    """A 1-row M-tile must never beat a full-height tile on a big layer."""
    shape = dict(h=28, w=28, c=128, k=512, r=1, s=1, stride=1, padding=0,
                 dtype_bytes=4)
    small = dataclasses.replace(conv_blocking_analytic(**shape), rb_p=1)
    tall = dataclasses.replace(small, rb_p=28)
    assert (tune.conv_cost_us(shape, tall)
            < tune.conv_cost_us(shape, small))


# -- numerical parity --------------------------------------------------------

def test_tuned_blocking_parity_resnet_layer(tmp_path, monkeypatch, rng):
    """Tuned blockings are a pure performance knob: outputs must be
    bit-identical to the heuristic blocking on a ResNet-50 layer sample."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "p.json"))
    h, c, k, r, stride, pad = 14, 64, 64, 3, 1, 1   # L13-family, thinned
    x = jnp.asarray(rng.standard_normal((1, h, h, c)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((r, r, c, k)) * 0.1, jnp.float32)

    heur = conv_blocking_analytic(h=h, w=h, c=c, k=k, r=r, s=r,
                                  stride=stride, padding=pad)
    tuned = tune.autotune_conv(h=h, w=h, c=c, k=k, r=r, s=r, stride=stride,
                               padding=pad, kind="fwd", backend="interpret")
    blockings = {(heur.rb_p, heur.k_blk): heur,
                 (tuned.rb_p, tuned.k_blk): tuned}
    # also pin one deliberately different candidate so the check bites even
    # when the tuner agrees with the heuristic
    alt = tune.conv_candidates(h=h, w=h, c=c, k=k, r=r, s=r, stride=stride,
                               padding=pad, kind="fwd")[-1]
    blockings.setdefault((alt.rb_p, alt.k_blk), alt)
    assert len(blockings) >= 2

    expect = np.asarray(ref.conv2d(x, w, stride=stride, padding=pad))
    outs = [np.asarray(conv2d_direct(x, w, stride=stride, padding=pad,
                                     rb_p=b.rb_p, k_blk=b.k_blk,
                                     interpret=True))
            for b in blockings.values()]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)           # bit-identical
    np.testing.assert_allclose(outs[0], expect, rtol=1e-4, atol=1e-4)


def test_streams_auto_consumes_tuned_blocking(tmp_path, monkeypatch, rng):
    """conv2d_streams_auto under autotune="tune" must still match the
    oracle — the tuned c_blk/order feed the dryrun schedule."""
    from repro.kernels.conv2d_streams import conv2d_streams_auto

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "s.json"))
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 16, 16)) * 0.1, jnp.float32)
    out = conv2d_streams_auto(x, w, stride=1, padding=1, autotune="tune",
                              interpret=True)
    expect = ref.conv2d(x, w, stride=1, padding=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)
    assert len(tune.TuneCache(str(tmp_path / "s.json"))) == 1
