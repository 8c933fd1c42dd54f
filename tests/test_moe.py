"""MoE dispatch invariants (hypothesis) + correctness vs a brute-force
token-loop reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, smoke_config
from repro.nn import moe


def _cfg(e=4, k=2, cf=2.0):
    base = smoke_config(get_config("phi3.5-moe-42b-a6.6b"))
    return dataclasses.replace(
        base, moe=dataclasses.replace(base.moe, n_experts=e, top_k=k,
                                      capacity_factor=cf))


def test_moe_matches_bruteforce_at_high_capacity(rng):
    """With capacity >= tokens, nothing is dropped: the grouped dispatch
    must equal the naive per-token top-k mixture."""
    cfg = _cfg(e=4, k=2, cf=8.0)
    key = jax.random.PRNGKey(0)
    p, _ = moe.init(key, cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 8, cfg.d_model)), jnp.float32)
    out, _ = moe.apply(p, cfg, x)

    xt = np.asarray(x).reshape(-1, cfg.d_model)
    logits = xt @ np.asarray(p["router"])
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    topv, topi = jax.lax.top_k(probs, 2)
    topv = np.asarray(topv / topv.sum(-1, keepdims=True))
    topi = np.asarray(topi)
    expect = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        for s in range(2):
            e = topi[t, s]
            g = np.asarray(jax.nn.silu(xt[t] @ np.asarray(p["w_gate"][e])))
            u = xt[t] @ np.asarray(p["w_up"][e])
            expect[t] += topv[t, s] * ((g * u) @ np.asarray(p["w_down"][e]))
    np.testing.assert_allclose(np.asarray(out).reshape(-1, cfg.d_model),
                               expect, rtol=2e-3, atol=2e-3)


@settings(max_examples=10, deadline=None)
@given(e=st.sampled_from([2, 4]), k=st.integers(1, 2),
       cf=st.sampled_from([0.5, 1.0, 4.0]), seed=st.integers(0, 2**31 - 1))
def test_moe_dispatch_invariants(e, k, cf, seed):
    cfg = _cfg(e=e, k=k, cf=cf)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed % 1000)
    p, _ = moe.init(key, cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 16, cfg.d_model)), jnp.float32)
    out, aux = moe.apply(p, cfg, x)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all())
    # Switch load-balancing loss E * sum_i f_i * P_i (f: top-1 routing
    # fractions, P: mean router probabilities), recomputed with the same
    # ops.  A token's top-1 probability is at least 1/E, so P_i >= f_i / E
    # and the loss is at least sum_i f_i^2 (>= 1/E); P_i <= 1 caps it at E.
    # It can fall below 1: a skewed router whose minority tokens are
    # confident.
    probs = jax.nn.softmax((x @ p["router"]).astype(jnp.float32), axis=-1)
    f = jax.nn.one_hot(jnp.argmax(probs, axis=-1), e).mean(axis=(0, 1))
    want = e * float(jnp.sum(probs.mean(axis=(0, 1)) * f))
    lb = float(aux["lb_loss"])
    np.testing.assert_allclose(lb, want, rtol=1e-5)
    assert float(jnp.sum(f * f)) * (1 - 1e-5) <= lb <= e * (1 + 1e-5)
    assert np.isfinite(float(aux["z_loss"]))


def test_moe_capacity_drops_overflow(rng):
    """With tiny capacity, output rows for dropped tokens are ~zero (they
    received no expert contribution)."""
    cfg = _cfg(e=2, k=1, cf=0.1)
    key = jax.random.PRNGKey(3)
    p, _ = moe.init(key, cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 32, cfg.d_model)), jnp.float32)
    out, _ = moe.apply(p, cfg, x)
    norms = np.linalg.norm(np.asarray(out)[0], axis=-1)
    # capacity = 0.1*32/2 -> 1 slot per expert: at most 2 non-zero rows
    assert (norms > 1e-6).sum() <= 2
