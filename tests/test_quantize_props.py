"""Property tests for ``core.quantize`` — the §II-K numerics contract.

Runs under real ``hypothesis`` when installed, else the deterministic
fixed-draw shim (``tests/_hypothesis_compat.py``).  The properties:

  * round-trip: |x - q*scale| <= scale/2 per element for every in-range
    value (round-to-nearest against the calibrated scale);
  * symmetric clipping: |q| <= 127 always, out-of-range values saturate,
    and quantization is an odd function (q(-x) == -q(x));
  * small tensors pass through ``quantize_int8`` untouched;
  * scales are strictly positive — the ``+ 1e-12`` guard is pinned
    explicitly, so an all-zero tensor quantizes to zeros instead of
    dividing by zero.
"""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.quantize import dequantize, quantize_act, quantize_int8

SCALE_GUARD = 1e-12      # the shared guard every scale in core.quantize adds
# No per-example deadline: each new shape compiles the quantizer, so an
# example's time is JAX's compile time, not the property's.


def _vals(seed: int, n: int, scale_pow: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(n) * 10.0 ** scale_pow).astype(np.float32)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1), n=st.integers(1, 64),
       scale_pow=st.integers(-3, 3))
def test_act_roundtrip_error_at_most_half_scale(seed, n, scale_pow):
    x = _vals(seed, n, scale_pow)
    scale = float(np.abs(x).max()) / 127.0 + SCALE_GUARD
    q = np.asarray(quantize_act(jnp.asarray(x), jnp.float32(scale)))
    deq = q.astype(np.float32) * np.float32(scale)
    # round-to-nearest: half a quantization step, plus f32 division slop
    assert np.all(np.abs(x - deq) <= scale * 0.5001), \
        float(np.max(np.abs(x - deq)) / scale)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1), n=st.integers(1, 64),
       blowup=st.floats(1.0, 100.0))
def test_act_clips_symmetrically_at_127(seed, n, blowup):
    x = _vals(seed, n, 0)
    # deliberately under-calibrated scale: values beyond ±127*scale saturate
    scale = jnp.float32(float(np.abs(x).max()) / (127.0 * blowup)
                        + SCALE_GUARD)
    q = np.asarray(quantize_act(jnp.asarray(x), scale), np.int32)
    assert np.all(np.abs(q) <= 127)
    over = np.abs(x) > 127.5 * float(scale)
    assert np.all(np.abs(q[over]) == 127)
    # odd function: jnp.round (half-to-even) is symmetric under negation
    q_neg = np.asarray(quantize_act(jnp.asarray(-x), scale), np.int32)
    np.testing.assert_array_equal(q_neg, -q)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1), rows=st.integers(1, 7),
       cols=st.integers(1, 8))
def test_small_tensors_pass_through_unquantized(seed, rows, cols):
    rng = np.random.default_rng(seed)
    small = jnp.asarray(rng.standard_normal((rows, cols)), jnp.float32)
    vec = jnp.asarray(rng.standard_normal(1024), jnp.float32)  # 1-D: never
    out = quantize_int8({"w": small, "b": vec}, min_size=64)
    assert not isinstance(out["b"], dict)            # ndim < 2 passthrough
    if small.size < 64:
        assert not isinstance(out["w"], dict)        # size < min_size
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(small))
    else:
        assert set(out["w"]) == {"q", "s"}           # big enough: quantized


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1), rows=st.integers(8, 32),
       cols=st.integers(8, 32), scale_pow=st.integers(-6, 3))
def test_weight_scales_strictly_positive(seed, rows, cols, scale_pow):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((rows, cols)) * 10.0 ** scale_pow,
                    jnp.float32)
    out = quantize_int8({"w": w}, min_size=1)
    s = np.asarray(out["w"]["s"], np.float64)
    assert np.all(s > 0)
    assert np.all(s >= SCALE_GUARD)


def test_zero_tensor_quantizes_to_zeros_via_guard():
    """The + 1e-12 guard, pinned: an all-zero matrix must produce exactly
    the guard as its scale (no division by zero) and reconstruct to exact
    zeros."""
    z = jnp.zeros((16, 16), jnp.float32)
    out = quantize_int8({"w": z}, min_size=1)
    np.testing.assert_array_equal(np.asarray(out["w"]["s"]),
                                  np.full(16, SCALE_GUARD, np.float32))
    np.testing.assert_array_equal(np.asarray(out["w"]["q"]),
                                  np.zeros((16, 16), np.int8))
    deq = dequantize(out, jnp.float32)
    np.testing.assert_array_equal(np.asarray(deq["w"]), np.asarray(z))
    # the activation side shares the same guard
    q = quantize_act(z, jnp.float32(0.0 / 127.0 + SCALE_GUARD))
    np.testing.assert_array_equal(np.asarray(q), np.zeros((16, 16), np.int8))
