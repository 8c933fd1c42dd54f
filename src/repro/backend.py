"""Kernel implementation dispatch.

"pallas"    — real Mosaic lowering (TPU targets; what the dry-run *describes*)
"interpret" — Pallas interpret mode (CPU correctness validation; tests)
"xla"       — pure-jnp/lax reference path (CPU dry-run lowering at 512 devices
              and the numerics oracle)

With ``REPRO_BACKEND`` unset the default is resolved on first use, never at
import: "pallas" where ``jax.default_backend() == "tpu"``, "xla" elsewhere.
Asking for "pallas" on a host without a TPU raises instead of running the
kernels somewhere they were not built for.

The per-shape JIT specialization story of the paper (§II-D) is carried by
jax.jit itself: every (layer shape × blocking) pair traces and compiles its
own specialized kernel, on demand, cached — libxsmm's runtime code
generation, one level up.

The *blocking* each specialization uses is governed by the autotune knob
(``REPRO_AUTOTUNE`` / ``set_autotune`` / ``use_autotune``):

  "off"    analytic heuristic only (seed behavior; default)
  "cache"  consult the persistent per-shape tuner cache, analytic on miss
  "tune"   on a miss, search the blocking space, persist the winner

See ``repro.tune`` and DESIGN.md §6.

The conv *input strategy* has its own knob (``REPRO_CONV_TILING``
/ ``set_conv_tiling``): "tiled" (default) streams row bands with a VMEM
working set independent of the image size, "whole" is the legacy
whole-plane kernel kept for A/B comparison.  It governs both the forward
kernel (DESIGN.md §9) and the weight-update kernel (DESIGN.md §10).

The strided backward-data plan (``REPRO_BWD_DUALITY`` / ``set_bwd_duality``)
selects how the generic §II-I duality case runs: "phase" (default)
decomposes into stride² forward sub-convs over the *undilated* dO — no
intermediate tensor, no multiply-by-zero work; "dilate" is the legacy
materialize-the-dilated-dO plan kept for A/B.  See DESIGN.md §10.

The data-parallel gradient reduction (``REPRO_GRAD_COMPRESS``
/ ``set_grad_compress``) selects the wire format of the cross-shard psum in
the DP CNN train step: "off" (default) reduces f32 gradients exactly;
"int8" routes every leaf through ``optim.compress.compressed_psum`` —
error-feedback int8 quantization, 1/4 the all-reduce bytes, residual
carried in the train state.  See DESIGN.md §11.

Depth-first chain fusion (``REPRO_CHAIN_FUSION`` / ``set_chain_fusion``)
gates the cross-layer band-fusion path (DESIGN.md §16): "off" (default)
runs every conv task layer-by-layer; "on" lets the GxM inference executor
run detected single-consumer conv->conv chains band-by-band through
``kernels.conv2d_chain`` — the intermediate activation never materializes
in HBM — falling back per-chain to unfused whenever the combined band
working set exceeds ``REPRO_VMEM_BUDGET`` (or fusion is unprofitable).

Quantized inference (``REPRO_QUANTIZE`` / ``set_quantize``) is the per-model
opt-in for the §II-K int8 serving path: "off" (default) runs f32 convs;
"int8" makes ``GxM``/``CnnInferenceEngine`` built without an explicit
``quantized=`` flag mark every conv task "q8" — int8 weights + per-tensor
calibrated activation scales through ``kernels.conv2d_q8``, int32
accumulation, f32 dequant epilogue.  See DESIGN.md §13.
"""
from __future__ import annotations

import os
from contextlib import contextmanager

_VALID = ("pallas", "interpret", "xla")
_VALID_AUTOTUNE = ("off", "cache", "tune")
_VALID_CONV_TILING = ("tiled", "whole")
_VALID_BWD_DUALITY = ("phase", "dilate")
_VALID_GRAD_COMPRESS = ("off", "int8")
_VALID_QUANTIZE = ("off", "int8")
_VALID_CHAIN_FUSION = ("off", "on")
_backend = os.environ.get("REPRO_BACKEND")     # None: resolve on first use
_autotune = os.environ.get("REPRO_AUTOTUNE", "off")
_conv_tiling = os.environ.get("REPRO_CONV_TILING", "tiled")
_bwd_duality = os.environ.get("REPRO_BWD_DUALITY", "phase")
_grad_compress = os.environ.get("REPRO_GRAD_COMPRESS", "off")
_quantize = os.environ.get("REPRO_QUANTIZE", "off")
_chain_fusion = os.environ.get("REPRO_CHAIN_FUSION", "off")
if _chain_fusion not in _VALID_CHAIN_FUSION:
    import sys
    print(f"repro.backend: ignoring invalid REPRO_CHAIN_FUSION="
          f"{_chain_fusion!r} (valid: {', '.join(_VALID_CHAIN_FUSION)}); "
          f"using off", file=sys.stderr)
    _chain_fusion = "off"
if _quantize not in _VALID_QUANTIZE:
    import sys
    print(f"repro.backend: ignoring invalid REPRO_QUANTIZE="
          f"{_quantize!r} (valid: {', '.join(_VALID_QUANTIZE)}); "
          f"using off", file=sys.stderr)
    _quantize = "off"
if _grad_compress not in _VALID_GRAD_COMPRESS:
    import sys
    print(f"repro.backend: ignoring invalid REPRO_GRAD_COMPRESS="
          f"{_grad_compress!r} (valid: {', '.join(_VALID_GRAD_COMPRESS)}); "
          f"using off", file=sys.stderr)
    _grad_compress = "off"
if _bwd_duality not in _VALID_BWD_DUALITY:
    import sys
    print(f"repro.backend: ignoring invalid REPRO_BWD_DUALITY="
          f"{_bwd_duality!r} (valid: {', '.join(_VALID_BWD_DUALITY)}); "
          f"using phase", file=sys.stderr)
    _bwd_duality = "phase"
if _autotune not in _VALID_AUTOTUNE:
    import sys
    print(f"repro.backend: ignoring invalid REPRO_AUTOTUNE={_autotune!r} "
          f"(valid: {', '.join(_VALID_AUTOTUNE)}); autotuning is off",
          file=sys.stderr)
    _autotune = "off"
if _conv_tiling not in _VALID_CONV_TILING:
    import sys
    print(f"repro.backend: ignoring invalid REPRO_CONV_TILING="
          f"{_conv_tiling!r} (valid: {', '.join(_VALID_CONV_TILING)}); "
          f"using tiled", file=sys.stderr)
    _conv_tiling = "tiled"


def _check_backend(name: str) -> str:
    import jax
    if name not in _VALID:
        raise ValueError(f"unknown kernel backend {name!r} (valid: "
                         f"{', '.join(_VALID)})")
    if name == "pallas" and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"kernel backend 'pallas' needs a TPU, but JAX runs on "
            f"{jax.default_backend()!r}; use 'interpret' or 'xla' there")
    return name


def get_backend() -> str:
    """The process-wide kernel backend, resolving the default on first use:
    "pallas" on a TPU, "xla" elsewhere (``REPRO_BACKEND`` overrides)."""
    global _backend
    if _backend is None:
        import jax
        _backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    return _check_backend(_backend)


def set_backend(name: str) -> None:
    global _backend
    _backend = _check_backend(name)


@contextmanager
def use_backend(name: str):
    global _backend
    prev = _backend
    set_backend(name)
    try:
        yield
    finally:
        _backend = prev


def resolve(impl: str | None) -> str:
    return _check_backend(impl) if impl else get_backend()


def get_autotune() -> str:
    return _autotune


def set_autotune(mode: str) -> None:
    global _autotune
    assert mode in _VALID_AUTOTUNE, mode
    _autotune = mode


@contextmanager
def use_autotune(mode: str):
    global _autotune
    prev = _autotune
    set_autotune(mode)
    try:
        yield
    finally:
        _autotune = prev


def resolve_autotune(mode: str | None) -> str:
    mode = mode or _autotune
    assert mode in _VALID_AUTOTUNE, mode
    return mode


def get_conv_tiling() -> str:
    """Forward direct-conv input strategy: "tiled" streams only the row band
    each grid step needs (VMEM working set independent of H*W — the default);
    "whole" is the legacy whole-plane kernel, kept for A/B benchmarking."""
    return _conv_tiling


def set_conv_tiling(mode: str) -> None:
    global _conv_tiling
    assert mode in _VALID_CONV_TILING, mode
    _conv_tiling = mode


@contextmanager
def use_conv_tiling(mode: str):
    global _conv_tiling
    prev = _conv_tiling
    set_conv_tiling(mode)
    try:
        yield
    finally:
        _conv_tiling = prev


def get_bwd_duality() -> str:
    """Generic strided backward-data plan: "phase" runs stride² forward
    sub-convs over the undilated dO (zero-free — the default); "dilate" is
    the legacy materialized-dilation plan, kept for A/B benchmarking."""
    return _bwd_duality


def set_bwd_duality(mode: str) -> None:
    global _bwd_duality
    assert mode in _VALID_BWD_DUALITY, mode
    _bwd_duality = mode


@contextmanager
def use_bwd_duality(mode: str):
    global _bwd_duality
    prev = _bwd_duality
    set_bwd_duality(mode)
    try:
        yield
    finally:
        _bwd_duality = prev


def get_grad_compress() -> str:
    """Data-parallel gradient-reduction wire format: "off" = exact f32 psum;
    "int8" = error-feedback compressed psum (1/4 the bytes, residual carried
    in the train state).  See ``train/distributed.py`` / DESIGN.md §11."""
    return _grad_compress


def set_grad_compress(mode: str) -> None:
    global _grad_compress
    assert mode in _VALID_GRAD_COMPRESS, mode
    _grad_compress = mode


@contextmanager
def use_grad_compress(mode: str):
    global _grad_compress
    prev = _grad_compress
    set_grad_compress(mode)
    try:
        yield
    finally:
        _grad_compress = prev


def resolve_grad_compress(mode: str | None) -> str:
    mode = mode or _grad_compress
    assert mode in _VALID_GRAD_COMPRESS, mode
    return mode


def get_quantize() -> str:
    """Quantized-inference opt-in: "off" = f32 convs (default); "int8" =
    the §II-K serving path — conv tasks marked "q8", int8 weights and
    calibrated activations through ``kernels.conv2d_q8``.  DESIGN.md §13."""
    return _quantize


def set_quantize(mode: str) -> None:
    global _quantize
    assert mode in _VALID_QUANTIZE, mode
    _quantize = mode


@contextmanager
def use_quantize(mode: str):
    global _quantize
    prev = _quantize
    set_quantize(mode)
    try:
        yield
    finally:
        _quantize = prev


def resolve_quantize(mode: str | None) -> str:
    mode = mode or _quantize
    assert mode in _VALID_QUANTIZE, mode
    return mode


def get_chain_fusion() -> str:
    """Depth-first chain-fusion opt-in: "off" = layer-by-layer conv tasks
    (default); "on" = run single-consumer conv->conv chains band-by-band
    (``kernels.conv2d_chain``), intermediates never touching HBM, with a
    per-chain VMEM/profitability fallback.  DESIGN.md §16."""
    return _chain_fusion


def set_chain_fusion(mode: str) -> None:
    global _chain_fusion
    assert mode in _VALID_CHAIN_FUSION, mode
    _chain_fusion = mode


@contextmanager
def use_chain_fusion(mode: str):
    global _chain_fusion
    prev = _chain_fusion
    set_chain_fusion(mode)
    try:
        yield
    finally:
        _chain_fusion = prev


def resolve_chain_fusion(mode: str | None) -> str:
    mode = mode or _chain_fusion
    assert mode in _VALID_CHAIN_FUSION, mode
    return mode
