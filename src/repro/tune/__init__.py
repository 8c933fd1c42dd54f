"""Shape-specialized blocking autotuner (paper §II-D, made empirical).

The seed port hardcoded one analytic heuristic in ``core.blocking``.  This
package searches the real parameter space of the Pallas kernels — RB_P,
K_blk, C_blk, loop order — per (shape, dtype, stride/padding, backend,
device) and remembers winners in a persistent versioned cache, so every later
process gets the tuned blocking for free: libxsmm's dispatch cache, one level
up.

Layering (no cycles): ``core.blocking`` lazily calls ``lookup_conv`` /
``autotune_conv`` here; this package statically imports the *analytic*
helpers from ``core.blocking`` as the search seed.

  mode "off"    analytic heuristic only (default; seed behavior)
  mode "cache"  consult the cache, fall back to the heuristic on a miss
  mode "tune"   on a miss, search + persist the winner, then use it

Select with ``REPRO_AUTOTUNE``, ``repro.backend.set_autotune()``, or the
``autotune=`` kwarg threaded through ``core.conv`` / ``kernels.ops``.
See DESIGN.md §6 for the cache key format and the re-tune workflow.
"""
from __future__ import annotations

import dataclasses

from repro.core.blocking import VMEM_BUDGET, ConvBlocking, MatmulBlocking
from repro.tune.cache import (CACHE_VERSION, TuneCache,  # noqa: F401
                              conv_key, default_cache, device_kind,
                              matmul_key)
from repro.tune.measure import (can_measure, conv_cost_us,  # noqa: F401
                                matmul_cost_us, rank_conv)
from repro.tune.space import (conv_candidates,  # noqa: F401
                              matmul_candidates, out_dim)

_CONV_FIELDS = ("rb_p", "k_blk", "c_blk", "order", "vmem_bytes")


def _to_conv(entry: dict, *, c: int, k: int) -> ConvBlocking | None:
    blk = entry.get("blocking", {})
    if not all(f in blk for f in _CONV_FIELDS):
        return None
    if k % blk["k_blk"] or c % blk["c_blk"]:    # key drift safety net
        return None
    if blk["vmem_bytes"] > VMEM_BUDGET:
        # the cache key has no budget coordinate: an entry tuned under the
        # default 16 MiB must not serve a REPRO_VMEM_BUDGET-forced process
        return None
    return ConvBlocking(**{f: blk[f] for f in _CONV_FIELDS})


def lookup_conv(*, h, w, c, k, r, s, stride, padding, dtype_bytes=4,
                kind="fwd", backend="xla", minibatch=1,
                cache: TuneCache | None = None) -> ConvBlocking | None:
    """Cache-only consult; None on a miss (caller falls back to analytic)."""
    cache = default_cache() if cache is None else cache
    key = conv_key(kind=kind, h=h, w=w, c=c, k=k, r=r, s=s, stride=stride,
                   padding=padding, dtype_bytes=dtype_bytes, backend=backend,
                   minibatch=minibatch)
    entry = cache.lookup(key)
    return _to_conv(entry, c=c, k=k) if entry else None


def autotune_conv(*, h, w, c, k, r, s, stride, padding, dtype_bytes=4,
                  kind="fwd", backend="xla", minibatch=1,
                  cache: TuneCache | None = None,
                  persist: bool = True) -> ConvBlocking:
    """Cache hit, else search the space, persist the winner, return it."""
    cache = default_cache() if cache is None else cache
    hit = lookup_conv(h=h, w=w, c=c, k=k, r=r, s=s, stride=stride,
                      padding=padding, dtype_bytes=dtype_bytes, kind=kind,
                      backend=backend, minibatch=minibatch, cache=cache)
    if hit is not None:
        return hit
    shape = dict(h=h, w=w, c=c, k=k, r=r, s=s, stride=stride,
                 padding=padding, dtype_bytes=dtype_bytes)
    cands = conv_candidates(h=h, w=w, c=c, k=k, r=r, s=s, stride=stride,
                            padding=padding, dtype_bytes=dtype_bytes,
                            kind=kind)
    ranked = rank_conv(shape, cands, kind=kind, backend=backend,
                       minibatch=minibatch)
    score, best = ranked[0]
    if k % best.k_blk == 0 and c % best.c_blk == 0:
        # only persist entries the lookup validator will accept — a
        # non-dividing winner (possible for lane-unalignable dims that the
        # kernels reject anyway) would otherwise miss forever
        key = conv_key(kind=kind, h=h, w=w, c=c, k=k, r=r, s=s,
                       stride=stride, padding=padding,
                       dtype_bytes=dtype_bytes, backend=backend,
                       minibatch=minibatch)
        cache.store(key, dataclasses.asdict(best),
                    source="measured" if can_measure(backend) else "model",
                    score_us=score, persist=persist)
    return best


def warmup_convs(shapes, *, minibatches=(1,), kinds=("fwd",), mode="tune",
                 backend=None, cache: TuneCache | None = None,
                 dtype_bytes=4, bwd_mode=None) -> list[dict]:
    """Pre-populate the blocking cache for conv ``shapes`` — the serving /
    training warmup entry (DESIGN.md §8, §10).

    ``shapes``: dicts with h/w/c/k/r/s/stride/padding (e.g. from
    ``graph.serving.conv_shapes``).  One entry is tuned per shape × ``kinds``
    × ``minibatches`` — minibatch is part of the cache key, so serving warms
    exactly the per-device batch of every bucket it will run.  Kinds beyond
    "fwd" cover the training pass: "wu" keys the update-pass blocking on the
    layer shape itself; "bwd" expands each layer into the *dual* forward-conv
    signature(s) its backward-data plan launches
    (``duality.dual_conv_signatures`` — stride² sub-convs under the default
    phase plan, selected by ``bwd_mode`` / the ``REPRO_BWD_DUALITY`` knob) so
    the first training step never tunes inline; "q8" keys the int8 serving
    path (pass ``dtype_bytes=1``).  ``mode`` follows the knob
    semantics: "tune" searches+persists on a miss, "cache" only reports what
    is already there.  All new entries are persisted in one atomic write at
    the end.  Returns one report dict per key:
    ``{"key", "kind", "cached", "source"}``.
    """
    from repro import backend as be
    from repro.core import duality
    backend = be.resolve(backend)
    cache = default_cache() if cache is None else cache
    report = []
    for sh in shapes:
        base = {f: sh[f] for f in ("h", "w", "c", "k", "r", "s",
                                   "stride", "padding")}
        db = sh.get("dtype_bytes", dtype_bytes)
        for kind in kinds:
            if kind == "bwd":
                targets = duality.dual_conv_signatures(
                    r=base["r"], s=base["s"], c=base["c"], k=base["k"],
                    stride=base["stride"], padding=base["padding"],
                    input_hw=(base["h"], base["w"]), mode=bwd_mode)
            else:
                targets = [base]
            for tgt in targets:
                for mb in minibatches:
                    if mode == "tune":
                        autotune_conv(**tgt, dtype_bytes=db, kind=kind,
                                      backend=backend, minibatch=mb,
                                      cache=cache, persist=False)
                    key = conv_key(kind=kind, **tgt, dtype_bytes=db,
                                   backend=backend, minibatch=mb)
                    entry = cache.lookup(key)
                    report.append({"key": key, "kind": kind,
                                   "cached": entry is not None,
                                   "source": entry["source"] if entry
                                   else None})
    if mode == "tune" and any(e["cached"] for e in report):
        try:
            cache.save()
        except OSError as e:        # unwritable path: warm in-memory only
            import sys
            print(f"repro.tune: warmup cache not persisted "
                  f"({cache.path}: {e})", file=sys.stderr)
    return report


def lookup_matmul(m, n, k, *, dtype_bytes=2, backend="xla",
                  cache: TuneCache | None = None) -> MatmulBlocking | None:
    cache = default_cache() if cache is None else cache
    entry = cache.lookup(matmul_key(m=m, n=n, k=k, dtype_bytes=dtype_bytes,
                                    backend=backend))
    if not entry:
        return None
    blk = entry.get("blocking", {})
    if not all(f in blk for f in ("bm", "bn", "bk", "vmem_bytes")):
        return None
    if m % blk["bm"] or n % blk["bn"] or k % blk["bk"]:
        return None
    return MatmulBlocking(bm=blk["bm"], bn=blk["bn"], bk=blk["bk"],
                          vmem_bytes=blk["vmem_bytes"])


def autotune_matmul(m, n, k, *, dtype_bytes=2, backend="xla",
                    cache: TuneCache | None = None,
                    persist: bool = True) -> MatmulBlocking:
    cache = default_cache() if cache is None else cache
    hit = lookup_matmul(m, n, k, dtype_bytes=dtype_bytes, backend=backend,
                        cache=cache)
    if hit is not None:
        return hit
    cands = matmul_candidates(m, n, k, dtype_bytes=dtype_bytes)
    scored = sorted(((matmul_cost_us(m, n, k, b, dtype_bytes=dtype_bytes), b)
                     for b in cands), key=lambda t: t[0])
    score, best = scored[0]
    if m % best.bm == 0 and n % best.bn == 0 and k % best.bk == 0:
        cache.store(matmul_key(m=m, n=n, k=k, dtype_bytes=dtype_bytes,
                               backend=backend),
                    dataclasses.asdict(best), source="model", score_us=score,
                    persist=persist)
    return best
