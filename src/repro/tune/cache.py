"""Persistent shape-specialized blocking cache — the libxsmm dispatch cache
one level up (paper §II-D: "JIT the right microkernel for the layer at hand",
here: *remember* the right blocking for the layer at hand).

Entries are keyed by everything that changes the winner:

  kind | shape params | dtype bytes | stride/padding | backend | device_kind

and stored in a single versioned JSON file (default
``~/.cache/repro_tune/blockings-v<CACHE_VERSION>.json``, currently
``blockings-v5.json``; override with ``REPRO_TUNE_CACHE``).
Writes are atomic (tempfile + ``os.replace``) so concurrent benchmark runs
never observe a torn file.  A version mismatch on load discards the file —
bump ``CACHE_VERSION`` whenever the candidate space, the cost model, or the
entry format changes incompatibly (see DESIGN.md §6).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

CACHE_VERSION = 5     # v5: full-row tiles (no rb_q field), feature blocks
                      #     128 or the whole dim, vmem_bytes = the kernel's
                      #     double-buffered, tile-padded phase-plane band
                      # v4: the "q8" int8-forward kind + its 1-byte-input
                      #     working-set model (grow-to-budget rb_p)
                      # v3: tiled-wu space (c_blk/rb_q free, ceil-div rb_p)
                      #     + the "bwd" dual-conv kind
                      # v2: ConvBlocking grew rb_q (RB_Q column blocking)
_ENV_VAR = "REPRO_TUNE_CACHE"


def default_cache_path() -> str:
    env = os.environ.get(_ENV_VAR)
    if env:
        return env
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "repro_tune", f"blockings-v{CACHE_VERSION}.json")


def device_kind() -> str:
    """Cache-key component: the accelerator the blocking was tuned for."""
    try:
        import jax
        return jax.devices()[0].device_kind.replace("|", "_")
    except Exception:  # noqa: BLE001 — no backend at all
        return "unknown"


def conv_key(*, kind: str, h: int, w: int, c: int, k: int, r: int, s: int,
             stride: int, padding: int, dtype_bytes: int, backend: str,
             minibatch: int = 1, device: str | None = None) -> str:
    device = device or device_kind()
    # minibatch is part of the key: the memory/refetch terms of the cost
    # model (and real wall clock) scale with N, so winners differ by batch
    return (f"conv|{kind}|n{minibatch}h{h}w{w}c{c}k{k}r{r}s{s}"
            f"|st{stride}pd{padding}|b{dtype_bytes}|{backend}|{device}")


def matmul_key(*, m: int, n: int, k: int, dtype_bytes: int, backend: str,
               device: str | None = None) -> str:
    device = device or device_kind()
    return f"matmul|m{m}n{n}k{k}|b{dtype_bytes}|{backend}|{device}"


class TuneCache:
    """In-memory dict over a versioned JSON file.  Thread-safe; lazily loaded."""

    def __init__(self, path: str | None = None):
        self.path = path or default_cache_path()
        self._entries: dict[str, dict] | None = None
        self._lock = threading.Lock()
        self._warned_readonly = False

    # -- persistence ---------------------------------------------------------
    def _load_locked(self) -> dict[str, dict]:
        if self._entries is not None:
            return self._entries
        self._entries = {}
        try:
            with open(self.path, encoding="utf-8") as f:
                blob = json.load(f)
            if blob.get("version") == CACHE_VERSION:
                self._entries = dict(blob.get("entries", {}))
        except (OSError, ValueError):
            pass                      # cold cache / stale version / torn file
        return self._entries

    def save(self) -> None:
        with self._lock:
            entries = self._load_locked()
            # merge what other processes persisted since our lazy load —
            # our own entries win on key conflict, nobody's work is dropped
            try:
                with open(self.path, encoding="utf-8") as f:
                    blob = json.load(f)
                if blob.get("version") == CACHE_VERSION:
                    merged = dict(blob.get("entries", {}))
                    merged.update(entries)
                    self._entries = entries = merged
            except (OSError, ValueError):
                pass
            blob = {"version": CACHE_VERSION, "entries": entries}
            d = os.path.dirname(self.path) or "."
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            try:
                with os.fdopen(fd, "w", encoding="utf-8") as f:
                    json.dump(blob, f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise

    # -- access --------------------------------------------------------------
    def lookup(self, key: str) -> dict | None:
        with self._lock:
            e = self._load_locked().get(key)
        return dict(e) if e is not None else None

    def store(self, key: str, blocking: dict, *, source: str,
              score_us: float, persist: bool = True) -> None:
        entry = {"blocking": dict(blocking), "source": source,
                 "score_us": float(score_us), "version": CACHE_VERSION,
                 "tuned_at": time.time()}
        with self._lock:
            self._load_locked()[key] = entry
        if persist:
            try:
                self.save()
            except OSError as e:     # unwritable path: keep tuning in-memory
                if not self._warned_readonly:
                    self._warned_readonly = True
                    print(f"repro.tune: cache not persisted "
                          f"({self.path}: {e}); continuing in-memory",
                          file=sys.stderr)

    def export_entries(self, keys=None) -> dict[str, dict]:
        """Snapshot entries (all, or just ``keys``) as a JSON-serializable
        payload — the "broadcast" half of tune-once-per-host warmup: host 0
        tunes, exports, and every other host ``merge_entries`` the payload
        instead of re-searching the same space (DESIGN.md §11)."""
        with self._lock:
            entries = self._load_locked()
            if keys is None:
                return {k: dict(v) for k, v in entries.items()}
            return {k: dict(entries[k]) for k in keys if k in entries}

    def merge_entries(self, payload: dict[str, dict], *,
                      persist: bool = True) -> int:
        """Install a broadcast payload verbatim (tuned_at/score preserved).
        Returns the number of entries installed."""
        with self._lock:
            self._load_locked().update(
                {k: dict(v) for k, v in payload.items()})
        if persist:
            try:
                self.save()
            except OSError as e:
                if not self._warned_readonly:
                    self._warned_readonly = True
                    print(f"repro.tune: cache not persisted "
                          f"({self.path}: {e}); continuing in-memory",
                          file=sys.stderr)
        return len(payload)

    def clear(self) -> None:
        with self._lock:
            self._entries = {}
        try:
            os.unlink(self.path)
        except OSError:
            pass

    def __len__(self) -> int:
        with self._lock:
            return len(self._load_locked())


_default: TuneCache | None = None
_default_lock = threading.Lock()


def default_cache() -> TuneCache:
    """Process-wide cache singleton (re-created if REPRO_TUNE_CACHE moved)."""
    global _default
    with _default_lock:
        if _default is None or _default.path != default_cache_path():
            _default = TuneCache()
        return _default
