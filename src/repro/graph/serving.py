"""CNN inference serving machinery over the GxM executor (DESIGN.md §8).

The paper's second half integrates the JIT'd conv kernels into the GxM
graph flow and reports *image throughput*; this module is the deployment
side of that story:

* **Bucketed batching** — requests are padded to a small fixed set of
  batch-size buckets so every bucket hits exactly one jitted, autotune-
  warmed executor.  The bucket set is finite, so the set of (shape ×
  blocking) specializations — and of autotuner cache keys — is finite too.
* **Data-parallel sharding** — each bucket's batch is split across the
  local devices of a ``launch.mesh.make_host_mesh`` mesh via ``shard_map``;
  inference has no cross-batch collectives, so scaling is embarrassing.
* **Pipelined feed** — a bucket whose input is larger than
  ``FEED_CHUNK_BYTES`` goes to the device in chunks (``feed_chunk``): each
  chunk is handed to the device and its executable dispatched in turn, so
  chunk i computes while chunk i+1 is still in transit and only the first
  chunk's copy is exposed.  Inference has no cross-image ops, so a chunk
  does the same work per image as the whole bucket would.
* **Warmup** — ``CnnInferenceEngine.warmup`` walks every conv signature of
  the network (shape-inferred from the ETG) and pre-populates both the
  per-shape blocking cache (``repro.tune``) and the jit executable cache
  (AOT lower+compile of the batch each bucket runs, and of the join of a
  chunked bucket's logits), so the request path never tunes, traces, or
  compiles.

``launch/serve_cnn.py`` builds the request queue / scheduler on top.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import backend as be
from repro import obs
from repro import tune
from repro.core.blocking import VMEM_BUDGET, conv_blocking
from repro.core.conv import lane_ok


def _out(dim: int, f: int, stride: int, padding: int) -> int:
    return (dim + 2 * padding - f) // stride + 1


def conv_shapes(etg, image_hw) -> list[dict]:
    """Per-conv-task full tuning shapes, inferred by walking the ETG.

    ``etg.kernel_cache`` dedups convs by (c,k,r,s,stride,padding,fused) but
    carries no spatial extent; the tuner key needs (h, w) too, so we run the
    ETG symbolically from the network input size.  Returns one dict per conv
    task (h/w are the conv's *input* plane) with its dedup ``kernel_id``.
    """
    h0, w0 = image_hw
    hw: dict[str, tuple | None] = {"input": (h0, w0)}
    shapes = []
    for t in etg.tasks:
        a = t.attrs
        if t.op == "input":
            hw[t.name] = (h0, w0)
            continue
        src = hw.get(t.inputs[0]) if t.inputs else None
        if t.op == "conv":
            h, w = src
            shapes.append(dict(name=t.name, h=h, w=w, c=a["c"], k=a["k"],
                               r=a["r"], s=a["s"], stride=a["stride"],
                               padding=a["padding"],
                               kernel_id=a.get("kernel_id")))
            res = (_out(h, a["r"], a["stride"], a["padding"]),
                   _out(w, a["s"], a["stride"], a["padding"]))
        elif t.op == "maxpool":
            h, w = src
            res = (_out(h, a["window"], a["stride"], a["padding"]),
                   _out(w, a["window"], a["stride"], a["padding"]))
        elif t.op in ("avgpool", "fc"):
            res = None                      # rank-2 from here on
        else:                               # bn / relu / add / split / concat
            res = src
        hw[t.name] = res
        if "output_name" in a:
            hw[a["output_name"]] = res
    return shapes


def distinct_conv_signatures(shapes: list[dict]) -> list[dict]:
    """Dedup conv shapes down to the tuner key coordinates."""
    seen, out = set(), []
    for sh in shapes:
        sig = (sh["h"], sh["w"], sh["c"], sh["k"], sh["r"], sh["s"],
               sh["stride"], sh["padding"])
        if sig in seen:
            continue
        seen.add(sig)
        out.append({f: sh[f] for f in ("h", "w", "c", "k", "r", "s",
                                       "stride", "padding")})
    return out


def cnn_model_flops(etg, image_hw, batch: int) -> float:
    """Useful model FLOPs of one inference forward: 2·P·Q·K·C·R·S per conv
    plus 2·C·K for the classifier — the numerator of roofline efficiency."""
    total = 0.0
    for sh in conv_shapes(etg, image_hw):
        p = _out(sh["h"], sh["r"], sh["stride"], sh["padding"])
        q = _out(sh["w"], sh["s"], sh["stride"], sh["padding"])
        total += 2.0 * p * q * sh["k"] * sh["c"] * sh["r"] * sh["s"]
    for t in etg.tasks:
        if t.op == "fc":
            total += 2.0 * t.attrs["c"] * t.attrs["k"]
    return total * batch


# -- bucketing ---------------------------------------------------------------

# A bucket whose input is larger than this many bytes is sent to the device
# in chunks; 10 MiB is 16 images of 224x224x3 f32 (a bucket of 128 in 8).
# Chunks of 16 beat 32, 64 and 8 in ResNet-101 offline serving on a v5e.
FEED_CHUNK_BYTES = 10 << 20


def feed_chunk(bucket: int, image_bytes: int, num_shards: int = 1) -> int:
    """Images per host-to-device chunk of a bucket: the largest divisor of
    ``bucket`` that is a multiple of ``num_shards`` and whose input,
    ``c * image_bytes``, fits in ``FEED_CHUNK_BYTES``.  The whole bucket
    when its input fits already, or when no divisor does."""
    if bucket * image_bytes <= FEED_CHUNK_BYTES:
        return bucket
    for c in range(bucket // 2, 0, -1):
        if (bucket % c == 0 and c % num_shards == 0
                and c * image_bytes <= FEED_CHUNK_BYTES):
            return c
    return bucket


def _join(*logits):
    return jnp.concatenate(logits)


def round_buckets(buckets, num_shards: int) -> tuple[int, ...]:
    """Round every rung up to the next multiple of ``num_shards`` (dedup'd,
    sorted) so a padded batch always splits evenly across the data-parallel
    mesh — a caller-supplied ladder like (2, 6) on 4 shards becomes (4, 8)
    instead of tripping a shard-split assert deep in shard_map."""
    assert num_shards >= 1
    rounded = {-(-int(b) // num_shards) * num_shards for b in buckets}
    assert all(b >= 1 for b in rounded), buckets
    return tuple(sorted(rounded))


def make_buckets(max_batch: int, *, num_shards: int = 1) -> tuple[int, ...]:
    """Geometric bucket ladder; every bucket is a multiple of ``num_shards``
    so a padded batch always splits evenly across the data-parallel mesh."""
    assert max_batch >= 1 and num_shards >= 1
    b, out = num_shards, []
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(b)
    return round_buckets(out, num_shards)


def pick_bucket(n: int, buckets) -> int:
    """Smallest bucket that fits ``n`` requests (minimal padding).  A batch
    beyond the largest bucket has no executor to run on — silently serving
    it at ``max(buckets)`` would truncate lanes, so it raises; callers
    chunk first (``ImageServer.step`` takes at most ``max(buckets)``)."""
    for b in sorted(buckets):
        if b >= n:
            return b
    raise ValueError(f"batch {n} exceeds largest bucket {max(buckets)}; "
                     f"chunk it first")


class CnnInferenceEngine:
    """Bucketed, sharded, warmup-able inference front-end for one GxM model.

    ``infer(images)`` pads the batch to the minimal bucket, runs the
    AOT-compiled executor for that bucket (data-parallel over ``mesh``'s
    "data" axis when given), and returns only the real lanes' logits —
    padded lanes are all-zero images whose outputs are sliced away and,
    because inference has no cross-batch ops (BN folded from running
    stats), cannot perturb real lanes.  ``chunks[bucket]`` is the batch a
    bucket's executable runs (``feed_chunk``): the bucket itself, or a
    divisor of it that ``infer`` feeds the device one chunk at a time.
    """

    def __init__(self, gxm, params, *, image_hw=(224, 224), mesh=None,
                 max_batch: int = 32, buckets=None, dtype=jnp.float32,
                 donate_input: bool | None = None,
                 autotune: str | None = "cache",
                 quantized: bool | None = None):
        self.gxm = gxm
        self.params = params
        self.image_hw = tuple(image_hw)
        self.mesh = mesh
        self.dtype = dtype
        # §II-K int8 serving (DESIGN.md §13): None defers to how the GxM was
        # built (its own default is the REPRO_QUANTIZE knob); an explicit
        # True on an f32 GxM re-marks its ETG in place.  ``params`` stays
        # the f32 tree — calibration runs on it; the quantized tree the
        # request path uses is derived at warmup (``calibrate``).
        if quantized is None:
            quantized = bool(getattr(gxm, "quantized", False))
        elif quantized and not getattr(gxm, "quantized", False):
            from repro.graph.etg import quantize_etg
            quantize_etg(gxm.etg)
            gxm.quantized = True
        self.quantized = quantized
        self.qparams = None
        self.act_scales = None
        # mode scoped around every trace/compile so the kernels' blocking
        # lookups see the entries warmup persisted ("cache": warmed winner
        # or analytic fallback — never a behavioral cliff); None defers to
        # the global REPRO_AUTOTUNE knob
        self.autotune = autotune
        from repro.launch.mesh import data_axis_size
        self.num_shards = data_axis_size(mesh) if mesh is not None else 1
        self.buckets = round_buckets(buckets, self.num_shards) if buckets \
            else make_buckets(max_batch, num_shards=self.num_shards)
        if donate_input is None:
            # donation is a no-op (plus a warning) on CPU backends
            donate_input = jax.default_backend() not in ("cpu",)
        self._fn = gxm.make_infer(mesh=mesh, donate_input=donate_input)
        image_bytes = (self.image_hw[0] * self.image_hw[1] * 3
                       * np.dtype(dtype).itemsize)
        self.chunks = {b: feed_chunk(b, image_bytes, self.num_shards)
                       for b in self.buckets}
        self._compiled: dict[int, object] = {}     # by the batch it runs
        self._joins: dict[tuple[int, int], object] = {}  # by (chunk, count)

    # -- shape / signature plumbing -----------------------------------------
    def local_batch(self, bucket: int) -> int:
        """Per-device batch a bucket lowers to inside shard_map (that of
        its chunk) — the ``minibatch`` coordinate of the autotuner cache
        key."""
        return self.chunks[bucket] // self.num_shards

    def conv_shapes(self) -> list[dict]:
        return conv_shapes(self.gxm.etg, self.image_hw)

    @property
    def _run_params(self):
        """The params tree the request path runs: the quantized tree once
        calibration produced one, the f32 tree otherwise."""
        if self.quantized and self.qparams is not None:
            return self.qparams
        return self.params

    # -- calibration ---------------------------------------------------------
    def calibrate(self, images=None, *, batches: int = 2, batch: int = 4,
                  seed: int = 0) -> dict:
        """Calibrate per-conv activation scales and build the quantized
        params tree (``core.quantize``).  ``images`` is an iterable of
        (n, H, W, 3) warmup batches; by default ``batches`` synthetic
        batches are drawn from a fixed-seed generator, so calibration is
        deterministic for a given seed.  Returns the scale dict."""
        assert self.quantized, "calibrate() on a non-quantized engine"
        from repro.core.quantize import calibrate_network, quantize_gxm_params
        if images is None:
            rng = np.random.default_rng(seed)
            images = [rng.standard_normal(
                (batch, *self.image_hw, 3)).astype(self.dtype)
                for _ in range(batches)]
        self.act_scales = calibrate_network(self.gxm, self.params, images)
        self.qparams = quantize_gxm_params(self.gxm.etg, self.params,
                                           self.act_scales)
        return self.act_scales

    # -- warmup --------------------------------------------------------------
    def warmup(self, *, autotune: str = "tune", cache=None,
               compile_buckets: bool = True) -> dict:
        """Pre-populate every cache a request would otherwise fall into:

        1. the persistent per-shape blocking cache (``repro.tune``) for every
           distinct conv signature × per-device bucket batch, and
        2. the compiled-executable cache: one AOT lower+compile per batch
           a bucket runs (its chunk; buckets that share a chunk share the
           executable), plus the join of a chunked bucket's logits (which
           also exercises the ETG's dedup'd ``kernel_cache`` ids), traced
           under this engine's ``autotune`` scope so the blocking lookups
           consult what step 1 just persisted.

        ``cache`` overrides the tuning *store* (tests / inspection); the
        compile-time lookups always read the process default cache
        (``REPRO_TUNE_CACHE``), so pass ``cache`` only together with that
        env override if the compiled blockings must match.  Returns a
        report dict (entry counts, compile seconds per bucket).
        """
        backend = be.resolve(self.gxm.impl)
        sigs = distinct_conv_signatures(self.conv_shapes())
        minibatches = sorted({self.local_batch(b) for b in self.buckets})
        if self.quantized and self.qparams is None:
            self.calibrate()          # deterministic synthetic batches
        # the quantized engine tunes/compiles the "q8" kind at 1 byte/elem;
        # its 4x-smaller bands admit taller rb_p under the same budget
        kind = "q8" if self.quantized else "fwd"
        db = 1 if self.quantized else 4
        report = {
            "conv_signatures": len(sigs),
            "pallas_path_signatures":
                sum(1 for s in sigs if lane_ok(s["c"], s["k"])),
            "kernel_cache_entries": len(self.gxm.etg.kernel_cache),
            "buckets": list(self.buckets),
            "tune_entries": 0,
            "compile_s": {},
            "conv_tiling": be.get_conv_tiling(),
            "vmem_budget": VMEM_BUDGET,
            "quantized": self.quantized,
        }
        if autotune != "off":
            entries = tune.warmup_convs(sigs, minibatches=minibatches,
                                        kinds=(kind,), mode=autotune,
                                        backend=backend, cache=cache,
                                        dtype_bytes=db)
            report["tune_entries"] = sum(1 for e in entries if e["cached"])
        # modeled per-grid-step VMEM high-water mark across the pallas-path
        # signatures (tiled: a row band — independent of image_hw, so large
        # serving buckets cannot blow the budget the way whole planes did)
        ws = [conv_blocking(**sg, dtype_bytes=db, backend=backend,
                            autotune="cache" if autotune != "off" else "off",
                            kind=kind, minibatch=max(minibatches))
              .vmem_bytes
              for sg in sigs if lane_ok(sg["c"], sg["k"])]
        report["max_conv_vmem_bytes"] = max(ws, default=0)
        if compile_buckets:
            for bucket in self.buckets:
                t0 = time.perf_counter()
                self._ensure_compiled(bucket)
                report["compile_s"][bucket] = round(
                    time.perf_counter() - t0, 3)
        return report

    def _autotune_scope(self):
        if self.autotune is None:
            import contextlib
            return contextlib.nullcontext()
        return be.use_autotune(self.autotune)

    def _ensure_compiled(self, bucket: int):
        c = self.chunks[bucket]
        if c not in self._compiled:
            x = jax.ShapeDtypeStruct((c, *self.image_hw, 3), self.dtype)
            with self._autotune_scope():
                self._compiled[c] = \
                    self._fn.lower(self._run_params, x).compile()
        fn = self._compiled[c]
        for m in range(2, bucket // c + 1):     # a partial bucket sends m
            if (c, m) not in self._joins:
                self._joins[c, m] = jax.jit(_join).lower(
                    *[fn.out_info] * m).compile()
        return fn

    def aot_executable(self, bucket: int):
        """Compiled executable a bucket runs, once per chunk (rooflines
        read its HLO)."""
        assert bucket in self.buckets, (bucket, self.buckets)
        return self._ensure_compiled(bucket)

    # -- the request path ----------------------------------------------------
    def infer(self, images):
        """Logits for ``images`` (n, H, W, 3), padded up to the minimal
        bucket, run by that bucket's warmed executable, padding sliced away.

        The bucket's batch goes to the device in chunks of
        ``chunks[bucket]`` images, the whole bucket where its input fits
        in ``FEED_CHUNK_BYTES``: each chunk is handed to the device and its
        executable dispatched in turn, so a chunk computes while the next
        is still in transit.  Chunks past the last real image are not sent,
        so only the last chunk holds padded lanes.  The chunks' logits are
        joined on the device.  Asynchronous: returns once every chunk is
        dispatched.  Its phases ``engine.pad``, then per chunk
        ``engine.chunk`` enclosing ``engine.put`` (handing the chunk to the
        device; the copy finishes asynchronously) and ``engine.run``
        (dispatch), are spans and counters (``repro.obs``)."""
        x = np.asarray(images, dtype=self.dtype)
        n = x.shape[0]
        if n > max(self.buckets):
            raise ValueError(f"batch {n} exceeds largest bucket "
                             f"{max(self.buckets)}; chunk it first")
        bucket = pick_bucket(n, self.buckets)
        c = self.chunks[bucket]
        m = -(-n // c)                          # chunks holding real images
        with obs.phase("engine.pad", n=n, bucket=bucket):
            if n < m * c:
                x = np.concatenate(
                    [x, np.zeros((m * c - n, *x.shape[1:]), x.dtype)])
        fn = self._compiled.get(c)
        outs = []
        for i in range(m):
            with obs.phase("engine.chunk", n=n, bucket=bucket, chunk=i):
                with obs.phase("engine.put", n=n, bucket=bucket):
                    xd = jnp.asarray(x[i * c:(i + 1) * c])
                with obs.phase("engine.run", n=n, bucket=bucket):
                    if fn is not None:
                        outs.append(fn(self._run_params, xd))
                    else:
                        with self._autotune_scope():  # unwarmed: trace here
                            outs.append(self._fn(self._run_params, xd))
        if m == 1:
            return outs[0][:n]
        join = self._joins.get((c, m), _join)
        return join(*outs)[:n]
