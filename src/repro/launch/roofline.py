"""Three-term roofline extraction from compiled dry-run artifacts.

  compute_s    = HLO_FLOPs_per_device / peak_FLOPs
  memory_s     = HLO_bytes_per_device / HBM_bw        (XLA "bytes accessed":
                 an upper bound on HBM traffic — fused ops count once)
  collective_s = Σ_ops per-device payload × ring_factor / link_bw

``cost_analysis()`` values on a partitioned module are already per-device.
Collective payloads are parsed from the compiled HLO: the result shape of
every all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute is the per-device shard; ring_factor(n) = 2(n-1)/n for
all-reduce, (n-1)/n otherwise.

Hardware model: the per-chip peaks of ``PEAKS``, keyed by the
``device_kind`` JAX reports.  Every modeled table (cost models, dry runs,
the CPU-run benches) prices ``MODEL_TARGET``, the TPU v5e; a path that
measures a real device looks its peaks up with ``device_peaks`` and fails
on a kind the table does not hold.
"""
from __future__ import annotations

import dataclasses
import re

# Published per-chip peaks (Google Cloud documentation, "TPU v5e"):
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of interchip interconnect
# over four links (~50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
MODEL_TARGET = "TPU v5 lite"     # the chip every modeled table prices

PEAK_FLOPS = PEAKS[MODEL_TARGET]["flops"]      # bf16 per chip
HBM_BW = PEAKS[MODEL_TARGET]["hbm_bw"]         # bytes/s per chip
ICI_BW = PEAKS[MODEL_TARGET]["ici_bw"]         # bytes/s per link
STEP_OVERHEAD_S = 5e-7       # grid-step pipeline-fill overhead (one source
                             # of truth; repro.tune.measure re-exports in us)

# Stable result-dict keys.  The bench JSONs persist these names and the
# perf-gate extractors (repro.perfci.extract) join on them — renaming one is
# a baseline-schema change and must bump perfci's SCHEMA_VERSION.
KERNEL_ROOFLINE_KEYS = ("compute_s", "memory_s", "step_time_s", "cost_s",
                        "dominant", "efficiency")
COMPOSITE_ROOFLINE_KEYS = ("cost_s", "flops", "hbm_bytes", "n_steps",
                           "launches", "efficiency")
CHAIN_ROOFLINE_KEYS = ("cost_s", "unfused_cost_s", "speedup", "flops",
                       "hbm_bytes", "unfused_hbm_bytes",
                       "intermediate_bytes", "launches", "efficiency",
                       "fused")


def device_peaks(device_kind: str) -> dict:
    """Peaks of the chip ``jax.Device.device_kind`` names.  A kind missing
    from ``PEAKS`` is an error, never a default: a measured path would
    otherwise be priced against another chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" add them to PEAKS with their source") from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")
# "%name = <shape or (tuple)> <collective>(" — shape first on RHS
_LINE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+(" + "|".join(_COLL) + r")(?:-start)?\(")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_V2 = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(s: str) -> int:
    total = 0
    for m in _SHAPE.finditer(s):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_V2.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


@dataclasses.dataclass
class CollectiveStats:
    per_device_bytes: float       # Σ payload shards
    wire_bytes: float             # Σ payload × ring factor
    by_kind: dict
    count: int


def parse_collectives(hlo_text: str, *, default_group: int) -> CollectiveStats:
    per_dev = 0.0
    wire = 0.0
    by_kind: dict[str, float] = {}
    count = 0
    for line in hlo_text.splitlines():
        m = _LINE.search(line)
        if not m:
            continue
        shape_s, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_s)
        if b == 0:
            continue
        n = max(_group_size(line, default_group), 2)
        factor = 2 * (n - 1) / n if kind == "all-reduce" else (n - 1) / n
        per_dev += b
        wire += b * factor
        by_kind[kind] = by_kind.get(kind, 0.0) + b
        count += 1
    return CollectiveStats(per_dev, wire, by_kind, count)


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_device: float
    bytes_per_device: float
    collectives: CollectiveStats
    model_flops_global: float
    useful_ratio: float           # MODEL_FLOPS / (HLO_FLOPs × chips)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time = max of the three overlappable terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Useful-FLOPs MFU at the roofline step time."""
        if self.step_time_s == 0:
            return 0.0
        chips_flops = self.model_flops_global / self.step_time_s
        return chips_flops / (PEAK_FLOPS * self._chips)

    _chips: int = 256


def kernel_roofline(*, flops: float, hbm_bytes: float, util: float = 1.0,
                    n_steps: int = 0,
                    step_overhead_s: float = STEP_OVERHEAD_S) -> dict:
    """Roofline terms for one *blocked kernel launch* (the per-layer analog
    of ``analyze``'s whole-module extraction).

    ``hbm_bytes`` is the schedule-resolved traffic from ``repro.tune``'s
    block-refetch model — including the multi-pass output term a C_b-blocked
    kernel pays when an output tile is revisited across accumulation passes
    (each extra visit is modeled as a read-back + rewrite, the conservative
    "bytes accessed" convention used for the HLO extraction above).
    ``efficiency`` is ideal-compute-time / modeled-cost: the Fig. 4 right
    axis ("% of peak") for one layer.
    """
    t_comp = flops / (PEAK_FLOPS * max(util, 1e-3))
    t_mem = hbm_bytes / HBM_BW
    step_time = max(t_comp, t_mem)
    cost = step_time + n_steps * step_overhead_s
    ideal = flops / PEAK_FLOPS
    return {
        "compute_s": t_comp,
        "memory_s": t_mem,
        "step_time_s": step_time,
        "cost_s": cost,
        "dominant": "compute" if t_comp >= t_mem else "memory",
        "efficiency": ideal / cost if cost > 0 else 0.0,
    }


def composite_roofline(parts: list[dict], *, extra_hbm_bytes: float = 0.0,
                       step_overhead_s: float = STEP_OVERHEAD_S) -> dict:
    """Roofline for a *multi-launch* kernel pipeline — e.g. the stride²
    phase sub-convolutions of the §II-I strided dual, or the dilate plan's
    single conv plus its materialization pass.

    Each part is a ``repro.tune.measure.conv_traffic`` dict (flops /
    hbm_bytes / util / n_steps); launches serialize, so the pipeline cost is
    the sum of per-launch ``kernel_roofline`` costs.  ``extra_hbm_bytes``
    charges non-kernel HBM traffic the pipeline pays between launches
    (materializing a dilated dO, re-interleaving phase outputs) at HBM
    bandwidth — traffic a zero-free plan avoids entirely.
    """
    cost = extra_hbm_bytes / HBM_BW + sum(
        kernel_roofline(flops=t["flops"], hbm_bytes=t["hbm_bytes"],
                        util=t.get("util", 1.0), n_steps=t.get("n_steps", 0),
                        step_overhead_s=step_overhead_s)["cost_s"]
        for t in parts)
    # summed per launch, in cost's order: ideal <= cost then holds term by
    # term and survives the rounding of both sums
    ideal = sum(t["flops"] / PEAK_FLOPS for t in parts)
    return {
        "cost_s": cost,
        "flops": sum(t["flops"] for t in parts),
        "hbm_bytes": extra_hbm_bytes + sum(t["hbm_bytes"] for t in parts),
        "n_steps": sum(t.get("n_steps", 0) for t in parts),
        "launches": len(parts),
        "efficiency": ideal / cost if cost > 0 else 0.0,
    }


def chain_roofline(chain_t: dict, *,
                   step_overhead_s: float = STEP_OVERHEAD_S) -> dict:
    """Roofline for a depth-first fused conv chain (DESIGN.md §16).

    ``chain_t`` is a ``repro.tune.measure.chain_traffic`` dict.  The fused
    cost composites the per-band-step launches of the interleaved schedule
    (hand-off bands already priced at 0 HBM bytes); the unfused cost
    composites the layer-by-layer launches.  When the chain fell back
    (``fused=False``) the two are identical by construction — the fallback
    rule — so ``speedup`` is exactly 1.0 there.
    """
    fused_roof = composite_roofline(chain_t["parts"],
                                    step_overhead_s=step_overhead_s)
    unfused_roof = composite_roofline(chain_t["unfused_parts"],
                                      step_overhead_s=step_overhead_s)
    cost = fused_roof["cost_s"]
    return {
        "cost_s": cost,
        "unfused_cost_s": unfused_roof["cost_s"],
        "speedup": unfused_roof["cost_s"] / cost if cost > 0 else 0.0,
        "flops": fused_roof["flops"],
        "hbm_bytes": chain_t["hbm_bytes"],
        "unfused_hbm_bytes": chain_t["unfused_hbm_bytes"],
        "intermediate_bytes": chain_t["intermediate_bytes"],
        "launches": fused_roof["launches"],
        "efficiency": fused_roof["efficiency"],
        "fused": chain_t["fused"],
    }


def analyze(compiled, *, chips: int, model_flops_global: float) -> Roofline:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    colls = parse_collectives(compiled.as_text(), default_group=chips)
    r = Roofline(
        compute_s=flops / PEAK_FLOPS,
        memory_s=bytes_acc / HBM_BW,
        collective_s=colls.wire_bytes / ICI_BW,
        flops_per_device=flops,
        bytes_per_device=bytes_acc,
        collectives=colls,
        model_flops_global=model_flops_global,
        useful_ratio=(model_flops_global / (flops * chips)
                      if flops else 0.0),
    )
    r._chips = chips
    return r


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N_active·D for training, 2·N_active·D for inference
    forward (D = tokens processed)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def to_dict(r: Roofline) -> dict:
    return {
        "compute_s": r.compute_s,
        "memory_s": r.memory_s,
        "collective_s": r.collective_s,
        "dominant": r.dominant,
        "step_time_s": r.step_time_s,
        "flops_per_device": r.flops_per_device,
        "bytes_per_device": r.bytes_per_device,
        "collective_per_device_bytes": r.collectives.per_device_bytes,
        "collective_wire_bytes": r.collectives.wire_bytes,
        "collective_count": r.collectives.count,
        "collective_by_kind": r.collectives.by_kind,
        "model_flops_global": r.model_flops_global,
        "useful_ratio": r.useful_ratio,
        "roofline_fraction": r.roofline_fraction,
    }
