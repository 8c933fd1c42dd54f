"""The names the program gives its work (``repro.obs``), read from a trace.

``trace.load``'s record holds each device op by its HLO instruction name
and the harness's host spans.  ``load`` adds to it:

  "scopes":  {module: {instruction: op_name}}   from the HLO metadata
  "program": [[span, start_ns, dur_ns]]         the program's host spans

An ``op_name`` is the scope path the op was traced under, transforms
included, e.g. ``jit(dp_step)/grads/transpose(jvp(s1b0_c2))/conv_bwd_data/
pallas_call``: the GxM task (``s1b0_c2``), the conv pass
(``conv_bwd_data``), batch norm (``bn``) and the step's phase (``grads``).
``reduce`` sums device time by task and pass, per chip averaged, and puts
each idle gap down to the innermost host span over it (the program's spans
nest inside the harness's ``step``).  Durations are summed op by op.
"""
from __future__ import annotations

import bisect
import re

from chipbench import trace

CONV_PASSES = ("conv_fwd", "conv_bwd_data", "conv_wu", "conv_q8",
               "conv_chain")
STEP_SCOPES = ("grads", "grad_allreduce", "bn_pmean", "sgd")
SCOPES = CONV_PASSES + ("bn",) + STEP_SCOPES
# the kind ``counts.pass_bytes`` counts for a kernel's pass
PASS_KIND = {"conv_fwd": "fwd", "conv_bwd_data": "bwd", "conv_wu": "wu"}
_META = re.compile(r'op_name="([^"]*)"')
_TOKEN = re.compile(r"[^/()]+")


def op_names(text: str) -> dict[str, str]:
    """{instruction name: op_name} of one HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = trace._INSTR.match(line)
        if not m:
            continue
        meta = _META.search(m.group(2))
        if meta:
            out[m.group(1)] = meta.group(1)
    return out


def classify(op_name: str, tasks) -> tuple[str | None, str | None, bool,
                                            bool]:
    """(task, conv pass, under ``bn``, under any program scope) of an op;
    the innermost task and pass where scopes nest."""
    toks = _TOKEN.findall(op_name)
    task = next((t for t in reversed(toks) if t in tasks), None)
    pas = next((t for t in reversed(toks) if t in CONV_PASSES), None)
    return (task, pas, "bn" in toks,
            task is not None or any(t in SCOPES for t in toks))


def program_spans(xplane: str, names) -> list:
    """[[span, start_ns, dur_ns]] of the host events named in ``names``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out += [[e.name, e.start_ns, e.duration_ns]
                        for e in ln.events if e.name in names]
    return out


def load(xplane: str, hlo: list[str]) -> dict:
    """``trace.load``'s record with the scope map and the program's spans
    (none from a program without ``repro.obs``)."""
    try:
        from repro.obs import SPANS
    except ImportError:
        SPANS = ()
    rec = trace.load(xplane, hlo)
    rec["scopes"] = {}
    for text in hlo:
        rec["scopes"].setdefault(trace.module_name(text), {}).update(
            op_names(text))
    rec["program"] = program_spans(xplane, SPANS)
    return rec


def idle_gaps(busy, host, w0, w1) -> list:
    """Idle time of a chip inside the window, by the innermost host span
    over each gap's middle (the latest-starting span that covers it, of
    two that start together the shorter, then the later in ``host``;
    ``none`` outside every span); the ten largest totals, in seconds.
    Where no spans nest this is ``trace.idle_gaps``."""
    spans = sorted(((s, s + d, name) for name, s, d in host),
                   key=lambda sp: (sp[0], -sp[1]))
    starts = [s for s, _, _ in spans]
    reach, far = [], float("-inf")          # furthest end among spans[:i+1]
    for _, e, _ in spans:
        far = max(far, e)
        reach.append(far)
    totals: dict[str, float] = {}
    for a, b in trace.minus([(w0, w1)], busy):
        mid = (a + b) / 2
        label = "none"
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0 and reach[i] >= mid:
            if spans[i][1] >= mid:
                label = spans[i][2]
                break
            i -= 1
        totals[label] = totals.get(label, 0.0) + (b - a)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return [[k, v * 1e-9] for k, v in top]


def reduce(rec: dict, tasks) -> dict | None:
    """Device time per chip by scope over the traced window, in seconds:
    Mosaic time per conv pass and with a task; XLA time under ``bn``, under
    a conv pass (its glue: pads, slices, casts; XLA convolutions apart),
    and under any program scope; device time with no task; ``rows``, the
    time of each (task, pass or role); the idle gaps by innermost span."""
    if rec["window"] is None or not rec["devices"]:
        return None
    w0, w1 = rec["window"]
    scopes = rec.get("scopes", {})
    keys = ("device", "mosaic", "mosaic_task", "xla", "xla_scoped", "bn",
            "glue", "xla_conv", "no_task")
    tot = dict.fromkeys(keys, 0.0)
    by_pass: dict[str, float] = {}
    rows: dict[tuple, float] = {}
    first_busy = None
    for plane in sorted(rec["devices"]):
        iv = []
        for name, mod, start, dur, kind in rec["devices"][plane]:
            a, b = max(start, w0), min(start + dur, w1)
            if dur <= 0 or b <= a:
                continue
            iv.append((a, b))
            d = b - a
            op_name = scopes.get(mod, {}).get(name, "")
            task, pas, bn, scoped = classify(op_name, tasks)
            tot["device"] += d
            tot["no_task"] += d * (task is None)
            if kind == "mosaic":
                tot["mosaic"] += d
                tot["mosaic_task"] += d * (task is not None)
                by_pass[pas or "none"] = by_pass.get(pas or "none", 0.0) + d
                role = pas or "kernel"
            else:
                tot["xla"] += d
                tot["xla_scoped"] += d * scoped
                conv = op_name.endswith("conv_general_dilated")
                if bn:
                    tot["bn"] += d
                    role = "bn"
                elif pas and conv:
                    tot["xla_conv"] += d
                    role = pas + ":xla"
                elif pas:
                    tot["glue"] += d
                    role = pas + ":glue"
                else:
                    role = "xla"
            rows[(task, role)] = rows.get((task, role), 0.0) + d
        if first_busy is None:
            first_busy = trace.union(iv)
    n = len(rec["devices"])
    out = {f"{k}_s": v * 1e-9 / n for k, v in tot.items()}
    out["mosaic_by_pass"] = {k: v * 1e-9 / n for k, v in by_pass.items()}
    out["rows"] = sorted(([t, r, v * 1e-9 / n] for (t, r), v in rows.items()),
                         key=lambda row: -row[2])
    out["idle_gaps"] = idle_gaps(first_busy, rec["host"]
                                 + rec.get("program", []), w0, w1)
    return out


def pass_ideal_s(lay: dict, pas: str, batch: int, peak: dict,
                 train: bool) -> float | None:
    """Least time of one conv's pass at ``batch`` images (``counts``'s
    roofline), or None for a pass it does not count."""
    from chipbench import counts
    kind = PASS_KIND.get(pas)
    if kind is None or not counts.on_kernel(lay):
        return None
    if kind == "fwd" and train:
        kind = "fwd_train"
    return max(counts.conv_flops(lay) * batch / peak["flops"],
               counts.pass_bytes(lay, kind, batch) / peak["hbm_bw"])


def table(red: dict, layers: list[dict], images: int, peak: dict, *,
          train: bool, top: int = 20) -> list[list]:
    """The ``top`` (task, pass or role) rows by device time: seconds, share
    of device time, and for a kernel pass its roofline share, in %;
    ``images`` is the traced window's images per chip."""
    by_name = {lay["name"]: lay for lay in layers}
    out = []
    for task, role, s in red["rows"][:top]:
        ideal = pass_ideal_s(by_name[task], role, images, peak, train) \
            if task in by_name else None
        out.append([task, role, round(s, 6),
                    round(100.0 * s / red["device_s"], 3),
                    None if ideal is None else round(100.0 * ideal / s, 3)])
    return out


# -- per-layer metrics ``trace.reduce``'s breakdown can give -------------

def kernel_s(red: dict | None, name: str) -> float | None:
    """Device seconds per chip of the Mosaic kernels whose instruction is
    named ``name`` (the program names each kernel by its pass), from the
    breakdown of ``trace.reduce``; None where it does not list them."""
    if red is None:
        return None
    return dict(red["breakdown"]["device_ops"]).get("mosaic:" + name)


def pass_roofline(ctx: dict, name: str, kind: str) -> float | None:
    """The training kernels of one pass at their roofline, in %: the least
    time of pass ``kind`` of every kernel conv over the traced steps, per
    chip, over the device time of the kernels named ``name``."""
    from chipbench import counts, device
    t = kernel_s(ctx["trace"], name)
    if not t:
        return None
    cfg = ctx["config"]
    layers = ctx["ref"].conv_layers(cfg, (cfg["image"], cfg["image"]))
    ideal = counts.kernel_ideal_s(layers, (kind,),
                                  ctx["mix"]["per_chip_batch"],
                                  device.peaks(ctx["device_kind"]))
    return 100.0 * ctx["traced"]["steps"] * ideal / t
