"""From a profiler trace to the device metrics of one traced window.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a small
neutral record (``to_json`` / ``from_json`` keep it as a fixture):

  {"window": [start_ns, end_ns],          the harness's "window" span
   "host": [[span, start_ns, dur_ns]],    the harness's other spans
   "devices": {plane: [[op, module, start_ns, dur_ns, kind]]}}

``op`` is the HLO instruction name and ``kind`` is "mosaic" for a Pallas
kernel (an HLO ``custom-call`` whose target is ``tpu_custom_call`` in the
executable that ran), "collective" for a cross-chip collective, else
"xla".  ``reduce`` turns the record into
busy and idle time, Mosaic and XLA time, exposed collective time and the
``breakdown`` of the result line, all per chip averaged over the chips.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re

from chipbench.spans import NAMES

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")


def _opcode(rhs: str) -> str:
    """Opcode of an HLO instruction's right-hand side (after the shape)."""
    i = 0
    if rhs.startswith("("):                     # tuple shape
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rhs.find(" ")
    rest = rhs[i:].lstrip()
    return rest.split("(", 1)[0].strip()


def hlo_kinds(text: str) -> dict[str, str]:
    """{instruction name: kind} of one HLO module's text."""
    kinds = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        op = _opcode(rhs)
        if op == "custom-call" and 'custom_call_target="tpu_custom_call"' \
                in rhs:
            kinds[name] = "mosaic"
        elif any(op.startswith(c) for c in COLLECTIVES):
            kinds[name] = "collective"
    return kinds


def module_name(text: str) -> str:
    """The ``HloModule`` name of an HLO text."""
    return text.split(None, 2)[1].rstrip(",")


def _base(name: str) -> str:
    """A trace's module name without its program id, ``jit_f(12)``."""
    return re.sub(r"\(\d+\)$", "", name)


_OP = re.compile(r"^%?([\w.\-]+)")


def _kind_from_text(text: str) -> str:
    """Kind of an op from the HLO instruction text the trace names it by,
    for a module whose executable was not given."""
    if 'custom_call_target="tpu_custom_call"' in text:
        return "mosaic"
    rhs = text.split("=", 1)[1].strip() if "=" in text else text
    op = _opcode(rhs)
    return "collective" if any(op.startswith(c) for c in COLLECTIVES) \
        else "xla"


def load(xplane: str, hlo: list[str]) -> dict:
    """The neutral record of one trace; ``hlo`` holds the HLO texts of the
    programs that ran.  A device op's trace event is named by its HLO
    instruction; it is matched by instruction name within its module
    (the "XLA Modules" event it starts in)."""
    from jax.profiler import ProfileData
    kinds: dict[str, dict[str, str]] = {}
    for text in hlo:                 # buckets share a module name
        kinds.setdefault(module_name(text), {}).update(hlo_kinds(text))
    pd = ProfileData.from_file(xplane)
    rec = {"window": None, "host": [], "devices": {}}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           _base(e.name))
                          for e in lines["XLA Modules"].events) \
                if "XLA Modules" in lines else []
            ops, j = [], 0
            for e in sorted(lines["XLA Ops"].events,
                            key=lambda e: e.start_ns) \
                    if "XLA Ops" in lines else []:
                while j < len(mods) and mods[j][1] < e.start_ns:
                    j += 1
                mod = mods[j][2] if j < len(mods) and \
                    mods[j][0] <= e.start_ns else ""
                m = _OP.match(e.name)
                name = m.group(1) if m else e.name
                kind = kinds.get(mod, {}).get(name) or (
                    "xla" if mod in kinds else _kind_from_text(e.name))
                ops.append([name, mod, e.start_ns, e.duration_ns, kind])
            rec["devices"][plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == "window":
                        rec["window"] = [e.start_ns,
                                         e.start_ns + e.duration_ns]
                    elif e.name in NAMES:
                        rec["host"].append([e.name, e.start_ns,
                                            e.duration_ns])
    return rec


def find_xplane(tdir: str) -> str | None:
    found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getsize) if found else None


# -- interval arithmetic -------------------------------------------------------

def union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(iv) -> float:
    return sum(b - a for a, b in iv)


def minus(iv, cover) -> list[tuple[float, float]]:
    """Parts of the (disjoint, sorted) ``iv`` outside the (disjoint,
    sorted) ``cover``."""
    out, j = [], 0
    for a, b in iv:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# -- reduction ----------------------------------------------------------------

def _label(name: str, kind: str) -> str:
    """A device op's breakdown label: Mosaic kernels together, others by
    HLO op without its instance number (``fusion.12`` -> ``fusion``)."""
    if kind == "mosaic":
        return "mosaic:" + re.sub(r"[.\d]+$", "", name)
    return re.sub(r"[.\d]+$", "", name) or name


def reduce(rec: dict) -> dict | None:
    """Per-chip means over the traced window; None without device ops."""
    if rec["window"] is None or not rec["devices"]:
        return None
    w0, w1 = rec["window"]
    window_s = (w1 - w0) * 1e-9
    per = []
    totals: dict[str, float] = {}
    first_busy = None
    for plane in sorted(rec["devices"]):
        ops = [o for o in rec["devices"][plane] if o[3] > 0]
        iv = {"all": [], "mosaic": [], "collective": [], "xla": []}
        mosaic_sum = 0.0
        n_mosaic = n_coll = 0
        for name, _, start, dur, kind in ops:
            a, b = start, start + dur
            if b <= w0 or a >= w1:
                continue
            a, b = max(a, w0), min(b, w1)
            iv["all"].append((a, b))
            iv[kind].append((a, b))
            if kind == "mosaic":
                mosaic_sum += b - a
                n_mosaic += 1
            n_coll += kind == "collective"
            lab = _label(name, kind)
            totals[lab] = totals.get(lab, 0.0) + (b - a)
        busy = union(iv["all"])
        coll = union(iv["collective"])
        compute = union(iv["mosaic"] + iv["xla"])
        per.append({"busy": length(busy), "mosaic": mosaic_sum,
                    "mosaic_busy": length(union(iv["mosaic"])),
                    "exposed": length(minus(coll, compute)),
                    "n_mosaic": n_mosaic, "n_coll": n_coll})
        if first_busy is None:
            first_busy = busy
    n = len(per)
    if not any(p["busy"] for p in per):
        return None

    def mean(k):
        return sum(p[k] for p in per) / n * 1e-9

    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": window_s, "busy_s": mean("busy"),
            "mosaic_s": mean("mosaic"), "mosaic_busy_s": mean("mosaic_busy"),
            "collective_exposed_s": mean("exposed"),
            "mosaic_events": sum(p["n_mosaic"] for p in per) // n,
            # on the chip that ran most (a chip may run none)
            "collective_events": max(p["n_coll"] for p in per),
            "chips": n,
            "breakdown": {
                "device_ops": [[k, v * 1e-9 / n] for k, v in top],
                "idle_gaps": idle_gaps(first_busy, rec["host"], w0, w1)}}


def idle_gaps(busy, host, w0, w1) -> list:
    """Idle time of the first chip inside the window, by the harness span
    the host was in at each gap's middle (``none`` outside every span; the
    spans under the window run one after another on one thread); the ten
    largest totals, in seconds."""
    gaps = minus([(w0, w1)], busy)
    spans = sorted((s, s + d, name) for name, s, d in host)
    starts = [s for s, _, _ in spans]
    totals: dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        label = spans[i][2] if i >= 0 and spans[i][1] >= mid else "none"
        totals[label] = totals.get(label, 0.0) + (b - a)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return [[k, v * 1e-9] for k, v in top]


def reduce_dir(tdir: str, hlo: list[str], *, n_devices: int,
               keep: str | None = None) -> dict | None:
    """Reduce the trace a run wrote under ``tdir``; ``keep``: a directory
    that gets the neutral record, the trace's layout and the HLO texts."""
    path = find_xplane(tdir)
    if path is None:
        return None
    if keep:
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, "layout.json"), "w") as f:
            json.dump(describe(path), f, indent=1)
        for i, text in enumerate(hlo):
            with gzip.open(os.path.join(keep, f"{module_name(text)}.{i}"
                                        ".hlo.txt.gz"), "wt") as f:
                f.write(text)
    rec = load(path, hlo)
    rec["devices"] = dict(sorted(rec["devices"].items())[:n_devices])
    if keep:
        to_json(rec, os.path.join(keep, "record.json.gz"))
    return reduce(rec)


def describe(xplane: str, per_line: int = 12) -> list:
    """Planes, lines and each line's first events with their stats."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        lines = []
        for ln in plane.lines:
            evs = list(ln.events)
            lines.append({"line": ln.name, "events": len(evs), "first": [
                {"name": e.name, "start_ns": e.start_ns,
                 "dur_ns": e.duration_ns,
                 "stats": {k: str(v)[:300] for k, v in e.stats}}
                for e in evs[:per_line]]})
        out.append({"plane": plane.name, "lines": lines})
    return out


def to_json(rec: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(rec, f)


def from_json(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
