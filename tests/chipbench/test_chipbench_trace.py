"""The reduction from a trace to device metrics: busy union, idle share,
Mosaic against XLA time, exposed collective time and the breakdown."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import trace  # noqa: E402

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"

HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %custom-call.3 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call", backend_config={}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %custom-call.3), kind=kLoop, calls=%f
  %all-reduce-start.1 = (f32[8]{0}, f32[8]{0}) all-reduce-start(f32[8]{0} %fusion.2), replica_groups={}
  %custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="Sharding"
  ROOT %all-reduce-done.1 = f32[8]{0} all-reduce-done((f32[8]{0}, f32[8]{0}) %all-reduce-start.1)
}
"""


def test_hlo_kinds_and_module_name():
    kinds = trace.hlo_kinds(HLO)
    assert kinds == {"custom-call.3": "mosaic",
                     "all-reduce-start.1": "collective",
                     "all-reduce-done.1": "collective"}
    assert trace.module_name(HLO) == "jit_step"


def test_interval_arithmetic():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)] and trace.length(u) == 6
    assert trace.minus([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                         (6, 10)]
    assert trace.minus([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def _record():
    """Two chips, a 100 ns window; chip 0: kernel 10-40, fusion 40-60,
    all-reduce 55-80 (exposed 60-80); chip 1: kernel 10-30 only."""
    op = lambda n, a, b, k: [n, "jit_step", a, b - a, k]  # noqa: E731
    return {"window": [0, 100],
            "host": [["step", 0, 50], ["block", 50, 50]],
            "devices": {
                "/device:TPU:0": [op("custom-call.3", 10, 40, "mosaic"),
                                  op("fusion.2", 40, 60, "xla"),
                                  op("all-reduce.1", 55, 80, "collective"),
                                  op("fusion.9", 120, 130, "xla")],
                "/device:TPU:1": [op("custom-call.3", 10, 30, "mosaic")]}}


def test_reduce_by_hand():
    r = trace.reduce(_record())
    ns = 1e-9
    assert r["window_s"] == pytest.approx(100 * ns)
    assert r["busy_s"] == pytest.approx((70 + 20) / 2 * ns)
    assert r["mosaic_s"] == pytest.approx((30 + 20) / 2 * ns)
    assert r["collective_exposed_s"] == pytest.approx(20 / 2 * ns)
    assert r["mosaic_events"] == 1 and r["chips"] == 2
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["mosaic:custom-call"] == pytest.approx(25 * ns)
    gaps = dict(r["breakdown"]["idle_gaps"])       # chip 0: 0-10, 80-100
    assert gaps["step"] == pytest.approx(10 * ns)
    assert gaps["block"] == pytest.approx(20 * ns)


def test_no_device_ops_reads_nothing():
    rec = _record()
    rec["devices"] = {}
    assert trace.reduce(rec) is None


def test_reduce_a_trace_recorded_on_the_chip():
    """0.75 s of `resnet101.offline` traced on one v5e (bucket 128): its
    ops, matched to the executable's HLO, reduce to the numbers an
    independent pass over the same events gives."""
    rec = trace.from_json(str(FIXTURES / "offline_resnet101_trace.json.gz"))
    ops = rec["devices"]["/device:TPU:0"]
    w0, w1 = rec["window"]
    kinds = {o[4] for o in ops}
    assert kinds == {"mosaic", "xla"}
    mosaic = [o for o in ops if o[4] == "mosaic"]
    assert len(mosaic) == 2 * 103          # two batches of 103 kernels
    assert {o[0].split(".")[0] for o in mosaic} == {"infer"}
    ends = sorted((max(o[2], w0), min(o[2] + o[3], w1)) for o in ops
                  if o[3] > 0 and o[2] < w1)
    busy, cur = 0.0, None
    for a, b in ends:
        if cur is None or a > cur[1]:
            busy += 0 if cur is None else cur[1] - cur[0]
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    busy += cur[1] - cur[0]
    r = trace.reduce(rec)
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert r["mosaic_s"] == pytest.approx(sum(o[3] for o in mosaic) * 1e-9)
    assert 0 < r["mosaic_busy_s"] <= r["busy_s"] < r["window_s"]
    assert r["collective_events"] == 0 and r["collective_exposed_s"] == 0
    assert r["breakdown"]["device_ops"][0][0] == "mosaic:infer"
    assert len(r["breakdown"]["device_ops"]) <= 10
    gaps = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx(r["window_s"] - r["busy_s"])


def _reader(name):
    from chipbench import bench
    return bench.metric_reader(name)


def test_exposed_allreduce_reader_by_hand():
    """Chip 0 has 20 ns of its all-reduce with no compute beside it, chip 1
    none: 10 ns of a 100 ns window on the average chip."""
    read = _reader("allreduce_exposed_share.dp4")
    assert read({"trace": trace.reduce(_record())}) == pytest.approx(10.0)


@pytest.mark.parametrize("name", ["allreduce_exposed_share.dp4"])
def test_collective_reader_reads_nothing_without_collectives(name):
    """A trace with no cross-chip collective (one chip serving) gives the
    reader nothing to read, and it returns nothing rather than 0."""
    rec = trace.from_json(str(FIXTURES / "offline_resnet101_trace.json.gz"))
    assert _reader(name)({"trace": trace.reduce(rec)}) is None
    assert _reader(name)({"trace": None}) is None
