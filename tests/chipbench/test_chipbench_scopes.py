"""The trace read by the names the program gives its work: op scopes from
the HLO metadata, idle gaps by the innermost host span, the per-pass
roofline readers, and the diagnosis run on a cell small enough for the
CPU."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench import bench, scopes, trace  # noqa: E402

FIXTURE = pathlib.Path(__file__).resolve().parent / "fixtures" \
    / "offline_resnet101_trace.json.gz"
TASKS = {"a", "b", "conv1"}

HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %conv_fwd.1 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/grads/jvp(a)/conv_fwd/pallas_call" stack_frame_id=1}
  %copy.6 = f32[8]{0} copy(f32[8]{0} %conv_fwd.1)
  ROOT %fusion.4 = f32[8]{0} fusion(f32[8]{0} %copy.6), kind=kLoop, calls=%f, metadata={op_name="jit(step)/grads/jvp(a)/bn/mul"}
}
"""


def test_op_names_and_classify():
    names = scopes.op_names(HLO)
    assert names == {
        "conv_fwd.1": "jit(step)/grads/jvp(a)/conv_fwd/pallas_call",
        "fusion.4": "jit(step)/grads/jvp(a)/bn/mul"}
    assert scopes.classify(names["conv_fwd.1"], TASKS) == ("a", "conv_fwd",
                                                           False, True)
    back = "jit(step)/grads/transpose(jvp(b))/conv_bwd_data/pallas_call"
    assert scopes.classify(back, TASKS) == ("b", "conv_bwd_data", False,
                                            True)
    assert scopes.classify("jit(step)/grads/transpose(jvp(a))/bn/reduce_sum",
                           TASKS) == ("a", None, True, True)
    assert scopes.classify("jit(step)/sgd/sub", TASKS) == (None, None, False,
                                                          True)
    assert scopes.classify("", TASKS) == (None, None, False, False)


def _record():
    """One chip, a 200 ns window, two harness steps; the program's spans
    nest inside the first.  Busy 20-80: three kernels, one per pass, then
    XLA batch norm, conv glue, a copy with no scope and an XLA conv; and
    100-105, the optimizer."""
    M = "jit_step"
    ops = [("conv_fwd.1", 20, 40, "mosaic",
            "jit(step)/grads/jvp(a)/conv_fwd/pallas_call"),
           ("conv_bwd_data.2", 40, 50, "mosaic",
            "jit(step)/grads/transpose(jvp(a))/conv_bwd_data/pallas_call"),
           ("conv_wu.3", 50, 60, "mosaic",
            "jit(step)/grads/transpose(jvp(b))/conv_wu/pallas_call"),
           ("fusion.4", 60, 65, "xla", "jit(step)/grads/jvp(a)/bn/mul"),
           ("fusion.5", 65, 70, "xla",
            "jit(step)/grads/jvp(a)/conv_fwd/pad"),
           ("copy.6", 70, 75, "xla", None),
           ("fusion.7", 75, 80, "xla",
            "jit(step)/grads/jvp(conv1)/conv_fwd/conv_general_dilated"),
           ("fusion.8", 100, 105, "xla", "jit(step)/sgd/sub")]
    return {"window": [0, 200],
            "host": [["step", 0, 100], ["step", 100, 100]],
            "program": [["serve.step", 0, 100], ["serve.stack", 5, 15],
                        ["engine.run", 25, 5], ["serve.fetch", 60, 25]],
            "scopes": {M: {n: o for n, *_, o in ops if o}},
            "devices": {"/device:TPU:0": [[n, M, a, b - a, k]
                                          for n, a, b, k, _ in ops]}}


def test_reduce_by_scope_by_hand():
    rec = _record()
    red = scopes.reduce(rec, TASKS)
    ns = 1e-9
    assert red["mosaic_by_pass"] == pytest.approx(
        {"conv_fwd": 20 * ns, "conv_bwd_data": 10 * ns, "conv_wu": 10 * ns})
    # every kernel falls to exactly one pass: the passes add up to Mosaic
    assert sum(red["mosaic_by_pass"].values()) == pytest.approx(
        trace.reduce(rec)["mosaic_s"])
    assert red["mosaic_task_s"] == pytest.approx(40 * ns)
    assert red["bn_s"] == pytest.approx(5 * ns)
    assert red["glue_s"] == pytest.approx(5 * ns)
    assert red["xla_conv_s"] == pytest.approx(5 * ns)
    assert red["xla_s"] == pytest.approx(25 * ns)
    assert red["xla_scoped_s"] == pytest.approx(20 * ns)
    assert red["no_task_s"] == pytest.approx(10 * ns)
    rows = {(t, r): s for t, r, s in red["rows"]}
    assert rows[("a", "conv_fwd:glue")] == pytest.approx(5 * ns)
    assert rows[(None, "xla")] == pytest.approx(10 * ns)     # copy, sgd
    # gaps 0-20, 80-100, 105-200: the innermost span over each middle
    gaps = dict(red["idle_gaps"])
    assert gaps == pytest.approx({"serve.stack": 20 * ns,
                                  "serve.step": 20 * ns, "step": 95 * ns})
    # the harness's reduction, whose spans never nest, calls 80-100 "none"
    old = dict(trace.idle_gaps([(20, 80), (100, 105)],
                               rec["host"] + rec["program"], 0, 200))
    assert old["none"] == pytest.approx(20 * ns)


def test_table_by_task_and_pass():
    cfg = bench.load_config("resnet50")
    layers = bench.family(cfg, "ref").conv_layers(cfg, (224, 224))
    rec = _record()
    for ops in rec["devices"].values():
        for op in ops:
            op[0] = op[0].replace(".", "_x.")            # instruction names
    rec["scopes"]["jit_step"] = {k.replace(".", "_x."): v for k, v in
                                 rec["scopes"]["jit_step"].items()}
    red = scopes.reduce(rec, {"s0b0_c1", "conv1"} | TASKS)
    peak = {"flops": 197e12, "hbm_bw": 819e9}
    rows = scopes.table(red, layers, 96, peak, train=True)
    assert [r[:2] for r in rows][:1] == [["a", "conv_fwd"]]
    assert all(r[4] is None for r in rows)          # no layer named a or b
    lay = layers[1]
    assert scopes.pass_ideal_s(lay, "conv_wu", 96, peak, True) > 0
    assert scopes.pass_ideal_s(layers[0], "conv_fwd", 96, peak,
                               True) is None


def test_fixture_reduces_as_before():
    """The recorded offline trace gives the values and breakdown it gave
    when it was committed, and the innermost-span gaps equal the harness's
    where no spans nest."""
    rec = trace.from_json(str(FIXTURE))
    r = trace.reduce(rec)
    assert r["window_s"] == pytest.approx(0.7458324070000001)
    assert r["busy_s"] == pytest.approx(0.393650763)
    assert r["mosaic_s"] == r["mosaic_busy_s"] == pytest.approx(
        0.28775959500000003)
    assert r["mosaic_events"] == 206 and r["collective_events"] == 0
    ops = r["breakdown"]["device_ops"]
    assert [k for k, _ in ops] == [
        "mosaic:infer", "pad_bitcast_fusion", "broadcast_select_fusion",
        "copy", "add_maximum_fusion", "fusion", "copy_bitcast_fusion",
        "reduce_window_max", "pad.26.clone", "pad.49.clone"]
    assert ops[1][1] == pytest.approx(0.046327498)
    assert r["breakdown"]["idle_gaps"] == [["step", pytest.approx(
        0.352181644)]]
    w0, w1 = rec["window"]
    busy = trace.union([(max(o[2], w0), min(o[2] + o[3], w1))
                        for o in rec["devices"]["/device:TPU:0"]
                        if o[3] > 0 and o[2] < w1 and o[2] + o[3] > w0])
    assert scopes.idle_gaps(busy, rec["host"], w0, w1) \
        == trace.idle_gaps(busy, rec["host"], w0, w1)
    cfg = bench.load_config("resnet101")
    ctx = {"trace": r, "config": cfg, "ref": bench.family(cfg, "ref"),
           "device_kind": "TPU v5 lite", "traced": {"by_bucket": {128: 2}}}
    got = {m: bench.metric_reader(m)(ctx) for m in (
        "xla_op_share.offline", "conv_roofline.offline", "idle_share.train",
        "xla_op_share.train", "allreduce_exposed_share.dp4")}
    assert got == {"xla_op_share.offline": pytest.approx(26.899774610623574),
                   "conv_roofline.offline": pytest.approx(19.05450522123206),
                   "idle_share.train": pytest.approx(47.219943876748175),
                   "xla_op_share.train": pytest.approx(26.899774610623574),
                   "allreduce_exposed_share.dp4": None}


PASS_READERS = {"conv_fwd_roofline.train": "conv_fwd",
                "conv_bwd_data_roofline.train": "conv_bwd_data",
                "conv_wu_roofline.train": "conv_wu"}


def _train_ctx(red):
    cfg = bench.load_config("resnet50")
    return {"trace": red, "config": cfg, "ref": bench.family(cfg, "ref"),
            "device_kind": "TPU v5 lite", "mix": {"per_chip_batch": 96},
            "traced": {"steps": 3}}


def test_pass_rooflines_split_the_conv_roofline():
    """Kernels named by their pass: the three passes' ideal times and
    device times add up to ``conv_roofline.train``'s."""
    red = trace.reduce(_record())
    ctx = _train_ctx(red)
    total = bench.metric_reader("conv_roofline.train")(ctx)
    parts = 0.0
    for name, kernel in PASS_READERS.items():
        share = bench.metric_reader(name)(ctx)
        assert share > 0
        parts += share * scopes.kernel_s(red, kernel)      # ideal x 100
    assert sum(scopes.kernel_s(red, k) for k in PASS_READERS.values()) \
        == pytest.approx(red["mosaic_s"])
    assert parts == pytest.approx(total * red["mosaic_s"])
    # the diagnosis reads the same shares from the scope map
    from chipbench import breakdown, device
    cfg = ctx["config"]
    got = breakdown.metrics(scopes.reduce(_record(), TASKS), red["busy_s"], {
        "train": True, "steps": 3, "batch": 96,
        "layers": ctx["ref"].conv_layers(cfg, (224, 224)),
        "peak": device.peaks("TPU v5 lite")})
    for name in PASS_READERS:
        short = name.split("_roofline")[0].replace("conv_", "")
        short = "bwd" if short == "bwd_data" else short
        assert got[f"conv_roofline_{short}"] == pytest.approx(
            bench.metric_reader(name)(ctx))
    assert got["mosaic_pass_share"] == pytest.approx(100.0)
    assert got["bn_xla_share"] == pytest.approx(100 * 5 / 65)   # busy 65


@pytest.mark.parametrize("name", sorted(PASS_READERS))
def test_pass_roofline_reads_nothing_without_named_kernels(name):
    """A program whose kernels carry no pass name (the recorded trace's are
    named ``infer``) gives the reader nothing, and it returns None."""
    red = trace.reduce(trace.from_json(str(FIXTURE)))
    assert bench.metric_reader(name)(_train_ctx(red)) is None
    assert bench.metric_reader(name)(_train_ctx(None)) is None


def test_breakdown_of_a_tiny_offline_cell():
    """The diagnosis on the CPU: untraced phase counters, the program's
    spans in the traced window and none with them off."""
    import chipbench_tiny
    from chipbench import breakdown
    from repro import obs
    out = breakdown.run(chipbench_tiny.plan("offline"), 2**31 + 11, 1.0,
                        harness_spans=True, require_tpu=False,
                        compile_cache=False)
    phases = out["untraced"]["phases"]
    steps = phases["serve.step"]["count"]
    assert steps >= 1
    for name in obs.SPANS:
        assert phases[name]["count"] == steps, name
    assert out["untraced"]["summary"]["by_bucket"] == {4: steps}
    assert out["metrics"]["host_ms_per_batch"] > 0
    longest = out["traced"]["longest_step"]
    assert {"serve.take", "serve.stack", "engine.run",
            "serve.fetch"} <= set(longest["phases"])
    top = ("serve.take", "serve.stack", "serve.fetch", "serve.post")
    assert sum(longest["phases"].get(k, 0.0) for k in top) <= longest["s"]
    assert longest["phases"]["engine.run"] <= longest["phases"]["serve.fetch"]
    assert out["traced_harness_spans"]["longest_step"] is None
