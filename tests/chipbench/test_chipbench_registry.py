"""BENCHMARK.json against the files it names, and the rule that a new
configuration, traffic mix or per-layer metric is found by its name alone."""
from __future__ import annotations

import json
import pathlib
import re
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import bench  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
B = bench.load_benchmark()
CELLS = [w["name"] for w in B["workloads"]]


def test_top_level_keys_and_paths():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"][0] == "python3"
    for word in B["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in B["paths"])
    for p in B["paths"]:
        assert (ROOT / p).is_dir() and re.fullmatch(r"[A-Za-z0-9_./\-]+", p)
    assert 1 <= B["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_entry_keys():
    seen = set()
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert any(c["file"].startswith(p + "/") for p in B["paths"])
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for group in (B["configs"], B["workloads"], B["end_to_end"],
                  B["per_layer"]):
        for e in group:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(B["workloads"]) // 2)
    assert len(json.dumps(B)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    plan = bench.resolve(cell)
    names = [m["name"] for m in plan["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert plan["per_layer"], "every cell reports a per-layer metric"
    for m in plan["per_layer"]:
        assert callable(bench.metric_reader(m["name"]))
        assert m["moves"] in names
    cfg = plan["config"]
    for part in ("ref", "sut"):
        assert bench.family(cfg, part) is not None
    assert set(cfg["limits"]) >= {"serve" if plan["mix"]["kind"] != "train"
                                  else "train"}


def test_every_config_and_metric_is_used():
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
    for m in B["end_to_end"] + B["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in B["per_layer"]:
        e2e = {e["name"]: e for e in B["end_to_end"]}[m["moves"]]
        assert set(m["workloads"]) <= set(e2e.get("workloads", CELLS))


def test_new_files_are_found_by_name(tmp_path):
    """A new configuration, mix and metric, added as files beside the
    committed ones, resolve with no existing file edited."""
    here = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    cfg = bench.load_config("resnet50", here)
    cfg["name"] = "resnet26"
    cfg["stages"] = [2, 2, 2, 2]
    (here / "configs" / "resnet26.json").write_text(json.dumps(cfg))
    (here / "traffic" / "server-slow.json").write_text(json.dumps(
        {"kind": "server", "rate_per_s": 5.0, "max_bucket": 8,
         "pool_images": 4, "sample": 4}))
    (here / "metrics" / "lanes.server.py").write_text(
        "def read(ctx):\n    return ctx['summary'].get('pad_share')\n")
    b = json.loads(json.dumps(B))
    b["configs"].append({"name": "resnet26", "source": "x", "file": "f",
                         "reduced": ["stages"], "why": "y"})
    b["workloads"].append({"name": "resnet26.server-slow",
                           "config": "resnet26", "traffic": "server-slow",
                           "chips": 1, "why": "y"})
    b["end_to_end"].append({"name": "serve_p99_ms", "unit": "ms",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["resnet26.server-slow"]})
    b["per_layer"].append({"name": "lanes.server", "unit": "%",
                           "better": "lower", "source": "program_counter",
                           "layer": "front end", "moves": "serve_p99_ms",
                           "workloads": ["resnet26.server-slow"]})
    plan = bench.resolve("resnet26.server-slow", b, here)
    assert plan["config"]["stages"] == [2, 2, 2, 2]
    assert plan["mix"]["rate_per_s"] == 5.0
    assert [m["name"] for m in plan["per_layer"]] == ["lanes.server"]
    read = bench.metric_reader("lanes.server", here)
    assert read({"summary": {"pad_share": 12.5}}) == 12.5
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_a_mix_of_unknown_kind_is_refused(tmp_path):
    here = tmp_path / "cb"
    (here / "traffic").mkdir(parents=True)
    (here / "traffic" / "odd.json").write_text('{"kind": "closed"}')
    with pytest.raises(ValueError):
        bench.load_mix("odd", here)
