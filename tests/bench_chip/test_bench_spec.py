"""``BENCHMARK.json`` against the benchmark's files: every entry resolves,
each name has its file, and a new cell needs only new files and a new
entry."""

from __future__ import annotations

import json
import re
import shutil

import pytest

import benchtiny
import harness

SPEC = json.loads((benchtiny.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][0] == "python3"
    for p in SPEC["command"][1:]:
        assert any(p.startswith(d + "/") for d in SPEC["paths"])
        assert (benchtiny.REPO / p).is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_resolves_to_its_files(cell):
    c = harness.resolve(benchtiny.REPO, cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    # a per-layer metric's end-to-end metric is reported in its cells
    assert {m["moves"] for m in c.per_layer} <= e2e


def test_configs_are_their_files():
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        cfg = json.loads((benchtiny.REPO / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"


def test_each_config_names_a_family_file():
    for c in SPEC["configs"]:
        cfg = json.loads((benchtiny.REPO / c["file"]).read_text())
        assert harness.family_file(cfg["family"]).is_file()


def test_names_units_and_bounds():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert "bound" not in m
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"


def test_a_new_cell_needs_only_new_files(tmp_path):
    """Copy the benchmark, add a config, a traffic mix, a limit with a
    number of its own and a metric reader as new files plus one entry
    each: the new cell resolves and the old ones still do, with no
    existing file edited."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(benchtiny.BENCH, bench,
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "qwen1.5-0.5b.json").read_text())
    (bench / "configs" / "other.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "chat.json").read_text())
    tr["arrivals"]["rate_per_s"] *= 1.5
    (bench / "traffic" / "chat_hot.json").write_text(json.dumps(tr))
    lim = json.loads((bench / "limits" / "qwen05_fast_chat.json").read_text())
    lim["limits"]["p99_logit_gap"] = 2.0
    (bench / "limits" / "other_hot.json").write_text(json.dumps(lim))
    (bench / "checks" / "p99_logit_gap.py").write_text(
        "def read(gaps):\n    return float(sorted(gaps)[-1])\n")
    (bench / "metrics" / "extra_ms.hot.py").write_text(
        "def read(run):\n    return None\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(dict(spec["configs"][0], name="other",
                                file="benchmarks/chip/configs/other.json"))
    spec["workloads"].append({"name": "other_hot", "config": "other",
                              "traffic": "chat_hot", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "extra_ms.hot", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "x", "moves": "setup_s",
                              "workloads": ["other_hot"]})
    # a kind's share of a quantity that has a reader needs no new reader
    spec["per_layer"].append({"name": "idle_pct.hot", "unit": "%",
                              "better": "lower", "source": "device_trace",
                              "layer": "x", "moves": "setup_s",
                              "workloads": ["other_hot"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    c = harness.resolve(tmp_path, "other_hot", bench)
    assert c.traffic["arrivals"]["rate_per_s"] == tr["arrivals"]["rate_per_s"]
    assert {"extra_ms.hot", "idle_pct.hot"} <= {m["name"]
                                                for m in c.per_layer}
    assert "p99_logit_gap" in c.limits["limits"]
    assert harness.metric_file("idle_pct.hot", bench).name == "idle_pct.py"
    assert harness.metric_file("extra_ms.hot", bench).name == "extra_ms.hot.py"
    for cell in CELLS:
        harness.resolve(tmp_path, cell, bench)
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_number_without_its_reader_is_an_error(tmp_path):
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(benchtiny.BENCH, bench,
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    lim = json.loads((bench / "limits" / "qwen05_fast_chat.json").read_text())
    lim["limits"]["unknown_gap"] = 1.0
    (bench / "limits" / "qwen05_fast_chat.json").write_text(json.dumps(lim))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(SPEC))
    with pytest.raises(harness.CellError, match="unknown_gap"):
        harness.resolve(tmp_path, "qwen05_fast_chat", bench)


def test_missing_file_is_an_error(tmp_path):
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "ghost", "config": "nowhere",
                              "traffic": "chat", "chips": 1, "why": "t"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(harness.CellError, match="missing"):
        harness.resolve(tmp_path, "ghost")
