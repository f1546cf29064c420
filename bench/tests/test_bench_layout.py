"""The benchmark is driven by data: BENCHMARK.json names every cell,
configuration, mix, limits file and per-layer reader, and a file dropped
in is found by its name without a change to any code."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from bench_fixtures import REPO, tiny_root  # noqa: F401
import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bm():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_benchmark_json_keeps_to_its_contract():
    bm = _bm()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"] and bm["command"][1] == "bench/run.py"
    assert 1 <= bm["run_seconds"] <= 51
    configs = {c["name"]: c for c in bm["configs"]}
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cells = [w["name"] for w in bm["workloads"]]
    assert len(set(cells)) == len(cells)
    pairs = {(w["config"], w["traffic"]) for w in bm["workloads"]}
    assert len(pairs) == len(cells)
    for w in bm["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (REPO / "bench/traffic" / f"{w['traffic']}.json").is_file()
        assert (REPO / "bench/limits" / f"{w['name']}.json").is_file()
        cell = harness.load_cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
    for c in bm["configs"]:
        assert (REPO / c["file"]).is_file() and NAME.match(c["name"])
        cfg = json.loads((REPO / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert c["name"] in {w["config"] for w in bm["workloads"]}
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bm["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cells)
        for w in m["workloads"]:
            assert "workloads" not in e2e[m["moves"]] \
                or w in e2e[m["moves"]]["workloads"]
        assert (REPO / "bench/metrics" / f"{m['name']}.py").is_file()
    layers = {}
    for m in bm["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_mix_files_hold_traffic_alone():
    """The placement history and the check's sample sizes are the
    harness's, the same for every cell, and no mix file sets them."""
    traffic_keys = {"arrival", "rate", "drain_s", "zipf_alpha",
                    "prompt_len", "batch", "trace_seconds", "check_batches"}
    for f in (REPO / "bench/traffic").glob("*.json"):
        assert set(json.loads(f.read_text())) <= traffic_keys, f.name


def test_program_and_published_sizes_agree():
    for c in _bm()["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        arch = cfg["program"]["arch"]
        assert (arch["n_layers"], arch["d_model"], arch["n_heads"],
                arch["n_kv_heads"], arch["d_ff"], arch["vocab"]) == (
            cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["vocab_size"])
        assert arch["param_dtype"] == cfg["torch_dtype"]


@pytest.mark.parametrize("kind", ["config", "mix", "metric"])
def test_a_file_dropped_in_is_found_by_name(tiny_root: Path, kind):
    bm = json.loads((tiny_root / "BENCHMARK.json").read_text())
    if kind == "metric":
        (tiny_root / "bench/metrics/probe_share.py").write_text(
            "def read(ctx):\n    return 42.0\n")
        bm["per_layer"].append({
            "name": "probe_share", "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "device",
            "moves": "req_per_s", "workloads": ["tiny.tiny-sat"]})
        (tiny_root / "BENCHMARK.json").write_text(json.dumps(bm))
        cell = harness.load_cell("tiny.tiny-sat", tiny_root)
        assert "probe_share" in [m["name"] for m in cell.per_layer]
        assert harness.reader("probe_share", tiny_root)(None) == 42.0
        return
    if kind == "config":
        cfg = json.loads((tiny_root / "bench/configs/tiny.json").read_text())
        cfg["cache"]["levels"] = [8, 16, 32]
        (tiny_root / "bench/configs/tiny2.json").write_text(json.dumps(cfg))
        bm["configs"].append({"name": "tiny2", "source": cfg["source"],
                              "file": "bench/configs/tiny2.json",
                              "reduced": [], "why": "test"})
        w = {"name": "tiny2.sat", "config": "tiny2", "traffic": "tiny-sat"}
    else:
        mix = json.loads((tiny_root / "bench/traffic/tiny-sat.json")
                         .read_text())
        mix["batch"] = 32
        (tiny_root / "bench/traffic/tiny-sat32.json").write_text(
            json.dumps(mix))
        w = {"name": "tiny.sat32", "config": "tiny", "traffic": "tiny-sat32"}
    bm["workloads"].append(dict(w, chips=1, why="test"))
    (tiny_root / f"bench/limits/{w['name']}.json").write_text(
        json.dumps({"hits_diff": 0}))
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = harness.load_cell(w["name"], tiny_root)
    if kind == "config":
        assert cell.cfg["cache"]["levels"] == [8, 16, 32]
    else:
        assert cell.mix["batch"] == 32
    assert cell.limits == {"hits_diff": 0}
    assert {m["name"] for m in cell.end_to_end} == {"req_per_s", "setup_s"}
