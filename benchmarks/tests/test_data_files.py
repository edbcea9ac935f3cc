"""BENCHMARK.json against the contract's limits and against the data files it
names; and the README's worked examples (a configuration, a cell, a per-layer
metric) loaded by the harness as new files, with no edit to one that exists."""

import json
import re
from pathlib import Path

import pytest

from benchmarks import layer_metrics, reference, run, traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"keys": {run.API_KEY: run.API_SECRET}}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"] and 1 <= BENCH["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in {"host_clock", "device_trace"}
    cells = {w["name"] for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == configs
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(cells) // 2)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert c["file"].startswith("benchmarks/") and (ROOT / c["file"]).exists()
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_each_per_layer_metric_has_its_file(metric):
    spec = json.loads((layer_metrics.HERE / f"{metric['name']}.json").read_text())
    for key in ("layer", "unit", "better", "moves"):
        assert spec[key] == metric[key], key
    if spec["source"]["kind"] == "reader":
        assert (layer_metrics.HERE / f"{metric['name']}.py").exists()
    # with nothing gathered, a source finds nothing and says nothing (never 0)
    empty = {"ticks": [], "before": {}, "after": {}, "launcher": {}, "trace": {},
             "clients": {}, "latency": {}, "on_chip": True, "plan": {"live_rooms": 1, "dims": [1, 1, 1, 1]}}
    assert layer_metrics.read(metric["name"], empty) == (None, metric["unit"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_cell_loads(cell):
    from livekit_server_tpu.config import load_config

    _, entry, config, workload = run.load_cell(cell["name"])
    assert entry == cell and workload["config"] == cell["config"] == config["name"]
    assert workload["traffic"] == cell["traffic"] and workload["why"] == cell["why"]
    declared = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert sorted(config["reduced"]) == sorted(declared["reduced"])
    assert config["source"] == declared["source"]
    cfg = load_config(yaml_text=json.dumps(config["server_config"] | KEYS))
    # the server's config says what the configuration's file says
    for section, values in config["server_config"].items():
        for key, value in values.items():
            assert getattr(getattr(cfg, section), key) == value, (section, key)
    assert cfg.rtc.require_encryption
    plan = traffic.make_plan(workload, seed=3)
    people = plan.participants
    assert people <= cfg.plane.subs_per_room
    assert len(plan.room_tracks(0)) <= cfg.plane.tracks_per_room
    assert plan.rooms <= cfg.plane.rooms
    lead = int(workload["lead_in_s"] * traffic.NS)
    assert reference.expected_deliveries(plan, lead, BENCH["run_seconds"] * traffic.NS) > 0
    # the busiest track's packets of one tick fit its staging slots twice over
    busiest = max(t["pps"] for t in workload["tracks"].values())
    assert busiest * cfg.plane.tick_ms / 1000 * 2 <= cfg.plane.pkts_per_track


def readme_examples():
    text = (ROOT / "benchmarks" / "README.md").read_text()
    blocks = [json.loads(b) for b in re.findall(r"```json\n(.*?)```", text, re.S)]
    assert len(blocks) == 3
    return blocks


def test_readme_examples_load_as_new_files(tmp_path, monkeypatch):
    """The worked examples as new files and new entries beside the real ones
    (a copy of the tree's lists with three entries added, no entry and no
    file of the real ones changed): the harness loads the new cell, and the
    new cell gets every per-layer metric that names no cells."""
    from livekit_server_tpu.config import load_config

    config, workload, metric = readme_examples()
    cfg = load_config(yaml_text=json.dumps(config["server_config"] | KEYS))
    assert cfg.plane.pager_enabled and cfg.plane.pager_pool_pages == 1024
    assert workload["config"] == config["name"]
    plan = traffic.make_plan(workload, seed=11)
    assert (plan.rooms, plan.participants, len(plan.tracks)) == (8, 4, 64)
    cell = f"{config['name']}.{workload['traffic']}"
    for real in ("configs", "workloads"):
        (tmp_path / "benchmarks" / real).mkdir(parents=True)
        for f in (ROOT / "benchmarks" / real).iterdir():
            (tmp_path / "benchmarks" / real / f.name).write_bytes(f.read_bytes())
    (tmp_path / "benchmarks" / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks" / "workloads" / f"{cell}.json").write_text(json.dumps(workload))
    grown = json.loads(json.dumps(BENCH))
    grown["configs"].append({"name": config["name"], "source": config["source"],
                             "file": f"benchmarks/configs/{config['name']}.json",
                             "reduced": sorted(config["reduced"]), "why": "the paged plane"})
    grown["workloads"].append({"name": cell, "config": config["name"], "traffic": "steady",
                               "chips": 1, "why": workload["why"]})
    grown["per_layer"].append({"name": "paged_kernel_ms_p50", "workloads": [cell]} | {
        k: metric[k] for k in ("unit", "better", "layer", "moves")} | {"source": "program_span"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(grown))
    monkeypatch.setattr(run, "ROOT", tmp_path)
    bench, entry, loaded, loaded_workload = run.load_cell(cell)
    assert entry["config"] == loaded["name"] and loaded_workload == workload
    assert bench["workloads"][:len(BENCH["workloads"])] == BENCH["workloads"]
    mine = [m["name"] for m in bench["per_layer"] if cell in m.get("workloads", [cell])]
    assert mine == [m["name"] for m in BENCH["per_layer"]] + ["paged_kernel_ms_p50"]
    (tmp_path / "paged_kernel_ms_p50.json").write_text(json.dumps(metric))
    ctx = {"ticks": [{"paged_kernel_ms": v} for v in (1.0, 2.0, 4.0)], "on_chip": True}
    assert layer_metrics.read("paged_kernel_ms_p50", ctx, tmp_path) == (2.0, "ms")
    assert layer_metrics.read("paged_kernel_ms_p50", {"ticks": [], "on_chip": True},
                              tmp_path) == (None, "ms")
