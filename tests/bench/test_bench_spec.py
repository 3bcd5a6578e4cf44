"""BENCHMARK.json keeps to its contract, and a configuration, a traffic mix,
a cell or a per-layer metric is added by adding a file."""
import json
import re
import shutil

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTHS = ("hidden_size", "intermediate_size", "head_dim",
          "num_experts_per_tok")


@pytest.fixture(scope="module")
def bm():
    return spec.load_benchmark()


def test_top_level_keys_and_command(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["command"] == ["python3", "bench/run.py"]
    assert 1 <= bm["run_seconds"] <= 51
    for p in bm["paths"]:
        assert (spec.ROOT / p).is_dir()
    assert (spec.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_units_and_entries(bm):
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["source"].startswith("https://")
        assert (spec.ROOT / c["file"]).is_file()
        for k in c["reduced"]:
            assert NAME.match(k) and k not in WIDTHS
            assert not k.endswith(("_dim", "_rank", "_size")), k
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    metrics = bm["end_to_end"] + bm["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in bm["end_to_end"])


def test_every_cell_has_what_it_reports(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    used = set()
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"], benchmark=bm)
        used.add(w["config"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        if cell.traffic["kind"] == "open_loop":
            assert cell.params["rate_per_s"] > 0
        assert cell.params["logit_gap_mean_limit"] > 0
    assert used == {c["name"] for c in bm["configs"]}
    for m in bm["per_layer"]:
        assert (spec.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
        moved = e2e[m["moves"]]
        for w in m.get("workloads", [x["name"] for x in bm["workloads"]]):
            assert "workloads" not in moved or w in moved["workloads"]
    layers = {m["layer"] for m in bm["per_layer"]}
    assert layers == {"slot manager", "jitted step", "kernels and glue",
                      "device"}


def test_a_new_cell_is_found_by_adding_files(tmp_path, bm):
    root = tmp_path
    shutil.copytree(spec.BENCH_DIR, root / "bench")
    cfg = spec.load_json(spec.BENCH_DIR / "configs" / "granite-3-2b.json")
    cfg["name"] = "new-config"
    (root / "bench" / "configs" / "new-config.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "new-mix.json").write_text(json.dumps(
        {"kind": "backlog", "requests": 3, "sample_seed": 1,
         "prompt_tokens": {"dist": "uniform", "min": 4, "max": 8},
         "output_tokens": {"dist": "uniform", "min": 4, "max": 8}}))
    (root / "bench" / "cells" / "new-cell.json").write_text(
        json.dumps({"logit_gap_mean_limit": 1.0}))
    (root / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['x']\n")
    new = dict(bm)
    new["configs"] = bm["configs"] + [dict(
        bm["configs"][0], name="new-config",
        file="bench/configs/new-config.json")]
    new["workloads"] = bm["workloads"] + [{
        "name": "new-cell", "config": "new-config", "traffic": "new-mix",
        "chips": 1, "why": "added by files alone"}]
    new["per_layer"] = bm["per_layer"] + [{
        "name": "new_metric", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "slot manager",
        "moves": "tokens_per_s", "workloads": ["new-cell"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    cell = spec.load_cell("new-cell", root=root, bench_dir=root / "bench")
    assert cell.config["name"] == "new-config"
    assert cell.traffic["requests"] == 3
    assert cell.params == {"logit_gap_mean_limit": 1.0}
    assert "new_metric" in [m["name"] for m in cell.per_layer]
    read = spec.load_metric_reader("new_metric", bench_dir=root / "bench")
    assert read({"x": 4.0}) == 8.0
    # the existing cells do not see the new metric
    old = spec.load_cell("granite2b-chat", root=root,
                         bench_dir=root / "bench")
    assert "new_metric" not in [m["name"] for m in old.per_layer]
    with pytest.raises(KeyError):
        spec.load_cell("missing", root=root, bench_dir=root / "bench")
