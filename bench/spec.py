"""What a run is made of, found by name: ``BENCHMARK.json`` at the root of
the checkout names the cells, and each cell's parts live in files of their
own under ``bench/``:

    bench/configs/<config>.json    model configuration, as it is run; its
                                   ``model_type`` names the architecture
    bench/archs/<model_type>.py    the architecture: ``Shape(c)`` (with
                                   ``params`` and ``cache_bytes(batch,
                                   rows)``), ``model_config(c)``,
                                   ``step_work(c, active_pos, chunks)``, the
                                   float32 reference ``forward(c, params,
                                   tokens, int8)``, and optionally
                                   ``leaf_std(names, shape)`` (see
                                   ``bench/model.py``)
    bench/traffic/<traffic>.json   traffic mix: parameters of the generator
    bench/cells/<workload>.json    what belongs to one cell alone (its fixed
                                   rate, its correctness limit); optional
    bench/metrics/<metric>.py      one per-layer metric: ``read(ctx)``

Adding a configuration, an architecture, a mix, a cell or a metric is
adding a file.

A cell's ``chips`` places it.  On one chip the engine serves the model
alone.  On several, ``bench/run.py`` builds a one-axis mesh (``model``) over
the first ``chips`` devices: the weights are drawn straight into their
places on it (``bench/model.draw_spec``), the engine serves them
tensor-parallel, the reference runs over the same chips on the weights
where they lie, and the trace is read for every chip (``bench/trace.py``).
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict                 # bench/configs/<config>.json
    traffic: dict                # bench/traffic/<traffic>.json
    params: dict                 # bench/cells/<name>.json, or {}
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)    # metric entries


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, bench_dir: Path = BENCH_DIR,
              benchmark: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with every part it names."""
    spec = benchmark if benchmark is not None else load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    cell_file = bench_dir / "cells" / f"{name}.json"
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=load_json(root / cfg_entry["file"]),
        traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        params=load_json(cell_file) if cell_file.exists() else {},
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def load_metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
