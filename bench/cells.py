"""Find a cell's data by name: the workload entry of ``BENCHMARK.json``, its
configuration file, its traffic mix, its correctness limits and the readers
of its per-layer metrics.  Nothing about one cell is written in code: a new
cell, mix or metric is a new file and a new entry.

  bench/configs/<config>.json   sizes, cuts, deviations, deployment
  bench/traffic/<traffic>.json  loop, batch, prompt, gen
  bench/checks/<workload>.json  what the correctness check samples, limits
  bench/metrics/<metric>.py     one reader per per-layer metric
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Optional

BENCH_DIR = Path(__file__).resolve().parent
REPO_DIR = BENCH_DIR.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    check: dict           # bench/checks/<workload>.json
    end_to_end: list      # BENCHMARK.json end_to_end entries of this cell
    per_layer: list       # BENCHMARK.json per_layer entries of this cell


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = REPO_DIR) -> dict:
    return load_json(root / "BENCHMARK.json")


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = REPO_DIR) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with every file it names."""
    spec = benchmark(root)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    bench = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(bench / "configs" / f"{w['config']}.json"),
        traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        check=load_json(bench / "checks" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in spec["per_layer"] if _in_cell(m, name)])


def metric_reader(name: str, root: Path = REPO_DIR) -> ModuleType:
    """``bench/metrics/<name>.py``, which defines ``read(ctx)``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def model_config(config: dict, override: Optional[dict] = None):
    """The program's ``ModelConfig`` for a configuration file: the module
    named by ``arch`` with the file's ``program`` overrides applied
    (``model`` fields, then ``moe`` fields).  ``override`` is merged over
    the file's own, for tests at small widths."""
    from repro.configs import get_config

    prog = {"model": dict(config["program"].get("model", {})),
            "moe": dict(config["program"].get("moe", {}))}
    for part, fields in (override or {}).items():
        prog[part].update(fields)
    cfg = get_config(config["arch"])
    moe = dataclasses.replace(cfg.moe, **prog["moe"])
    return dataclasses.replace(cfg, moe=moe, **prog["model"])
