"""Find a cell's configuration, traffic mix and metrics by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own:

* ``BENCHMARK.json`` (checkout root) names the cell, its configuration
  and its traffic, and lists the metrics;
* the configuration is the ``file`` its entry names (``bench/configs/``);
* the traffic mix is ``bench/traffic/<traffic>.json``;
* a per-layer metric is ``bench/metrics/<name>.py`` with ``read(ctx)``;
* the configuration's ``algo`` is ``bench/algos/<algo>.py`` (``-`` as
  ``_``): the reference's policy step, the check's first-step leaves and
  the step's operation count.

A later cell needs new files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
ALGOS = BENCH / "algos"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # entries of BENCHMARK.json that this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {spec_path.name}; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _reports(m, name)])


def metric_reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def algorithm(name: str):
    """The module ``bench/algos/<name>.py``, with ``-`` in ``name`` as
    ``_``. It defines ``init(pol, c)``, the reference's policy-learner
    state; ``step(state, model, key, c, mm, fault)`` -> ``(state,
    imagined return)``, the reference's policy step; ``first_step(state0,
    state1)``, the leaves the check compares for the first step, of the
    program's state or the reference's; and ``step_ops(c)``, the
    operations of one policy improvement. Loaded once a process, so its
    jitted functions compile once."""
    known = sorted(p.stem.replace("_", "-") for p in ALGOS.glob("*.py"))
    if name not in known:
        raise SystemExit(f"no algorithm {name!r} in {ALGOS}; known: {known}")
    path = ALGOS / f"{name.replace('-', '_')}.py"
    key = f"bench_algo_{path.stem}"
    mod = sys.modules.get(key)
    if mod is None or mod.__file__ != str(path):
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return mod
