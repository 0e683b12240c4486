"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

A cell names a configuration and a traffic mix; a metric names its reader.
Each piece is a file of its own under ``benchmarks/chip/``:

* ``configs/<config>.json``   -- the network recipe and how it is compiled
  (the path is the configuration's ``file`` in ``BENCHMARK.json``);
* ``traffic/<traffic>.json``  -- the parameters one general generator reads;
* ``metrics/<metric>.py``     -- a reader with ``read(ctx) -> float | None``.
  A metric split by the end-to-end metric it moves (``idle_share.sim``)
  is read by the file of its whole name where there is one, else by the
  file of the part before its first dot (``metrics/idle_share.py``), so
  one computation has one reader.

A configuration brings its network's code by naming two modules, each a
path under ``benchmarks/chip/``:

* ``"generator"`` (default ``chipbench/netgen.py``) -- ``generate(cfg)
  -> NetSpec`` and ``to_program(spec) -> SNNNetwork``;
* ``"reference"`` (default ``chipbench/reference.py``) -- ``simulate(spec,
  spikes, dtype=np.float32) -> {population: (T, B, n) uint8}``.

Adding a cell, a configuration or a metric adds files and entries; no
existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

#: ``benchmarks/chip`` -- the benchmark's own directory.
BENCH_DIR = Path(__file__).resolve().parents[1]
#: The checkout's root, where ``BENCHMARK.json`` lives.
ROOT = BENCH_DIR.parents[1]
#: A configuration's network code where its file names none.
DEFAULT_CODE = {"generator": "chipbench/netgen.py",
                "reference": "chipbench/reference.py"}


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics_e2e: List[Metric]
    metrics_layer: List[Metric]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(rel: str) -> ModuleType:
    """The module in file ``rel`` (a path under ``benchmarks/chip/``),
    loaded once per process.

    A file of a package (``chipbench/graph.py``) is imported by its dotted
    name, so it is one object however it is reached.  Any other file is
    loaded by its path and belongs to no package: it imports by absolute
    name (``from chipbench import graph``), never relatively.
    """
    path = (BENCH_DIR / rel).resolve()
    parts = path.relative_to(BENCH_DIR).with_suffix("").parts  # never out
    if not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    if (path.parent / "__init__.py").is_file():
        return importlib.import_module(".".join(parts))
    name = "chipbench_file_" + re.sub(r"\W", "_", "/".join(parts))
    if name not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(mod_spec)
        # a dataclass looks its module up by name while it is made
        sys.modules[name] = module
        try:
            mod_spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def load_reader(name: str) -> Callable:
    """The ``read`` function of ``metrics/<name>.py``, else of
    ``metrics/<name up to its first dot>.py``."""
    tried = [f"metrics/{n}.py"
             for n in dict.fromkeys((name, name.split(".", 1)[0]))]
    rel = next((r for r in tried if (BENCH_DIR / r).is_file()), None)
    if rel is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at "
                                f"{' or '.join(tried)} under {BENCH_DIR}")
    return load_module(rel).read


def network_code(config: dict, role: str) -> ModuleType:
    """The module configuration ``config`` names for ``role``
    (``"generator"`` or ``"reference"``), or the default."""
    return load_module(config.get(role, DEFAULT_CODE[role]))


def _metrics_for(entries, cell: str, e2e_names, end_to_end: bool):
    out = []
    for m in entries:
        if "workloads" in m:
            if cell not in m["workloads"]:
                continue
        elif not end_to_end and m["moves"] not in e2e_names:
            continue
        out.append(Metric(m["name"], m["unit"], load_reader(m["name"])))
    return out


def load_cell(name: str, bench: dict | None = None) -> Cell:
    """Everything the harness needs to run cell ``name`` of ``bench``
    (default: ``BENCHMARK.json``)."""
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    e2e = _metrics_for(bench["end_to_end"], name, (), True)
    layer = _metrics_for(bench["per_layer"], name, {m.name for m in e2e}, False)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, metrics_e2e=e2e, metrics_layer=layer)


def config_path(rel: str) -> Path:
    """A file named by a configuration, relative to ``configs/``."""
    return BENCH_DIR / "configs" / rel


def merged(base: Dict, overrides: Dict | None) -> Dict:
    """``base`` with top-level keys of ``overrides`` replaced."""
    return {**base, **(overrides or {})}
