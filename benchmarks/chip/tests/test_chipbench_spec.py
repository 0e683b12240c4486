"""The harness finds each piece by the name ``BENCHMARK.json`` gives it,
and the file keeps to the benchmark's contract."""
import copy
import re
from pathlib import Path

import pytest

from chipbench import spec

BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_by_name(cell):
    c = spec.load_cell(cell, BENCH)
    e2e = {m.name for m in c.metrics_e2e}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.metrics_layer
    assert {"steps", "batch", "temporal", "trace_seconds"} <= set(c.traffic)
    assert all(callable(m.read) for m in c.metrics_e2e + c.metrics_layer)


def test_a_cell_added_as_data_needs_no_code():
    bench = copy.deepcopy(BENCH)
    bench["workloads"].append({"name": "gesture.extra", "config": "gesture",
                               "traffic": "scan_t256", "chips": 1, "why": "x"})
    c = spec.load_cell("gesture.extra", bench)
    assert c.config["name"] == "gesture"
    assert [m.name for m in c.metrics_e2e] == ["setup_s"]
    with pytest.raises(KeyError):
        spec.load_cell("no.such_cell", bench)


def test_unknown_metric_has_no_reader():
    with pytest.raises(FileNotFoundError):
        spec.load_reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        spec.load_reader("no_such_metric.sim")


@pytest.mark.parametrize("name,file", [
    ("idle_share.sim", "idle_share.py"),
    ("idle_share.serve", "idle_share.py"),
    ("compile_s.xla", "compile_s.xla.py"),
    ("lif_update_roofline", "lif_update_roofline.py"),
])
def test_a_split_metric_is_read_by_its_base_names_reader(name, file):
    """A metric split by what it moves shares the one reader of its
    computation, unless a file of its whole name exists."""
    read = spec.load_reader(name)
    assert read.__code__.co_filename == str(spec.BENCH_DIR / "metrics" / file)


def test_contract_shapes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"]: c for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(configs)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (spec.ROOT / c["file"]).is_file()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] == 1
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            reported = e2e[m["moves"]].get("workloads", CELLS)
            assert cell in reported, (m["name"], cell)


def test_stored_classifier_dataset_is_the_generators():
    """The committed training set is named by the hash of the generator
    inputs that made it, as the program names its cache file."""
    from repro.core import dataset

    cfg = spec.load_json(spec.BENCH_DIR / "configs" / "gesture.json")
    stored = spec.config_path(cfg["compile"]["dataset"])
    assert stored.is_file()
    want = dataset.dataset_cache_path(
        source_grid=dataset.EXT_SOURCE_GRID, target_grid=dataset.EXT_TARGET_GRID,
        density_grid=dataset.EXT_DENSITY_GRID, delay_grid=dataset.EXT_DELAY_GRID)
    assert stored.name == Path(want).name
    assert len(dataset.ParadigmDataset.load(str(stored))) > 10_000
