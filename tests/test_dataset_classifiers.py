"""Dataset generation + the 12-classifier zoo."""
import numpy as np
import pytest

from repro.core import (
    LABEL_PARALLEL,
    LABEL_SERIAL,
    generate_dataset,
    load_or_generate,
)
from repro.core import dataset as dataset_mod
from repro.core.dataset import dataset_cache_path
from repro.core.hw import SpiNNaker2Config
from repro.core.classifiers import zoo, ZOO_NAMES


@pytest.fixture(scope="module")
def mini_dataset():
    """A 384-layer sub-grid (fast); same generator as the paper's 16k."""
    return generate_dataset(
        source_grid=(50, 200, 400),
        target_grid=(100, 300),
        density_grid=(0.1, 0.3, 0.6, 0.9),
        delay_grid=(1, 2, 4, 6, 8, 10, 12, 16),
        seed=7,
    )


def test_dataset_shape_and_labels(mini_dataset):
    ds = mini_dataset
    assert len(ds) == 3 * 2 * 4 * 8
    assert ds.features.shape == (len(ds), 4)
    # label = argmin PEs with tie -> serial
    want = np.where(ds.parallel_pes < ds.serial_pes, LABEL_PARALLEL, LABEL_SERIAL)
    np.testing.assert_array_equal(ds.labels, want)
    assert 0.05 < ds.labels.mean() < 0.8  # both classes present


def test_dataset_deterministic():
    kw = dict(source_grid=(100,), target_grid=(100,),
              density_grid=(0.5,), delay_grid=(1, 4), seed=3)
    a, b = generate_dataset(**kw), generate_dataset(**kw)
    np.testing.assert_array_equal(a.serial_pes, b.serial_pes)
    np.testing.assert_array_equal(a.parallel_pes, b.parallel_pes)


TINY = dict(source_grid=(100,), target_grid=(100,), density_grid=(0.5,),
            delay_grid=(1, 4), seed=3)


@pytest.mark.parametrize("change", [
    dict(seed=4), dict(source_grid=(100, 200)), dict(delay_grid=(1, 8)),
    dict(density_grid=(0.25,)), dict(hw=SpiNNaker2Config(dtcm_bytes=64 * 1024)),
])
def test_cache_path_keys_every_generator_input(change):
    """A file made with other grids, seed or hardware config is never read
    in place of this one: its name differs."""
    base = dataset_cache_path(**TINY)
    assert dataset_cache_path(**{**TINY, **change}) != base
    assert dataset_cache_path(**TINY) == base          # deterministic
    assert dataset_cache_path(**{**TINY, "source_grid": [100]}) == base


def test_load_or_generate_caches_under_hashed_name(tmp_path, monkeypatch):
    monkeypatch.setattr(dataset_mod, "_DATA_DIR", str(tmp_path))
    stale = tmp_path / "paradigm_dataset.npz"            # old fixed name
    stale.write_bytes(b"not a dataset")
    ds = load_or_generate(progress=False, **TINY)
    path = dataset_cache_path(**TINY)
    assert path.startswith(str(tmp_path)) and path != str(stale)
    again = load_or_generate(progress=False, **TINY)
    np.testing.assert_array_equal(ds.serial_pes, again.serial_pes)
    np.testing.assert_array_equal(ds.labels, again.labels)


def test_paper_trends(mini_dataset):
    """C1: parallel improves with density; degrades with delay range."""
    ds = mini_dataset
    dens = ds.features[:, 2]
    lo = ds.labels[dens <= 0.3].mean()
    hi = ds.labels[dens >= 0.6].mean()
    assert hi > lo
    delay = ds.features[:, 3]
    early = ds.labels[delay <= 4].mean()
    late = ds.labels[delay >= 12].mean()
    assert early >= late


def test_split_disjoint(mini_dataset):
    (Xtr, ytr), (Xte, yte) = mini_dataset.split(0.25, seed=0)
    assert len(Xte) == int(0.25 * len(mini_dataset))
    assert len(Xtr) + len(Xte) == len(mini_dataset)


class TestClassifierZoo:
    def test_zoo_has_12(self):
        assert len(ZOO_NAMES) == 12
        assert "adaboost" in ZOO_NAMES

    @pytest.mark.parametrize("name", ZOO_NAMES)
    def test_separable_problem(self, name):
        """Every classifier must solve an easy axis-aligned problem."""
        rng = np.random.default_rng(0)
        X = rng.normal(size=(600, 4))
        y = (X[:, 1] > 0.1).astype(np.int64)
        clf = zoo(seed=0)[name]()
        clf.fit(X[:500], y[:500])
        assert clf.score(X[500:], y[500:]) >= 0.9, name

    def test_adaboost_beats_majority_on_paradigm_data(self, mini_dataset):
        from repro.core import train_switch_classifier
        clf, acc = train_switch_classifier(mini_dataset, seed=0)
        majority = max(
            mini_dataset.labels.mean(), 1 - mini_dataset.labels.mean()
        )
        assert acc > majority
        assert acc > 0.8
