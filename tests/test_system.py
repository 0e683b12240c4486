"""End-to-end behaviour tests: the full paper pipeline + the launchers."""
import numpy as np
import pytest

from repro.core import (
    SwitchingCompiler,
    feedforward_network,
    generate_dataset,
    train_switch_classifier,
)
from repro.core.layer import LIFParams
from repro.core.runtime import run_network, run_reference


@pytest.fixture(scope="module")
def pipeline():
    """dataset -> classifier -> switching compiler (the whole paper)."""
    ds = generate_dataset(
        source_grid=(50, 200, 400),
        target_grid=(100, 300),
        density_grid=(0.1, 0.4, 0.8),
        delay_grid=(1, 4, 8),
        seed=11,
    )
    clf, acc = train_switch_classifier(ds, seed=0)
    return ds, clf, acc


def test_classifier_accuracy_reasonable(pipeline):
    _, _, acc = pipeline
    assert acc >= 0.8  # paper: 91.69% on their compiler's dataset


def test_end_to_end_compile_and_run(pipeline):
    """Compile a network with the prejudging classifier and execute it;
    spikes must match the dense oracle layer-by-layer, and the switched
    mapping must not exceed either pure paradigm's PE count."""
    _, clf, _ = pipeline
    lif = LIFParams(alpha=0.5, v_th=64.0)
    net = feedforward_network([80, 60, 40], density=0.5, delay_range=2, seed=5)
    for l in net.layers:
        l.lif = lif

    switched = SwitchingCompiler("classifier", clf).compile_network(net)
    serial = SwitchingCompiler("serial").compile_network(net)
    parallel = SwitchingCompiler("parallel").compile_network(net)
    assert switched.total_pes <= max(serial.total_pes, parallel.total_pes)

    rng = np.random.default_rng(0)
    spikes = (rng.random((12, 2, 80)) < 0.3).astype(np.float32)
    outs = run_network(net, switched, spikes)
    x = spikes
    for layer, z in zip(net.layers, outs):
        z_ref = run_reference(layer, x, lif)
        np.testing.assert_array_equal(z, z_ref)
        x = z_ref


def test_compile_work_halves_with_prejudging(pipeline):
    """C4: the switching system does half the compilations of 'ideal'."""
    _, clf, _ = pipeline
    net = feedforward_network([300, 200, 100], density=0.4, delay_range=4,
                              seed=8)
    sw = SwitchingCompiler("classifier", clf).compile_network(net)
    ideal = SwitchingCompiler("ideal").compile_network(net)
    assert sw.total_compilations * 2 == ideal.total_compilations
    assert sw.host_bytes_peak < ideal.host_bytes_peak


class TestEntryPoints:
    """What every entry point does before its first jit, and the chip
    smoke's refusal to run anywhere but on a TPU."""

    def test_compile_cache_defaults_to_fixed_repo_dir(self, monkeypatch):
        import jax

        from repro.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        was = jax.config.jax_compilation_cache_dir
        try:
            assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
        assert DEFAULT_CACHE_DIR.name == ".jax_cache"
        assert (DEFAULT_CACHE_DIR.parent / "src" / "repro").is_dir()

    def test_compile_cache_written_only_where_env_says(self, tmp_path):
        """With ``JAX_COMPILATION_CACHE_DIR`` set, a compile lands there
        and the helper sets no directory of its own."""
        import os
        import subprocess
        import sys
        from pathlib import Path

        src = Path(__file__).resolve().parents[1] / "src"
        probe = (
            "import jax, jax.numpy as jnp\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_ready()\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src),
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        out = subprocess.run([sys.executable, "-c", probe], env=env,
                             capture_output=True, text=True, timeout=120,
                             check=True).stdout.split()
        assert out == [str(tmp_path), str(tmp_path)]
        assert any(p.name.startswith("jit__lambda") for p in tmp_path.iterdir())

    def test_chip_smoke_refuses_without_tpu(self, capsys):
        import importlib.util
        from pathlib import Path

        import jax

        assert jax.devices()[0].platform != "tpu"
        path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
        spec = importlib.util.spec_from_file_location("chip_smoke", path)
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        assert smoke.main() != 0
        assert '"ok"' not in capsys.readouterr().out
