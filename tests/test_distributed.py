"""Sharding rules and the fault-tolerance primitives serving wires in."""
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.runtime.executor import LayerMeta, _param_axes
from repro.distributed import HeartbeatRegistry, StragglerDetector
from repro.distributed.sharding import snn_rules, spec_for_shape


class FakeMesh:
    shape = {"data": 2, "model": 4}


def _meta(paradigm):
    return LayerMeta(paradigm=paradigm, n_source=8, n_target=8,
                     delay_range=1, alpha=0.5, v_th=1.0, n_rows=8)


class TestShardingRules:
    def test_divisible_dims_shard(self):
        spec = spec_for_shape(("batch", "neurons"), snn_rules(), (8, 20),
                              FakeMesh())
        assert spec == P("data", "model")

    def test_non_divisible_dims_degrade(self):
        # batch 3 over data=2 and 6 neurons over model=4 -> replicated
        spec = spec_for_shape(("batch", "neurons"), snn_rules(), (3, 6),
                              FakeMesh())
        assert spec == P(None, None)

    def test_batch_prefix_fit(self):
        # neurons and rows both map to "model": only the first gets it
        spec = spec_for_shape(("neurons", "rows"), snn_rules(), (8, 8),
                              FakeMesh())
        assert spec == P("model", None)


# Every operand of ``_param_axes`` at a width the mesh divides and at one
# it does not: (paradigm, form, operand index, shape, expected spec).
_OPERAND_CASES = [
    ("serial", "event", 0, (64,), P("model")),         # row_weight
    ("serial", "event", 1, (66,), P(None)),            # row_delay
    ("serial", "event", 2, (12,), P("model")),         # row_src
    ("serial", "event", 3, (2,), P(None)),             # row_tgt
    ("serial", "dense", 0, (2, 2048, 20), P(None, None, "model")),
    ("serial", "dense", 0, (2, 20, 6), P(None, None, None)),
    ("serial", "sparse", 0, (40, 7), P("model", None)),  # ell_val
    ("serial", "sparse", 1, (42, 7), P(None, None)),     # ell_idx
    ("parallel", "-", 0, (20, 4096), P("model", None)),  # wdm_stack
    ("parallel", "-", 1, (4096,), P(None)),              # col_source
    ("parallel", "-", 2, (4096,), P(None)),              # col_delay
]


@pytest.mark.parametrize("paradigm,form,k,shape,want", _OPERAND_CASES)
def test_operand_axes_place(paradigm, form, k, shape, want):
    axes = _param_axes(_meta(paradigm), form)[k]
    assert len(axes) == len(shape)
    assert spec_for_shape(axes, snn_rules(), shape, FakeMesh()) == want


@pytest.mark.parametrize("axes,shape,want", [
    (("steps", "batch", None), (256, 8, 2048), P(None, "data", None)),
    (("steps", "batch", None), (256, 3, 2048), P(None, None, None)),
    (("batch",), (8,), P("data")),
    (("batch",), (1,), P(None)),
])
def test_input_axes_place(axes, shape, want):
    """The request batch's placement (``_place_inputs``): the batch on
    the data axis where it divides, the steps never sharded."""
    assert spec_for_shape(axes, snn_rules(), shape, FakeMesh()) == want


class TestFaultTolerance:
    def test_heartbeats(self):
        reg = HeartbeatRegistry(timeout_s=10)
        reg.beat(0, now=0.0)
        reg.beat(1, now=0.0)
        reg.beat(0, now=9.0)
        assert reg.dead_hosts(now=15.0) == [1]

    def test_stragglers(self):
        det = StragglerDetector(threshold=1.5)
        for h in range(8):
            for _ in range(5):
                det.record(h, 1.0 if h != 3 else 2.5)
        assert det.stragglers() == [3]
