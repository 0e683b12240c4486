"""``gesture.scan_t256`` driven whole on the CPU at 32 steps a launch:
sound, it is correct; with each fault planted, it is not."""
import pytest

import faultkit


@pytest.mark.parametrize("fault", [faultkit.SOUND, "control", "state_unchanged",
                                   "half_batch", "altered_answer"])
def test_scan_t256(fault, monkeypatch):
    r = faultkit.run("gesture.scan_t256", fault, monkeypatch,
                     traffic={"steps": 32})
    assert r["correct"] is (fault == faultkit.SOUND), r["checks"]
    assert "temporal_residual" not in r["checks"]
