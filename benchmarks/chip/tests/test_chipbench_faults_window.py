"""``gesture.window_t256`` driven whole on the CPU: sound, it is correct
with a zero temporal residual; with each fault planted, it is not."""
import pytest

import faultkit


@pytest.mark.parametrize("fault", [faultkit.SOUND, "control", "state_unchanged",
                                   "half_batch", "altered_answer"])
def test_window_t256(fault, monkeypatch):
    r = faultkit.run("gesture.window_t256", fault, monkeypatch)
    assert r["correct"] is (fault == faultkit.SOUND), r["checks"]
    assert r["checks"]["temporal_residual"]["value"] == 0
