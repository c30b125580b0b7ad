"""A whole run of mnist-fedavg (cut to a CPU size) comes out correct,
and each fault the cell can have, planted in the program, turns
``correct`` false under the committed limits."""
import pytest

from chipbench.tests import faults
from chipbench.tests.tiny import run_tiny

WORKLOAD = "mnist-fedavg"


def test_sound_run_is_correct():
    out = run_tiny(WORKLOAD)
    assert out.line["correct"] is True, out.line["checks"]
    assert out.info["window_compile_events"] == 0


@pytest.mark.parametrize("plant", [faults.state_unchanged, faults.half_batch,
                                   faults.altered_alpha],
                         ids=lambda f: f.__name__)
def test_fault_is_caught(monkeypatch, plant):
    plant(monkeypatch)
    out = run_tiny(WORKLOAD)
    assert out.line["correct"] is False, out.line["checks"]
