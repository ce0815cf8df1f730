"""A sound run is correct; each fault planted under the timed path is not.

The cells run on the CPU at tiny sizes, past the harness's look for a
chip, through the same data plane, consumers and checks as on the chip.
"""
import pytest

from chip_bench_cells import hymba_cell, imagenet_cell, run
from chipbench import faults  # noqa: E402


def test_imagenet_sound_run_is_correct():
    res = run(imagenet_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"samples_per_s", "step_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch"])
def test_imagenet_fault_is_caught(fault):
    res = run(imagenet_cell(), fault=faults.FAULTS[fault])
    assert not res["correct"]
    assert res["checks"]["samples_mismatched"]["value"] > 0


def test_hymba_sound_run_is_correct():
    res = run(hymba_cell())
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"samples_per_s", "setup_s"}


@pytest.mark.parametrize("fault,number", [
    ("answer_altered", "tokens_mismatched"),
    ("half_batch", "tokens_mismatched"),
    ("state_unchanged", "change_gap"),
])
def test_hymba_fault_is_caught(fault, number):
    res = run(hymba_cell(), fault=faults.FAULTS[fault])
    assert not res["correct"]
    c = res["checks"][number]
    assert c["value"] > c["limit"]


def test_hymba_half_batch_in_the_step_is_caught():
    """Rows delivered whole, the step's mean over half of them: only the
    comparison with the reference can see it."""
    res = run(hymba_cell(), fault=faults.FAULTS["half_batch_step"])
    assert not res["correct"]
    assert res["checks"]["tokens_mismatched"]["value"] == 0
    assert any(res["checks"][n]["value"] > res["checks"][n]["limit"]
               for n in ("loss_gap", "grad_gap", "change_gap"))


def test_hymba_control_reads_well_above_a_sound_run():
    """The reference at float8 products, in the program's place, departs
    from the float32 reference by several times what the bf16 program
    does, on at least one of the cell's numbers (at this tiny size; the
    cell's limits come from the same readings at its own size)."""
    import control
    cell = hymba_cell()
    sound = run(cell)["checks"]
    ctl = control.training_control(cell, 2 ** 31 + 99)["checks"]
    assert any(ctl[n]["value"] > 3 * sound[n]["value"]
               for n in ("loss_gap", "grad_gap", "change_gap")), (ctl, sound)
