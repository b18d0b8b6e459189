"""The check must fail a broken timed path, and the control.

Each test drives a whole run of the harness on the CPU (only the look
for a chip is skipped) with the decision program broken underneath,
where its outputs are produced (`bench/faults.py`), and sees `correct`
come out false, on the number that is there to catch the fault. The
exchange between chips cannot be left out: every cell runs on one chip.
The control, the reference with its KNN distance at three-pass bfloat16
put in the program's place, must fail the check too; and, on a session
mix with the prefix-affinity term on, so must the reference with that
term dropped.
"""
from bench import check, faults


def _fails_on(res, *numbers):
    assert not res["correct"]
    for n in numbers:
        assert res["checks"][n]["value"] > res["checks"][n]["limit"], n


def test_sound_run_is_correct(drive):
    res, _ = drive()
    assert res["correct"], res["checks"]


def test_state_left_unchanged_fails(drive):
    res, _ = drive(fault=faults.state_unchanged)
    _fails_on(res, "slot_miss", "work_gap")


def test_pending_work_frozen_fails(drive):
    res, _ = drive(fault=faults.pending_frozen)
    _fails_on(res, "work_gap")
    assert res["checks"]["slot_miss"]["value"] == 0.0


def test_half_the_batch_left_out_fails(drive):
    res, _ = drive(fault=faults.half_batch)
    _fails_on(res, "decide_p99")


def test_an_altered_answer_fails(drive):
    res, _ = drive(fault=faults.answer_altered)
    _fails_on(res, "decide_p99")


def test_the_control_fails(drive, small):
    cell = small[0]
    res, _ = drive(control="knn_high", seconds=3.0)
    verdict = check.judge(res["control"], check.load_limits(cell.name))
    assert not all(v["ok"] for v in verdict.values()), verdict


def test_the_affinity_control_fails(drive, sessions):
    """A program that dropped the affinity term could not pass: the
    reference without it fails `decide_p99` where the program passes
    and its picks found cached prefixes."""
    res, _ = drive(control="affinity_off", seconds=3.0, on=sessions)
    assert res["correct"], res["checks"]
    assert res["program"]["hit_share"] > 0.05, res["program"]
    verdict = check.judge(res["control"], check.load_limits(sessions[0].name))
    assert not verdict["decide_p99"]["ok"], verdict
