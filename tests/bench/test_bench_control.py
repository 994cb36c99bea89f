"""The correctness controls on the CPU at a tiny size: the reference with
fp8 projections, put in the program's place, goes through the cell's own
checks and comes out not correct; with int8 projections it reads wider
than the program on both numbers. (At the cell's own size on the chip,
``bench/control.py`` reads the program and the controls on many seeds, and
the limits lie between those readings: there int8 fails the mean gap and
fp8 fails both.)"""
import pytest

CELL = "serve.mistral-nemo-12b.decode-heavy"


def test_control_in_the_programs_place_is_not_correct(tiny_run):
    res = tiny_run(CELL, control="fp8")
    rec = res["_rec"]
    assert not res["correct"], (res["checks"], rec["readings"])
    judged = rec["readings"]["fp8"]
    assert res["checks"]["logit_err"]["value"] == judged["logit_err"]
    assert judged["tokens"] == rec["readings"]["program"]["tokens"]


@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_control_reads_wider_than_the_program(tiny_run, control):
    res = tiny_run(CELL, readings=(control,))
    got, program = res["_rec"]["readings"][control], res["_rec"]["readings"]["program"]
    assert res["correct"], res["checks"]
    assert got["logit_err"] > 2 * program["logit_err"]
