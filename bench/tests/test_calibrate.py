"""The readings that limits are set from, at tiny sizes on the CPU: every
kind is read, the control (the reference computed in bfloat16 in the
program's place) reads 1 on each share of the bfloat16 reference's gap, and
a sound program reads far below it."""
import json

import pytest


def read(capsys, argv):
    import calibrate

    assert calibrate.main(argv) == 0
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]


def test_training_readings(checkout, capsys):
    rows = read(capsys, ["--workload", "tiny-vgg-train", "--seeds", "5", str(2**31 + 3),
                         "--controls", "1", "--witness", "1", "--units", "1"])
    assert [(r["seed"], r["kind"]) for r in rows] == [
        (5, "program"), (5, "control"), (5, "half_batch"), (5, "reordered"),
        (2**31 + 3, "program")]
    by = {r["kind"]: r for r in rows[:4]}
    assert by["control"]["change_median_vs_bf16"] == pytest.approx(1.0)
    assert by["program"]["change_median_vs_bf16"] < 0.05
    assert by["half_batch"]["change_median_vs_bf16"] > 0.05


def test_serving_readings(checkout, capsys):
    rows = read(capsys, ["--workload", "tiny-covid-serve", "--seeds", "7",
                         "--controls", "1", "--units", "2"])
    assert [r["kind"] for r in rows] == ["program", "control"]
    program, control = rows
    for k in ("answer_vs_bf16", "answer_rms_vs_bf16"):
        assert control[k] == pytest.approx(1.0)
        assert program[k] < 0.05
    assert program["ledger"] == 0
