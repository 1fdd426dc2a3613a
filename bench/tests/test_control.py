"""The control, at a size a test holds: the reference computed in the next
precision down (bfloat16 registers, three-pass matmuls) in the program's
place must come out not correct, and the program must come out correct."""

import time

import pytest

from bench import check, pipelines, run
from bench.tests.helpers import tiny_cell

CELLS = [("tiny-flow-ddos-mlp", "tiny-churn"),
         ("tiny-mitigate-mat", "tiny-flood16")]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_control_fails_program_passes(config, traffic):
    cell = tiny_cell(config, traffic)
    built = pipelines.Built(cell.config)
    devices = run.chips(1, require_accelerator=False)
    served = run.serve(cell, built, 2**32 + 77, 0.5, devices,
                       time.perf_counter())
    ok, checks = run.judge(served, built)
    assert ok, checks
    limits = cell.config["check"]["limits"]
    ctl = check.control_numbers(served.sample, built)
    bad, _ = check.judge(ctl, limits)
    assert not bad, ctl
    assert ctl["table_words_off"] > 0
