"""The lower-precision control comes out not correct, at each cell's size.

It needs the chip (on the CPU every precision computes in float32, so
there is nothing to lower) and skips elsewhere; run it there with

    python -m pytest -s bench/tests/test_bench_control.py

(``-s`` shows each run's result line, with the numbers the control read).
"""

import time

import jax
import pytest

from bench import registry, run
from bench.lib import control

SEEDS = (2**31 + 101, 2**31 + 202, 2**31 + 303)


@pytest.fixture(scope="module")
def tpu():
    devices = jax.devices()
    if devices[0].platform != "tpu":
        pytest.skip("the control is read on a TPU")
    return devices[:1]


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  registry.benchmark()["workloads"]])
def test_control_is_not_correct(cell, tpu):
    seconds = registry.benchmark()["run_seconds"]
    with control.lowered_precision():
        for seed in SEEDS:
            res = run.execute(cell, seed, seconds, False, tpu,
                              t_start=time.perf_counter())
            assert not res["correct"], (seed, res["checks"])
