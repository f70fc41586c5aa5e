"""The arena cells' check, driven through the harness on the CPU at a
tiny size: a sound run is correct, and the control and each fault the
cell can have make it come out false."""

import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench.loops import LOOPS
from bench.tests.faults import FAULTS
from bench.tests.tiny import tiny_cell

CELL = "cifar10_cnn.arena_zoo"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def test_sound_run_is_correct():
    code, res = bench_run.execute(tiny_cell(CELL), 2**33 + 3, 0.5, False,
                                  require_tpu=False, peaks=PEAKS)
    assert code == 0 and res["correct"], res
    assert res["metrics"]["arena_scenario_rounds_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def limits_failed(numbers, limits):
    return [k for k, v in limits.items() if not numbers[k] <= v]


def test_control_fails():
    c = tiny_cell(CELL)
    r = LOOPS["arena"](c["cfg"], c["cell"], 6)
    r.setup()
    r.free()
    numbers = r.compare(r.reference(jnp.bfloat16), r.reference(jnp.float32))
    assert limits_failed(numbers, c["cell"]["limits"]), numbers


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_fails(fault):
    with FAULTS[fault]():
        code, res = bench_run.execute(tiny_cell(CELL), 7, 0.5, False,
                                      require_tpu=False, peaks=PEAKS)
    assert code == 0 and not res["correct"], res["checks"]
