"""The control of ``correct`` at a size a test run holds: the reference
computed in bfloat16 in the program's place must fail a limit of each
cell, while the program itself passes them all."""
from unittest import mock

import bench_cells
import jax
import pytest

from bench import check, control, spec


def readings(name, seed):
    cell = bench_cells.tiny_cell(name)
    drv = spec.driver(cell["traffic"]["kind"]).Driver(cell, seed, jax.profiler.TraceAnnotation)
    with mock.patch("repro.launch.compile_cache.enable", lambda: None):
        drv.setup()
        drv.window(0.0 if cell["traffic"]["kind"] == "train" else 0.5)
        drv.release()
        program = drv.check_numbers()
        fn = control.control_train if cell["traffic"]["kind"] == "train" else control.control_serve
        return cell["limits"], program, fn(drv)


@pytest.mark.parametrize("name", ["train-paper-n200", "train-fleet-n50k",
                                  "serve-paper-latency"])
def test_control_fails_and_program_passes(name):
    limits, program, ctl = readings(name, 2**31 + 3)
    assert check.verdict(program, limits)[0], (program, limits)
    assert not check.verdict(ctl, limits)[0], (ctl, limits)
