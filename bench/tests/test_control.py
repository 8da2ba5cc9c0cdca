"""The control (the reference at float8 matmuls, one step below the
bfloat16 the configuration states, put in the program's place) reads far
above the program, at the smoke sizes on the CPU.  On the chip the same
readings at each cell's own size come from ``bench/readings.py``."""
from types import SimpleNamespace

import compare
import run
from conftest import SMOKE_LIMITS


def test_control_fails_where_the_program_passes(smoke_root):
    cell = run.load_cell("smoke.t", smoke_root)
    run.enable_cache(smoke_root)
    from drivers.train import Trainer
    tr = Trainer(SimpleNamespace(config=cell.config, traffic=cell.traffic,
                                 seed=7, step_hook=None))
    prog, params, opt_state = tr.first_steps(7)
    del params, opt_state
    ref = tr.reference(7)
    sound = compare.numbers(prog, ref)
    control = compare.numbers(tr.reference(7, prec="fp8"), ref)
    assert all(r["ok"] for r in compare.verdict(sound, SMOKE_LIMITS)), sound
    assert not all(r["ok"] for r in compare.verdict(control, SMOKE_LIMITS)), \
        control
    assert max(control[k] / sound[k] for k in compare.NUMBERS) >= 3
    assert control["gap_geomean"] >= 3 * sound["gap_geomean"]
