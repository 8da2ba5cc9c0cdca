"""The train step's phases and flash kernels keep their names through
compilation: each ``jax.named_scope`` of ``repro.tracing`` must reach the
``op_name`` metadata of the compiled step's instructions, where a profile's
device ops are joined to it.  Explicit-backward schedules run on four
virtual devices in a subprocess (the main process keeps its one device)."""
import json
import re
from pathlib import Path

import jax
import pytest

from repro import tracing
from test_system import _run_subprocess   # shared multi-device harness

SMOKE = ["--arch", "qwen3-0.6b", "--smoke", "--mode", "terapipe",
         "--use-kernel", "--seq", "32", "--token-slices", "2",
         "--steps", "2"]
KERNELS = {tracing.FLASH_FWD, tracing.FLASH_BWD_DQ, tracing.FLASH_BWD_DKV}


def compiled_op_names(argv):
    """Every ``op_name`` in the compiled HLO of the train step that
    ``launch.train.setup`` builds for ``argv``."""
    from repro.compat import use_mesh
    from repro.launch import train
    prog = train.setup(train.parse_args(argv))
    with use_mesh(prog.mesh):
        compiled = jax.jit(prog.train_step).lower(
            prog.params, prog.opt_state, prog.data.batch_at(0)).compile()
    return sorted(set(re.findall(r'op_name="([^"]*)"', compiled.as_text())))


def holds(op_name, name):
    """``name`` is a whole component of ``op_name``'s scope path."""
    return re.search(rf"(^|[/(]){re.escape(name)}($|[/)])", op_name)


CASES = {
    "contiguous": (1, ["--batch", "2"],
                   {tracing.OPTIMIZER, tracing.EMBED, tracing.TICK_FWD,
                    tracing.CACHE, tracing.REASSEMBLE, tracing.HEAD_LOSS,
                    tracing.ATTN_RELAYOUT} | KERNELS),
    "1f1b": (4, ["--batch", "4", "--microbatches", "2", "--schedule", "1f1b"],
             {tracing.TICK_FWD, tracing.TICK_BWD, tracing.RING,
              tracing.GRAD_REDUCE, tracing.CACHE} | KERNELS),
    "zb-h1": (4, ["--batch", "4", "--microbatches", "2", "--schedule",
                  "zb-h1"],
              {tracing.TICK_FWD, tracing.TICK_BWD_INPUT,
               tracing.TICK_BWD_WEIGHT, tracing.RING, tracing.GRAD_REDUCE,
               tracing.CACHE} | KERNELS),
}


@pytest.mark.parametrize("schedule", list(CASES))
def test_compiled_step_carries_phase_names(schedule):
    devices, extra, expected = CASES[schedule]
    argv = SMOKE + extra
    if devices == 1:
        names = compiled_op_names(argv)
    else:
        out = _run_subprocess(f"""
            import json, sys
            sys.path.insert(0, {str(Path(__file__).parent)!r})
            from test_tracing import compiled_op_names
            print(json.dumps(compiled_op_names({argv!r})))
        """, devices=devices)
        names = json.loads(out.strip().splitlines()[-1])
    missing = sorted(n for n in expected
                     if not any(holds(o, n) for o in names))
    assert not missing, missing
    if schedule == "contiguous":
        # autodiff's backward carries the forward's scope in a transpose
        fwd = [o for o in names if holds(o, tracing.TICK_FWD)]
        assert any("transpose(" not in o for o in fwd)
        assert any("transpose(" in o for o in fwd)
