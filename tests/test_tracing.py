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


@pytest.mark.parametrize("K", [2, 4])
def test_executor_counts_ticks_and_idle_units(K):
    """Building the contiguous executor records ``tp.ticks``, ``tp.ranks``
    and ``tp.idle_units`` as the schedule IR states them: ``n_ticks(D*M) =
    D*M + K - 1`` ticks, K ranks, and the tick table's idle (tick, rank)
    entries, K - 1 per rank.  Nothing runs: the loop is built on an abstract (1, K) mesh."""
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, AxisType
    from repro.configs import get_config
    from repro.core.pipeline import (TeraPipeConfig,
                                     make_terapipe_value_and_grad)
    from repro.core.schedules import get_schedule
    from repro.models import build_model

    cfg = get_config("gpt3-1b", smoke=True).replace(dtype=jnp.float32)
    model = build_model(cfg)
    specs = model.init(jax.random.PRNGKey(0))[1]
    mesh = AbstractMesh((1, K), ("data", "pipe"),
                        axis_types=(AxisType.Auto,) * 2)
    D, M = 4, 8
    make_terapipe_value_and_grad(
        model, specs, mesh, TeraPipeConfig(n_token_slices=M,
                                           n_microbatches=D), 64, D)
    assign = get_schedule("contiguous", n_ranks=K, n_layers=cfg.n_layers,
                          virtual_stages=1, n_microbatches=D)
    tab = assign.tick_table(D * M)
    idle = (tab[..., 0] < 0).sum(axis=0)
    assert tracing.counters[tracing.TICKS] == assign.n_ticks(D * M) \
        == D * M + K - 1
    assert tracing.counters[tracing.RANKS] == K
    assert list(idle) == [K - 1] * K
    assert tracing.counters[tracing.IDLE_UNITS] == idle.sum()
