"""Join a profile's device ops to the program's phases.

A device op in the trace carries only its HLO instruction's name
(``trace.op_name``).  The compiled step's HLO text says where each
instruction came from: its ``metadata={op_name="..."}`` is the path of
``jax.named_scope``s it was traced under, such as
``jit(train_step)/transpose(jvp())/.../tp.tick.fwd/.../tp.cache/...``
(autodiff's backward keeps its forward's scope inside ``transpose(...)``).
``op_scopes`` reads that map from the text, ``phase_of`` names the
innermost phase of one path, and ``of_run`` builds the map for a traced
run's train step.

The phase names are the benchmark's own copy of the program's
(``src/repro/tracing.py``): a scope the program renames reads as no phase,
never as another one.
"""
from __future__ import annotations

import re
import sys
import traceback
from types import SimpleNamespace
from typing import Dict, Optional

PHASES = ("step.optimizer", "tp.embed", "tp.tick.fwd", "tp.tick.bwd",
          "tp.tick.bwd_input", "tp.tick.bwd_weight", "tp.ring", "tp.cache",
          "tp.reassemble", "tp.head_loss", "tp.grad_reduce", "attn.relayout")

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+)\s*=[^\n]*?\bmetadata=\{[^}\n]*?'
                    r'op_name="((?:[^"\\\n]|\\.)*)"', re.M)
# a phase is a whole component of the path: between '/', '(' and ')'
_PHASE = re.compile(r"(?:^|(?<=[/(]))(%s)(?=$|[/)])" % "|".join(
    re.escape(p) for p in sorted(PHASES, key=len, reverse=True)))


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` for every instruction of the HLO
    text whose metadata has an ``op_name``."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def phase_of(op_name: str) -> Optional[str]:
    """The phase that occurs rightmost in ``op_name`` (the innermost
    scope), or None."""
    found = _PHASE.findall(op_name or "")
    return found[-1] if found else None


def step_hlo(obs) -> str:
    """The compiled HLO text of the cell's train step.  The step is built
    as ``drivers.train.Trainer`` builds the window's and lowered on
    arguments of the window's form, so the compile is the window's program
    and the compile cache hands back its executable."""
    import jax.numpy as jnp

    import weights
    from drivers.train import Trainer
    tr = Trainer(SimpleNamespace(config=obs.config, traffic=obs.traffic,
                                 seed=0, step_hook=None))
    with tr.mesh_ctx():
        params = tr.init(jnp.asarray(weights.key_data(0)))
        lowered = tr.step.lower(params, tr.zeros(), tr.feed(0))
        del params
        return lowered.compile().as_text()


def of_run(obs) -> Dict[str, str]:
    """The traced run's ``{op: op_name}`` map, built once and kept on
    ``obs.op_scopes``; empty (every phase reads None) where the step
    cannot be rebuilt."""
    if getattr(obs, "op_scopes", None) is None:
        try:
            obs.op_scopes = op_scopes(step_hlo(obs))
        except Exception:               # a reader returns nothing, never raises
            sys.stderr.write("scopes: no phase map\n" + traceback.format_exc())
            obs.op_scopes = {}
    return obs.op_scopes
