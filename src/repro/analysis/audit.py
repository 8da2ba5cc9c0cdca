"""Schedule-matrix audit driver: run the rule registry over every
registered schedule × representative configs.

One cell = one registered schedule at one ``use_kernel`` setting, traced as
the full loss+grad program of the unified executor on a tiny dense model
(the same geometry the executor tests use).  Per cell the driver runs:

* ``ir.validate``            — the schedule's own tick-table audit;
* ``comm.*``                 — ppermute permutations, branch-uniform
                               collectives, rings == ``comm_plan()``;
* ``buffer.*``               — score-matrix / repeated-KV lints
                               (``use_kernel=True`` cells only: the pure-jnp
                               reference legitimately materializes scores);
* ``scale.*``                — carry stability + O(1)-in-M and O(1)-in-D
                               growth (two extra traces per cell);
* ``dtype.upcast``           — bf16 -> f32 cast census (info);
* ``vmem.budget``            — Pallas kernel VMEM estimates;
* ``donation.aliased``       — an SGD step with donated params actually
                               aliases every leaf (compiles; once per
                               schedule unless forced).

``run_matrix`` aggregates the cells into the machine-readable report
``python -m repro.analysis`` serializes (see EXPERIMENTS.md §Analysis).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

from . import rules
from .findings import Finding, errors
from .walker import count_eqns

#: the training schedules `make lint-ir` must hold green (ISSUE 8
#: acceptance); the fwd-only serving schedule is audited best-effort since
#: its tick table normally comes from a live request queue.
TRAIN_SCHEDULES = ("contiguous", "interleaved", "1f1b", "interleaved-1f1b",
                   "zb-h1")


@dataclasses.dataclass
class Cell:
    """One (schedule, use_kernel) audit cell's geometry."""
    schedule: str
    use_kernel: bool
    K: int = 2
    D: int = 2          # microbatches
    #: 5 slices of l=8 tokens: S=40 collides with none of the tiny model's
    #: projection fan-outs ({hkv·hd=32, d_model=64, d_ff=128, vocab=256}),
    #: so the (l, ctx+l) buffer lint cannot false-fire on a weight matmul.
    M: int = 5          # token slices
    n_layers: int = 4
    required: bool = True

    def name(self) -> str:
        return f"{self.schedule}/kernel={'on' if self.use_kernel else 'off'}"


def _build_model(n_layers: int):
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.models.common import ModelConfig
    cfg = ModelConfig(name="audit", family="dense", n_layers=n_layers,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=256, dtype=jnp.bfloat16, remat=False)
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, specs


def _virtual_stages(schedule: str) -> int:
    from repro.core import schedules
    return max(schedules.REGISTRY[schedule].min_virtual, 1)


def _trace_vg(model, specs, params, *, schedule: str, K: int, D: int,
              M: int, use_kernel: bool):
    """(vg, jaxpr, batch) of the executor's loss+grad program."""
    import jax
    import jax.numpy as jnp

    from repro.compat import make_mesh, use_mesh
    from repro.core.pipeline import (TeraPipeConfig,
                                     make_terapipe_value_and_grad)
    B, S = 2 * D, 8 * M
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}
    mesh = make_mesh((1, K), ("data", "pipe"))
    tcfg = TeraPipeConfig(n_token_slices=M, n_microbatches=D,
                          data_axes=("data",), cache_dtype=jnp.bfloat16,
                          schedule=schedule, use_kernel=use_kernel,
                          virtual_stages=_virtual_stages(schedule))
    with use_mesh(mesh):
        vg, _ = make_terapipe_value_and_grad(model, specs, mesh, tcfg, S, B)
        jaxpr = jax.make_jaxpr(vg)(params, batch)
    return vg, jaxpr, batch


def audit_cell(cell: Cell, *, compile_donation: bool = False,
               growth: bool = True) -> Dict[str, Any]:
    """Run the full rule set on one cell; returns the cell record."""
    import jax

    from repro.core import schedules as sched_mod
    if jax.device_count() < cell.K:
        raise ValueError(
            f"cell {cell.name()} needs K={cell.K} devices, have "
            f"{jax.device_count()} (the CLI forces host devices itself)")
    findings: List[Finding] = []
    K, D, M = cell.K, cell.D, cell.M
    S = 8 * M
    geom = {"K": K, "D": D, "M": M, "S": S, "l": S // M, "cache_len": S,
            "hq": 4, "hkv": 2, "V": _virtual_stages(cell.schedule),
            "n_layers": cell.n_layers}

    # the IR's own audit first: tick table vs comm plan
    assign = sched_mod.get_schedule(
        cell.schedule, n_ranks=K, n_layers=cell.n_layers,
        virtual_stages=geom["V"], n_microbatches=D * M)
    try:
        assign.validate(D * M)
        findings.append(Finding("ir.validate", "info",
                                f"tick table validates for {D * M} items"))
    except sched_mod.ScheduleValidationError as e:
        findings.append(Finding("ir.validate", "error", str(e)))

    _, model, params, specs = _build_model(cell.n_layers)
    vg, jaxpr, batch = _trace_vg(model, specs, params,
                                 schedule=cell.schedule, K=K, D=D, M=M,
                                 use_kernel=cell.use_kernel)
    plan = assign.comm_plan()

    findings += rules.check_ppermute_perms(jaxpr, axis_size=K,
                                           axis_name="pipe")
    findings += rules.check_branch_uniform(jaxpr)
    # the loss+grad trace always carries the reverse ring: declared by
    # explicit-bwd schedules, AD-transposed from the fwd ring otherwise
    findings += rules.check_ring_match(jaxpr, n_ranks=K, plan=plan,
                                       expect_rev=True)
    if cell.use_kernel:
        findings += rules.check_score_matrix(jaxpr, l=geom["l"], sk=S)
        findings += rules.check_kv_repeat(jaxpr, sk=S, hq=geom["hq"],
                                            hkv=geom["hkv"])
    findings += rules.check_carry_stability(jaxpr)
    findings += rules.check_dtype_upcasts(jaxpr)
    findings += rules.check_vmem(jaxpr)

    if growth:
        _, jx_bigm, _ = _trace_vg(model, specs, params,
                                  schedule=cell.schedule, K=K, D=D,
                                  M=4 * M, use_kernel=cell.use_kernel)
        findings += rules.check_flat_growth(jaxpr, jx_bigm,
                                            label=f"M {M}->{4 * M}")
        _, jx_bigd, _ = _trace_vg(model, specs, params,
                                  schedule=cell.schedule, K=K, D=2 * D,
                                  M=M, use_kernel=cell.use_kernel)
        findings += rules.check_flat_growth(jaxpr, jx_bigd,
                                            label=f"D {D}->{2 * D}")

    if compile_donation:
        def step(p, b):
            _, grads = vg(p, b)
            return jax.tree.map(lambda w, g: (w - 1e-2 * g).astype(w.dtype),
                                p, grads)
        findings += rules.check_donation(step, (params, batch),
                                         donate_argnums=(0,),
                                         label=cell.name())

    return {"cell": cell.name(), "schedule": cell.schedule,
            "use_kernel": cell.use_kernel, "geometry": geom,
            "eqns": count_eqns(jaxpr), "required": cell.required,
            "findings": [f.to_dict() for f in findings],
            "ok": not errors(findings)}


def default_cells(schedules: Optional[Sequence[str]] = None, *,
                  K: int = 2) -> List[Cell]:
    """The registry matrix: every requested schedule × use_kernel on/off.
    Defaults to every REGISTRY entry; non-training schedules (streaming)
    are best-effort cells."""
    from repro.core import schedules as sched_mod
    names = tuple(schedules) if schedules else sched_mod.schedule_names()
    return [Cell(name, use_kernel, K=K,
                 required=name in TRAIN_SCHEDULES)
            for name in names for use_kernel in (False, True)]


def run_matrix(cells: Sequence[Cell], *, compile_donation: bool = True,
               growth: bool = True,
               log=lambda msg: None) -> Dict[str, Any]:
    """Audit every cell; donation compiles once per schedule (on the
    kernel-off cell) to bound wall-clock.  Returns the JSON-ready report."""
    import jax
    records = []
    donated = set()
    for cell in cells:
        donate_here = (compile_donation and not cell.use_kernel
                       and cell.schedule not in donated)
        try:
            rec = audit_cell(cell, compile_donation=donate_here,
                             growth=growth)
            if donate_here:
                donated.add(cell.schedule)
        except Exception as e:                      # noqa: BLE001
            if cell.required:
                raise
            rec = {"cell": cell.name(), "schedule": cell.schedule,
                   "use_kernel": cell.use_kernel, "required": False,
                   "skipped": f"{type(e).__name__}: {e}", "findings": [],
                   "ok": True}
            log(f"  skipped best-effort cell {cell.name()}: {e}")
        n_err = len([f for f in rec["findings"]
                     if f["severity"] == "error"])
        log(f"  {rec['cell']}: {len(rec['findings'])} findings, "
            f"{n_err} errors")
        records.append(rec)
    return {"jax": jax.__version__,
            "rules": sorted(rules.rule_ids()),
            "cells": records,
            "ok": all(r["ok"] for r in records)}
