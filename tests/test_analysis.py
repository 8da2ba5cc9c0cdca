"""repro.analysis (ISSUE 8): every rule family has a seeded violation the
rule must catch BY NAME (rule id + offending eqn), plus clean positives,
the hardened HLO-text layer, ScheduleValidationError message-content
checks, and an in-process run of the full audit matrix at K=1.

The negative tests are the analyzer's teeth: each seeds exactly the bug
class the rule exists for (non-permutation ppermute, branch-skewed
collective, materialized score matrix, GQA-repeated KV, unrolled trace
growth, drifting scan carry, dropped donation, silent fp32 upcast,
VMEM-busting Pallas blocks) and asserts the finding identifies it.
"""
import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import count_eqns, errors
from repro.analysis import hlo as ahlo
from repro.analysis import rules
from repro.compat import make_mesh
from repro.core.schedules import (KIND_BWD, KIND_BWD_INPUT, KIND_FWD,
                                  KIND_IDLE, CommPlan,
                                  ScheduleValidationError, get_schedule)

from test_system import _run_subprocess   # shared multi-device harness


def _pipe_mesh():
    return make_mesh((1,), ("pipe",))


def _smap(f):
    return jax.shard_map(f, mesh=_pipe_mesh(), in_specs=P("pipe"),
                     out_specs=P("pipe"), check_vma=False)


# ---------------------------------------------------------------------------
# comm-safety
# ---------------------------------------------------------------------------
def test_ppermute_permutation_rule_flags_duplicates():
    f = _smap(lambda x: jax.lax.ppermute(x, "pipe", [(0, 0), (0, 0)]))
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((1, 4)))
    errs = errors(rules.check_ppermute_perms(jaxpr, axis_size=1))
    assert errs, "duplicate-pair ppermute not flagged"
    assert errs[0].rule == "comm.ppermute-permutation"
    assert errs[0].eqn == "ppermute"
    assert "duplicate source" in errs[0].message


def test_ppermute_permutation_rule_flags_out_of_range():
    f = _smap(lambda x: jax.lax.ppermute(x, "pipe", [(0, 3)]))
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((1, 4)))
    errs = errors(rules.check_ppermute_perms(jaxpr, axis_size=1))
    assert errs and "out of range" in errs[0].message


def test_ppermute_permutation_rule_clean_on_ring():
    f = _smap(lambda x: jax.lax.ppermute(x, "pipe", [(0, 0)]))
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((1, 4)))
    assert not errors(rules.check_ppermute_perms(jaxpr, axis_size=1))


def test_branch_uniform_flags_skewed_collective():
    def g(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda y: jax.lax.psum(y, "pipe"),
                            lambda y: y, x)
    jaxpr = jax.make_jaxpr(_smap(g))(jnp.zeros((1, 4)))
    errs = errors(rules.check_branch_uniform(jaxpr))
    assert errs, "branch-skewed psum not flagged"
    assert errs[0].rule == "comm.branch-uniform"
    assert errs[0].eqn == "cond"
    assert "psum" in errs[0].message


def test_branch_uniform_clean_when_both_branches_fire():
    def g(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda y: jax.lax.psum(y, "pipe"),
                            lambda y: jax.lax.psum(2.0 * y, "pipe"), x)
    jaxpr = jax.make_jaxpr(_smap(g))(jnp.zeros((1, 4)))
    assert not errors(rules.check_branch_uniform(jaxpr))


def test_ring_match_flags_missing_forward_ring():
    jaxpr = jax.make_jaxpr(_smap(lambda x: x * 2.0))(jnp.zeros((1, 4)))
    errs = errors(rules.check_ring_match(jaxpr, n_ranks=1, plan=CommPlan(),
                                         expect_rev=False))
    assert errs and errs[0].rule == "comm.ring-match"
    assert "no forward-ring ppermute" in errs[0].message


def test_ring_match_flags_ring_under_cond_branch():
    def g(x):
        return jax.lax.cond(
            x.sum() > 0,
            lambda y: jax.lax.ppermute(y, "pipe", [(0, 0)]),
            lambda y: y, x)
    jaxpr = jax.make_jaxpr(_smap(g))(jnp.zeros((1, 4)))
    errs = errors(rules.check_ring_match(jaxpr, n_ranks=1, plan=CommPlan(),
                                         expect_rev=False))
    assert any("inside a cond branch" in e.message for e in errs), errs


def test_ring_match_flags_undeclared_ring_k4():
    """K=4 (real devices, subprocess): an identity 'ring' is neither the
    forward nor the reverse ring of the comm plan and is named as such."""
    out = _run_subprocess(devices=4, code="""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.analysis import rules, errors
        from repro.compat import make_mesh
        from repro.core.schedules import CommPlan
        mesh = make_mesh((4,), ("pipe",))
        ident = [(j, j) for j in range(4)]
        f = jax.shard_map(lambda x: jax.lax.ppermute(x, "pipe", ident),
                      mesh=mesh, in_specs=P("pipe"), out_specs=P("pipe"),
                      check_vma=False)
        jaxpr = jax.make_jaxpr(f)(jnp.zeros((4, 4)))
        errs = errors(rules.check_ring_match(jaxpr, n_ranks=4,
                                             plan=CommPlan(),
                                             expect_rev=False))
        assert errs, "identity perm accepted as a ring"
        assert errs[0].rule == "comm.ring-match", errs
        assert "neither the declared forward ring" in errs[0].message
        # and the true rings pass
        fwd = [(j, (j + 1) % 4) for j in range(4)]
        g = jax.shard_map(lambda x: jax.lax.ppermute(x, "pipe", fwd),
                      mesh=mesh, in_specs=P("pipe"), out_specs=P("pipe"),
                      check_vma=False)
        jg = jax.make_jaxpr(g)(jnp.zeros((4, 4)))
        assert not errors(rules.check_ring_match(jg, n_ranks=4,
                                                 plan=CommPlan(),
                                                 expect_rev=False))
        print("RING-MATCH-K4-OK")
    """)
    assert "RING-MATCH-K4-OK" in out


# ---------------------------------------------------------------------------
# buffer lints
# ---------------------------------------------------------------------------
def test_score_matrix_rule_flags_injected_einsum():
    l, sk = 32, 96
    q = jnp.zeros((l, 16))
    k = jnp.zeros((sk, 16))
    jaxpr = jax.make_jaxpr(lambda q, k: jnp.einsum("ld,sd->ls", q, k))(q, k)
    errs = errors(rules.check_score_matrix(jaxpr, l=l, sk=sk))
    assert errs, "materialized (l, sk) einsum not flagged"
    assert errs[0].rule == "buffer.score-matrix"
    assert errs[0].eqn == "dot_general"
    assert f"(l={l}, ctx+l={sk})" in errs[0].message


def test_score_matrix_rule_clean_on_linear_op():
    jaxpr = jax.make_jaxpr(lambda x: jnp.cumsum(x, axis=0))(
        jnp.zeros((32, 16)))
    assert not errors(rules.check_score_matrix(jaxpr, l=32, sk=96))


def test_repeated_kv_rule_flags_broadcast():
    sk, hq, hkv = 96, 8, 2
    k = jnp.zeros((1, sk, 1, 16))
    jaxpr = jax.make_jaxpr(
        lambda k: jnp.broadcast_to(k, (1, sk, hq, 16)))(k)
    errs = errors(rules.check_kv_repeat(jaxpr, sk=sk, hq=hq, hkv=hkv))
    assert errs, "GQA-repeated KV broadcast not flagged"
    assert errs[0].rule == "buffer.repeated-kv"
    assert errs[0].eqn == "broadcast_in_dim"
    # dense heads: the rule is a no-op by definition
    assert not rules.check_kv_repeat(jaxpr, sk=sk, hq=hq, hkv=hq)


# ---------------------------------------------------------------------------
# scale lints
# ---------------------------------------------------------------------------
def _rolled(n):
    return jax.make_jaxpr(lambda x: jax.lax.scan(
        lambda c, _: (c * 1.5 + 1.0, None), x, None, length=n)[0])(2.0)


def _unrolled(n):
    def f(x):
        for _ in range(n):
            x = x * 1.5 + 1.0
        return x
    return jax.make_jaxpr(f)(2.0)


def test_flat_growth_rule_flags_unrolled_trace():
    errs = errors(rules.check_flat_growth(_unrolled(4), _unrolled(64),
                                          label="unrolled"))
    assert errs and errs[0].rule == "scale.flat-growth"
    assert "not O(1)" in errs[0].message
    assert not errors(rules.check_flat_growth(_rolled(4), _rolled(64)))


def test_eqn_budget_rule():
    errs = errors(rules.check_eqn_budget(_unrolled(64), max_eqns=10))
    assert errs and errs[0].rule == "scale.eqn-budget"
    ok = rules.check_eqn_budget(_rolled(64), max_eqns=10)
    assert not errors(ok) and ok[0].data["eqns"] == count_eqns(_rolled(64))


def test_carry_stability_rule_flags_drifting_carry():
    """jax itself rejects drifting carries at trace time, so the negative
    is a stub jaxpr — the rule still matters for hand-built/rewritten IR
    and guards against tracer regressions."""
    def var(shape, dtype):
        return SimpleNamespace(aval=jax.core.ShapedArray(shape, dtype))
    body = SimpleNamespace(eqns=[], constvars=[],
                           invars=[var((4,), jnp.float32)],
                           outvars=[var((4,), jnp.bfloat16)])
    eqn = SimpleNamespace(primitive=SimpleNamespace(name="scan"),
                          params={"jaxpr": SimpleNamespace(jaxpr=body),
                                  "num_consts": 0, "num_carry": 1},
                          invars=[], outvars=[])
    top = SimpleNamespace(eqns=[eqn])
    errs = errors(rules.check_carry_stability(top))
    assert errs and errs[0].rule == "scale.carry-stability"
    assert "carry leaf 0" in errs[0].message
    # a real scan is clean
    assert not errors(rules.check_carry_stability(_rolled(8)))


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------
def test_donation_rule_flags_unaliased_donation():
    w = jnp.ones((32, 32))
    x = jnp.ones((32, 32))
    # w is donated but never returned: its buffer cannot alias any output
    errs = errors(rules.check_donation(lambda w, x: x * 2.0, (w, x),
                                       donate_argnums=(0,)))
    assert errs, "dropped donation not flagged"
    assert errs[0].rule == "donation.aliased"
    assert "NOT aliased" in errs[0].message and errs[0].data["param"] == 0


def test_donation_rule_clean_on_real_aliasing():
    w = {"a": jnp.ones((32, 32)), "b": jnp.zeros((8,))}
    x = jnp.ones((32, 32))
    step = lambda w, x: jax.tree.map(lambda p: p * 0.5, w)
    findings = rules.check_donation(step, (w, x), donate_argnums=(0,))
    assert not errors(findings)
    assert findings[0].data["donated_leaves"] == 2


# ---------------------------------------------------------------------------
# dtype lint
# ---------------------------------------------------------------------------
def test_dtype_upcast_rule_flags_fp32_upcast():
    x = jnp.ones((8, 8), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda x: (x.astype(jnp.float32) @ x.astype(jnp.float32)))(x)
    errs = errors(rules.check_dtype_upcasts(jaxpr, allow=0))
    assert errs, "bf16 -> f32 upcast not flagged"
    assert errs[0].rule == "dtype.upcast"
    assert any(e.eqn == "convert_element_type" for e in errs)
    # the same trace under a budget that admits it: info only
    assert not errors(rules.check_dtype_upcasts(jaxpr, allow=2))
    clean = jax.make_jaxpr(lambda x: x * 2)(x)
    assert rules.check_dtype_upcasts(clean, allow=0)[0].severity == "info"


# ---------------------------------------------------------------------------
# Pallas VMEM estimator
# ---------------------------------------------------------------------------
def test_vmem_rule_flags_oversized_block():
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def big(x):
        return pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct((2048, 2048), jnp.float32),
            interpret=True)(x)

    jaxpr = jax.make_jaxpr(big)(jnp.zeros((2048, 2048), jnp.float32))
    errs = errors(rules.check_vmem(jaxpr))
    assert errs, "a 2x16.8 MiB whole-array block passed the VMEM budget"
    assert errs[0].rule == "vmem.budget" and errs[0].eqn == "pallas_call"
    assert errs[0].data["total_bytes"] > rules.VMEM_BUDGET_BYTES
    # the budget is a parameter: a TPU generation with more VMEM admits it
    assert not errors(rules.check_vmem(jaxpr, budget_bytes=64 * 2 ** 20))


# ---------------------------------------------------------------------------
# hardened HLO-text layer (the hlo_tripcount bugfix surface)
# ---------------------------------------------------------------------------
_HLO_TYPED = """\
HloModule m

ENTRY %main (p0: f32[8,16], p1: f32[16,4]) -> f32[8,4] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %p1 = f32[16,4]{1,0} parameter(1)
  ROOT %dot = f32[8,4]{1,0} dot(f32[8,16]{1,0} %p0, f32[16,4]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

_HLO_BARE = _HLO_TYPED.replace("f32[8,16]{1,0} %p0,", "p0,").replace(
    "f32[16,4]{1,0} %p1)", "p1)")


def test_hlo_dot_flops_typed_and_sigilless_operands():
    from repro.launch.hlo_tripcount import analyze
    want = 2.0 * 8 * 4 * 16
    assert analyze(_HLO_TYPED)["flops"] == want
    # sigil-less operand style: the old first-%ref-anywhere parser silently
    # returned 0 flops here
    assert analyze(_HLO_BARE)["flops"] == want


def test_hlo_multi_ring_ppermute_names_counted():
    from repro.launch.hlo_tripcount import analyze
    hlo = """\
HloModule m

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %collective-permute = f32[8]{0} collective-permute(f32[8]{0} %p0), source_target_pairs={{0,1},{1,0}}
  ROOT %collective-permute.1 = f32[8]{0} collective-permute(f32[8]{0} %collective-permute), source_target_pairs={{1,0},{0,1}}
}
"""
    coll = analyze(hlo)["collectives"]
    # both rings counted: the `.1` suffix is on the NAME, not the opcode
    assert coll["collective-permute"] == 2 * 8 * 4, coll


def test_hlo_operand_refs_stop_at_call_paren():
    refs = ahlo.operand_refs(
        "f32[8]{0} %a, f32[8]{0} %b.1), calls=%fused_computation, "
        "control-predecessors={%z}")
    assert refs == ["a", "b.1"], refs


def test_hlo_input_output_alias_parsing():
    hlo = ("HloModule m, input_output_alias={ {0}: (0, {}, may-alias), "
           "{1}: (2, {1}, must-alias) }\n")
    aliases = ahlo.parse_input_output_aliases(hlo)
    assert [(a.param_number, a.output_index, a.param_index, a.kind)
            for a in aliases] == [(0, (0,), (), "may-alias"),
                                  (2, (1,), (1,), "must-alias")]
    assert ahlo.parse_input_output_aliases("HloModule m\n") == []


# ---------------------------------------------------------------------------
# ScheduleValidationError message content
# ---------------------------------------------------------------------------
def _tampered(base, mutate):
    """A copy of ``base`` whose tick table is mutated before validation."""
    cls = type(base)

    class Tampered(cls):
        def tick_table(self, n_items):
            tab = super().tick_table(n_items).copy()
            mutate(tab)
            return tab

    return Tampered(**dataclasses.asdict(base))


def _first(tab, kind):
    import numpy as np
    ts, ks = np.nonzero(tab[:, :, 2] == kind)
    return int(ts[0]), int(ks[0])


def _rank_ticks(tab, kind, rank=None):
    """Ticks at which ``rank`` (default: the kind's first rank) runs
    ``kind`` units — same-rank tampering keeps stage_of() stable so the
    validator names the intended violation, not a count mismatch."""
    import numpy as np
    ts, ks = np.nonzero(tab[:, :, 2] == kind)
    if rank is None:
        rank = int(ks[0])
    return [int(t) for t, k in zip(ts, ks) if int(k) == rank], rank


def test_validation_error_names_double_scheduled_unit():
    base = get_schedule("1f1b", n_ranks=2, n_layers=2, n_microbatches=4)

    def dup(tab):
        (t0, t1, *_), k = _rank_ticks(tab, KIND_FWD)
        tab[t1, k] = tab[t0, k]

    with pytest.raises(ScheduleValidationError,
                       match=r"scheduled twice.*tick"):
        _tampered(base, dup).validate(4)


def test_validation_error_names_undeliverable_fwd_unit():
    base = get_schedule("contiguous", n_ranks=2, n_layers=2,
                        n_microbatches=4)

    def swap(tab):
        # swapping two same-rank fwd units breaks producer timing for the
        # downstream stage without touching unit counts
        (t0, t1, *_), k = _rank_ticks(tab, KIND_FWD, rank=0)
        tab[[t0, t1], k] = tab[[t1, t0], k]

    with pytest.raises(ScheduleValidationError,
                       match=r"ring predecessor rank .* the forward ring "
                             r"cannot deliver it"):
        _tampered(base, swap).validate(4)


def test_validation_error_names_bwd_before_fwd():
    base = get_schedule("1f1b", n_ranks=2, n_layers=2, n_microbatches=4)

    def early(tab):
        tb, kb = _first(tab, KIND_BWD)
        idle, _ = _rank_ticks(tab, KIND_IDLE, rank=kb)
        t0 = [t for t in idle if t < tb][0]
        tab[t0, kb] = tab[tb, kb]
        tab[tb, kb] = (-1, -1, KIND_IDLE)

    with pytest.raises(ScheduleValidationError,
                       match=r"no\s+residuals to transpose"):
        _tampered(base, early).validate(4)


def test_validation_error_names_fused_bwd_in_split_schedule():
    base = get_schedule("zb-h1", n_ranks=2, n_layers=2, n_microbatches=4)

    def fuse(tab):
        t, k = _first(tab, KIND_BWD_INPUT)
        tab[t, k, 2] = KIND_BWD

    with pytest.raises(ScheduleValidationError,
                       match=r"fused bwd unit.*bwd-input/bwd-weight"):
        _tampered(base, fuse).validate(4)


# ---------------------------------------------------------------------------
# the audit matrix itself (in-process, K=1; the CLI runs K>=2)
# ---------------------------------------------------------------------------
def test_audit_matrix_clean_for_all_training_schedules():
    """Every training schedule × use_kernel on/off passes the full rule set
    on the loss+grad trace — the in-process half of `make lint-ir`."""
    from repro.analysis import audit
    for sched in audit.TRAIN_SCHEDULES:
        for use_kernel in (False, True):
            rec = audit.audit_cell(audit.Cell(sched, use_kernel, K=1),
                                   growth=False)
            bad = [f for f in rec["findings"] if f["severity"] == "error"]
            assert not bad, (sched, use_kernel, bad)
            rule_set = {f["rule"] for f in rec["findings"]}
            assert "ir.validate" in rule_set
            assert "comm.ring-match" in rule_set
            if use_kernel:
                assert "vmem.budget" in rule_set


def test_audit_cell_records_donation_finding():
    from repro.analysis import audit
    rec = audit.audit_cell(audit.Cell("1f1b", False, K=1), growth=False,
                           compile_donation=True)
    don = [f for f in rec["findings"] if f["rule"] == "donation.aliased"]
    assert don and don[0]["severity"] == "info", don
    assert don[0]["data"]["donated_leaves"] > 0
