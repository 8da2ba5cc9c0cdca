"""The audit rules: name -> pass over a traced (or compiled) program.

Six families (ISSUE 8): comm-safety, buffer lints, scale lints, donation,
dtype, and the Pallas VMEM estimator.  Every rule returns a list of
:class:`~repro.analysis.findings.Finding` and never raises on a violation —
callers pick the enforcement (``raise_on_errors`` for CI/benchmarks, plain
asserts in tests, JSON in the ``python -m repro.analysis`` matrix).

Rules that only read a trace take a (Closed)jaxpr first; the donation audit
takes ``(fn, args)`` because aliasing only exists in the compiled module.
"""
from __future__ import annotations

import dataclasses
import math
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import hlo
from .findings import SEV_ERROR, SEV_INFO, Finding
from .walker import count_eqns, iter_eqn_avals, iter_eqns

# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Rule:
    rule_id: str
    family: str
    doc: str
    fn: Callable[..., List[Finding]]


RULES: Dict[str, Rule] = {}


def register_rule(rule_id: str, family: str):
    """Register an audit pass under ``family.name`` (CLI listing + docs)."""
    def deco(fn):
        assert rule_id not in RULES, f"duplicate rule {rule_id!r}"
        RULES[rule_id] = Rule(rule_id, family, (fn.__doc__ or "").strip()
                              .split("\n")[0], fn)
        return fn
    return deco


def rule_ids() -> Tuple[str, ...]:
    return tuple(RULES)


# ---------------------------------------------------------------------------
# comm-safety
# ---------------------------------------------------------------------------
#: primitives whose execution must be uniform across ranks (SPMD deadlock
#: surface); ``axis_index`` excluded — it communicates nothing.
COLLECTIVE_PRIMS = frozenset({
    "ppermute", "pbroadcast", "psum", "psum_scatter", "pmax", "pmin",
    "all_gather", "all_gather_invariant", "all_to_all", "reduce_scatter",
    "pgather", "psum_invariant"})


def _axis_names(eqn) -> Tuple[str, ...]:
    ax = eqn.params.get("axis_name", ())
    return tuple(ax) if isinstance(ax, (tuple, list)) else (ax,)


@register_rule("comm.ppermute-permutation", "comm")
def check_ppermute_perms(jaxpr, *, axis_size: Optional[int] = None,
                         axis_name: Optional[str] = None) -> List[Finding]:
    """Every ppermute ``perm`` is a true permutation: distinct sources,
    distinct destinations, in-range ranks — a duplicate silently drops or
    double-delivers a ring message (data corruption, then deadlock)."""
    out: List[Finding] = []
    for site in iter_eqns(jaxpr):
        if site.prim != "ppermute":
            continue
        if axis_name is not None and axis_name not in _axis_names(site.eqn):
            continue
        perm = [tuple(p) for p in site.eqn.params["perm"]]
        srcs = Counter(s for s, _ in perm)
        dsts = Counter(d for _, d in perm)
        bad = []
        bad += [f"duplicate source rank {r}" for r, c in srcs.items()
                if c > 1]
        bad += [f"duplicate destination rank {r}" for r, c in dsts.items()
                if c > 1]
        if axis_size is not None:
            bad += [f"rank {r} out of range for axis size {axis_size}"
                    for r in set(srcs) | set(dsts)
                    if not 0 <= r < axis_size]
        if bad:
            out.append(Finding(
                "comm.ppermute-permutation", SEV_ERROR,
                f"perm {perm} is not a permutation: " + "; ".join(bad),
                eqn="ppermute", path=site.where(),
                data={"perm": [list(p) for p in perm]}))
    return out


@register_rule("comm.branch-uniform", "comm")
def check_branch_uniform(jaxpr) -> List[Finding]:
    """Collectives are issued uniformly across cond/switch branches: a rank
    taking a branch that fires a different collective multiset than its
    peers' branch deadlocks the mesh (static deadlock-freedom)."""
    out: List[Finding] = []
    for site in iter_eqns(jaxpr):
        if site.prim != "cond":
            continue
        branches = site.eqn.params["branches"]
        counts = [Counter(s.prim for s in iter_eqns(br)
                          if s.prim in COLLECTIVE_PRIMS)
                  for br in branches]
        if any(c != counts[0] for c in counts[1:]):
            detail = [dict(sorted(c.items())) for c in counts]
            skew = sorted({p for c in counts for p in c
                           if any(c2[p] != c[p] for c2 in counts)})
            out.append(Finding(
                "comm.branch-uniform", SEV_ERROR,
                f"cond branches fire different collective multisets "
                f"{detail} (skewed: {skew}): a rank in one branch blocks "
                f"on a collective its peers never issue",
                eqn="cond", path=site.where(),
                data={"per_branch": detail}))
    return out


@register_rule("comm.ring-match", "comm")
def check_ring_match(jaxpr, *, n_ranks: int, plan,
                     axis_name: str = "pipe",
                     expect_rev: Optional[bool] = None) -> List[Finding]:
    """The set of rings the trace fires matches the schedule's
    ``comm_plan()``: every ppermute on the pipe axis is the declared
    forward ring ``j -> j+1`` or the reverse ring ``j -> j-1`` (the latter
    also arises as the AD transpose of the forward ring), the declared
    rings actually fire, and no ring is issued under a cond branch."""
    K = n_ranks
    fwd = {(j, (j + 1) % K) for j in range(K)}
    rev = {(j, (j - 1) % K) for j in range(K)}
    out: List[Finding] = []
    n_fwd = n_rev = 0
    for site in iter_eqns(jaxpr):
        if site.prim != "ppermute" or axis_name not in _axis_names(site.eqn):
            continue
        pset = {tuple(p) for p in site.eqn.params["perm"]}
        known = False
        if pset == fwd:
            n_fwd += 1
            known = True
        if pset == rev:            # K <= 2: fwd == rev, count as both
            n_rev += 1
            known = True
        if not known:
            out.append(Finding(
                "comm.ring-match", SEV_ERROR,
                f"ppermute perm {sorted(pset)} is neither the declared "
                f"forward ring nor the reverse ring of comm_plan() "
                f"(K={K})", eqn="ppermute", path=site.where(),
                data={"perm": sorted(list(p) for p in pset)}))
        elif site.in_cond_branch():
            out.append(Finding(
                "comm.ring-match", SEV_ERROR,
                "ring ppermute issued inside a cond branch: fill/drain "
                "ranks that take the other branch deadlock the ring",
                eqn="ppermute", path=site.where()))
    if plan.fwd_ring and n_fwd == 0:
        out.append(Finding(
            "comm.ring-match", SEV_ERROR,
            f"comm_plan() declares the forward activation ring but no "
            f"forward-ring ppermute appears on axis {axis_name!r}",
            data={"n_fwd": n_fwd, "n_rev": n_rev}))
    want_rev = plan.rev_ring if expect_rev is None else expect_rev
    if want_rev and n_rev == 0:
        out.append(Finding(
            "comm.ring-match", SEV_ERROR,
            f"reverse cotangent ring expected (declared or AD-transposed) "
            f"but no reverse-ring ppermute appears on axis {axis_name!r}",
            data={"n_fwd": n_fwd, "n_rev": n_rev}))
    if not out:
        out.append(Finding(
            "comm.ring-match", SEV_INFO,
            f"rings match comm_plan(): {n_fwd} forward / {n_rev} reverse "
            f"ring ppermute(s), none under a cond branch",
            data={"n_fwd": n_fwd, "n_rev": n_rev}))
    return out


# ---------------------------------------------------------------------------
# buffer lints (the kernel_bench shape audits, generalized)
# ---------------------------------------------------------------------------
def _adjacent_pair_sites(jaxpr, a: int, b: int):
    for site, aval in iter_eqn_avals(jaxpr):
        shape = tuple(getattr(aval, "shape", ()))
        for x, y in zip(shape, shape[1:]):
            if x == a and y == b:
                yield site, shape
                break


@register_rule("buffer.score-matrix", "buffer")
def check_score_matrix(jaxpr, *, l: int, sk: int) -> List[Finding]:
    """No intermediate carries an adjacent ``(l, ctx+l)`` dim pair — the
    quadratic attention score matrix the flash kernels exist to avoid."""
    return [Finding(
        "buffer.score-matrix", SEV_ERROR,
        f"quadratic (l={l}, ctx+l={sk}) score-matrix buffer {shape} from "
        f"`{site.prim}`", eqn=site.prim, path=site.where(),
        data={"shape": list(shape), "l": l, "sk": sk})
        for site, shape in _adjacent_pair_sites(jaxpr, l, sk)]


@register_rule("buffer.repeated-kv", "buffer")
def check_kv_repeat(jaxpr, *, sk: int, hq: int, hkv: int) -> List[Finding]:
    """No GQA-repeated K/V buffer: with Hkv < Hq no intermediate may carry
    an adjacent ``(Sk, Hq)`` dim pair (K/V materialized at Hq heads)."""
    if hkv == hq:
        return []
    return [Finding(
        "buffer.repeated-kv", SEV_ERROR,
        f"GQA-repeated K/V buffer {shape} (Sk={sk}, Hq={hq}, Hkv={hkv}) "
        f"from `{site.prim}`", eqn=site.prim, path=site.where(),
        data={"shape": list(shape), "sk": sk, "hq": hq, "hkv": hkv})
        for site, shape in _adjacent_pair_sites(jaxpr, sk, hq)]


# ---------------------------------------------------------------------------
# scale lints
# ---------------------------------------------------------------------------
@register_rule("scale.eqn-budget", "scale")
def check_eqn_budget(jaxpr, *, max_eqns: int, label: str = "") -> \
        List[Finding]:
    """Total (recursive) equation count stays under a budget — the traced
    program must not secretly unroll over the work-item grid."""
    n = count_eqns(jaxpr)
    tag = f"{label}: " if label else ""
    if n > max_eqns:
        return [Finding("scale.eqn-budget", SEV_ERROR,
                        f"{tag}jaxpr has {n} equations (> budget "
                        f"{max_eqns})", data={"eqns": n,
                                              "max_eqns": max_eqns})]
    return [Finding("scale.eqn-budget", SEV_INFO,
                    f"{tag}{n} equations (budget {max_eqns})",
                    data={"eqns": n, "max_eqns": max_eqns})]


@register_rule("scale.flat-growth", "scale")
def check_flat_growth(jaxpr_small, jaxpr_big, *, slack: int = 8,
                      label: str = "") -> List[Finding]:
    """The traced program is O(1) in a scaled dimension: the big trace's
    equation count exceeds the small trace's by at most ``slack`` (only
    scan lengths and constant gather tables may change)."""
    n_small, n_big = count_eqns(jaxpr_small), count_eqns(jaxpr_big)
    tag = f"{label}: " if label else ""
    data = {"small": n_small, "big": n_big, "slack": slack}
    if n_big > n_small + slack:
        return [Finding("scale.flat-growth", SEV_ERROR,
                        f"{tag}traced program grew {n_small} -> {n_big} "
                        f"equations (> slack {slack}): not O(1) in the "
                        f"scaled dimension", data=data)]
    return [Finding("scale.flat-growth", SEV_INFO,
                    f"{tag}{n_small} -> {n_big} equations (flat within "
                    f"slack {slack})", data=data)]


def _aval_sig(var):
    aval = var.aval
    return (tuple(getattr(aval, "shape", ())),
            str(getattr(aval, "dtype", "?")))


@register_rule("scale.carry-stability", "scale")
def check_carry_stability(jaxpr) -> List[Finding]:
    """Every scan/while carry leaf keeps its shape and dtype between body
    input and output (a drifting carry means per-iteration recompilation
    or silent widening on the hot loop)."""
    out: List[Finding] = []
    for site in iter_eqns(jaxpr):
        if site.prim == "scan":
            body = site.eqn.params["jaxpr"].jaxpr
            nc = site.eqn.params["num_consts"]
            k = site.eqn.params["num_carry"]
            ins = body.invars[nc:nc + k]
            outs = body.outvars[:k]
        elif site.prim == "while":
            body = site.eqn.params["body_jaxpr"].jaxpr
            nc = site.eqn.params["body_nconsts"]
            ins = body.invars[nc:]
            outs = body.outvars
        else:
            continue
        for i, (vi, vo) in enumerate(zip(ins, outs)):
            si, so = _aval_sig(vi), _aval_sig(vo)
            if si != so:
                out.append(Finding(
                    "scale.carry-stability", SEV_ERROR,
                    f"{site.prim} carry leaf {i} drifts across the body: "
                    f"in {si[0]}/{si[1]} vs out {so[0]}/{so[1]}",
                    eqn=site.prim, path=site.where(),
                    data={"carry": i, "in": list(si), "out": list(so)}))
    return out


# ---------------------------------------------------------------------------
# donation audit (compiled executable)
# ---------------------------------------------------------------------------
@register_rule("donation.aliased", "donation")
def check_donation(fn, args: Sequence[Any], *,
                   donate_argnums: Sequence[int],
                   label: str = "") -> List[Finding]:
    """Donated arguments are actually aliased in the compiled executable:
    a donated-but-unaliased buffer silently doubles its memory (the PR 3
    donate-but-no-save bug class).  Compiles ``fn`` under jit."""
    import jax
    import jax.tree_util as jtu
    donate = tuple(donate_argnums)
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    aliased = {a.param_number
               for a in hlo.parse_input_output_aliases(compiled.as_text())}
    out: List[Finding] = []
    base = 0
    n_donated = 0
    tag = f"{label}: " if label else ""
    for i, arg in enumerate(args):
        leaves, _ = jtu.tree_flatten_with_path(arg)
        if i in donate:
            for off, (kp, _leaf) in enumerate(leaves):
                n_donated += 1
                pn = base + off
                if pn not in aliased:
                    out.append(Finding(
                        "donation.aliased", SEV_ERROR,
                        f"{tag}donated arg {i} leaf "
                        f"{jtu.keystr(kp) or '<leaf>'} (entry param {pn}) "
                        f"is NOT aliased to any output: the donation is "
                        f"dropped and the buffer duplicated",
                        data={"arg": i, "param": pn,
                              "leaf": jtu.keystr(kp)}))
        base += len(leaves)
    if not out:
        out.append(Finding(
            "donation.aliased", SEV_INFO,
            f"{tag}all {n_donated} donated leaves aliased in the compiled "
            f"executable", data={"donated_leaves": n_donated}))
    return out


# ---------------------------------------------------------------------------
# dtype lint
# ---------------------------------------------------------------------------
@register_rule("dtype.upcast", "dtype")
def check_dtype_upcasts(jaxpr, *, src: str = "bfloat16",
                        dst: str = "float32",
                        allow: Optional[int] = None) -> List[Finding]:
    """Flag silent ``convert_element_type`` upcasts (bf16 -> f32 by
    default) on the hot path.  With ``allow=None`` the count is reported
    as info (softmax/loss accumulations are legitimately f32); with an
    integer budget, exceeding it is an error naming each cast site."""
    sites = []
    for site in iter_eqns(jaxpr):
        if site.prim != "convert_element_type":
            continue
        in_dt = str(getattr(site.eqn.invars[0].aval, "dtype", "?"))
        out_dt = str(getattr(site.eqn.outvars[0].aval, "dtype", "?"))
        if in_dt == src and out_dt == dst:
            sites.append(site)
    if allow is not None and len(sites) > allow:
        out = [Finding(
            "dtype.upcast", SEV_ERROR,
            f"silent {src} -> {dst} upcast from `convert_element_type`",
            eqn="convert_element_type", path=s.where())
            for s in sites[:16]]
        out.append(Finding(
            "dtype.upcast", SEV_ERROR,
            f"{len(sites)} {src} -> {dst} upcasts exceed the allowed "
            f"budget of {allow}",
            data={"count": len(sites), "allow": allow}))
        return out
    return [Finding(
        "dtype.upcast", SEV_INFO,
        f"{len(sites)} {src} -> {dst} convert_element_type site(s)",
        data={"count": len(sites),
              "paths": sorted({s.where() for s in sites})[:10]})]


# ---------------------------------------------------------------------------
# Pallas VMEM estimator
# ---------------------------------------------------------------------------
VMEM_BUDGET_BYTES = 16 * 2 ** 20


def _pallas_footprint(eqn):
    """(kernel name, grid, per-block bytes, scratch bytes) of a
    ``pallas_call`` equation — the one place that reads Pallas internals.

    A block's bytes come from ``transformed_block_aval``, the ref the kernel
    body sees (squeezed dims gone); the name is the kernel function's."""
    gm = eqn.params["grid_mapping"]
    src = eqn.params["jaxpr"].debug_info.func_src_info or "pallas_call"
    name = eqn.params.get("name") or src.split(" ")[0]
    blocks = [math.prod(bm.transformed_block_aval.shape)
              * bm.transformed_block_aval.dtype.itemsize
              for bm in gm.block_mappings]
    scratch = sum(math.prod(a.shape) * getattr(a.dtype, "itemsize", 4)
                  for a in gm.scratch_avals)
    return name, tuple(int(g) for g in gm.grid), blocks, scratch


@register_rule("vmem.budget", "vmem")
def check_vmem(jaxpr, *, budget_bytes: int = VMEM_BUDGET_BYTES,
               double_buffer: bool = True) -> List[Finding]:
    """Static per-kernel VMEM footprint from BlockSpecs + scratch shapes
    stays under the 16 MB budget (×2 per block for the pipeline's
    double-buffering).  An estimate — Mosaic may spill or fuse — but a
    kernel failing this bound statically will not fit."""
    out: List[Finding] = []
    for site in iter_eqns(jaxpr):
        if site.prim != "pallas_call":
            continue
        name, grid, blocks, scratch = _pallas_footprint(site.eqn)
        mult = 2 if (double_buffer and grid) else 1
        block = sum(blocks) * mult
        total = block + scratch
        data = {"kernel": str(name), "grid": list(grid),
                "block_bytes": block, "scratch_bytes": scratch,
                "total_bytes": total, "budget_bytes": budget_bytes}
        if total > budget_bytes:
            out.append(Finding(
                "vmem.budget", SEV_ERROR,
                f"kernel `{name}`: estimated VMEM {total / 2**20:.2f} MiB "
                f"(blocks {block / 2**20:.2f} + scratch "
                f"{scratch / 2**20:.2f}, x{mult} buffering) exceeds the "
                f"{budget_bytes / 2**20:.0f} MiB budget",
                eqn="pallas_call", path=site.where(), data=data))
        else:
            out.append(Finding(
                "vmem.budget", SEV_INFO,
                f"kernel `{name}`: estimated VMEM {total / 2**20:.2f} MiB "
                f"within the {budget_bytes / 2**20:.0f} MiB budget",
                eqn="pallas_call", path=site.where(), data=data))
    return out
