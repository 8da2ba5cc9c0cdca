"""DP scheduler (paper Algorithm 1 + §3.4) correctness."""
import numpy as np
import pytest

from _hyp import given, settings, st   # hypothesis or skip-stub (tests/_hyp.py)

from repro.core.dp import (brute_force_slicing, joint_batch_token,
                           optimal_slicing, pad_slice_count)
from repro.core.cost_model import (AnalyticCostModel, BilinearFitCostModel,
                                   TPU_V5E, V100_AWS)
from repro.core.simulator import (_lockstep_loop, _lockstep_total, eq5_latency, simulate)
from repro.core.schedule import SlicingScheme
from repro.configs import get_config


def _rand_cost(L, seed, monotone=True):
    rng = np.random.default_rng(seed)
    T = rng.uniform(0.5, 2.0, (L + 1, L))
    if monotone:  # longer slices / more context cost more (physical)
        T += 0.05 * np.arange(L + 1)[:, None] + 0.02 * np.arange(L)[None, :]
    return lambda l, c: float(T[l, c])


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("K", [2, 4, 7])
def test_dp_matches_bruteforce(seed, K):
    L = 9
    t = _rand_cost(L, seed)
    dp = optimal_slicing(t, L, K, eps=1e-12)
    bf = brute_force_slicing(t, L, K)
    assert dp.latency == pytest.approx(bf.latency, rel=1e-12)
    assert sum(dp.slices) == L


@given(seed=st.integers(0, 10_000), K=st.integers(2, 12),
       L=st.integers(2, 10))
@settings(max_examples=40, deadline=None)
def test_dp_never_worse_than_uniform(seed, K, L):
    """Property: the DP solution is at least as good as every uniform split."""
    t = _rand_cost(L, seed)
    dp = optimal_slicing(t, L, K, eps=1e-12)
    for m in range(1, L + 1):
        if L % m == 0:
            uni = eq5_latency([L // m] * m, K, t)
            assert dp.latency <= uni + 1e-9


def test_epsilon_gap_bound():
    """Gap between ε-grid DP and exact is ≤ K·ε (paper's bound)."""
    L, K, eps = 10, 4, 0.05
    t = _rand_cost(L, 3)
    exact = optimal_slicing(t, L, K, eps=1e-12)
    approx = optimal_slicing(t, L, K, eps=eps)
    assert approx.latency <= exact.latency + K * eps + 1e-9


def test_eps_coarser_than_cost_range_still_feasible():
    """Regression: when eps exceeds the whole cost range (microsecond-scale
    analytic costs, default eps=1e-4) the ε-grid used to collapse to ONE
    infeasible t_max candidate and the DP returned no slices (crashing
    train --dp-plan).  The max achievable value must stay a candidate."""
    cm = AnalyticCostModel(get_config("qwen3-0.6b", smoke=True), TPU_V5E,
                           layers_per_stage=2)
    dp = optimal_slicing(cm, 64, 4, granularity=4)   # costs span ~1e-7 s
    assert dp.slices, dp
    assert sum(dp.slices) == 64
    assert np.isfinite(dp.latency)


def test_granularity():
    cm = AnalyticCostModel(get_config("gpt3-1b"), V100_AWS, layers_per_stage=2)
    dp = optimal_slicing(cm, 2048, 8, granularity=256)
    assert sum(dp.slices) == 2048
    assert all(l % 256 == 0 for l in dp.slices)


def test_early_stop_prunes():
    cm = AnalyticCostModel(get_config("gpt3-1b"), V100_AWS, layers_per_stage=2)
    dp = optimal_slicing(cm, 2048, 24, granularity=128)
    # t_max enumeration must terminate early, not scan all O((L/g)^2) values
    assert dp.n_tmax_evaluated < (2048 // 128) ** 2


def test_joint_batch_token_knapsack_paper_objective():
    cfg = get_config("gpt3-13b")
    def per_b(b):
        return AnalyticCostModel(cfg, V100_AWS, layers_per_stage=2, batch=b)
    res = joint_batch_token(per_b, L=512, B=8, K=8, granularity=64,
                            batch_candidates=[1, 2, 4, 8], objective="paper")
    assert sum(b for b, _ in res.scheme) == 8
    for b, slices in res.scheme:
        assert sum(slices) == 512
    # paper objective == sum of per-split Eq.5 latencies
    total = sum(eq5_latency(list(sl), 8, per_b(b)) for b, sl in res.scheme)
    assert res.latency == pytest.approx(total, rel=1e-9)


def test_joint_pipeline_objective_matches_simulator_and_dominates():
    """The global-t_max (beyond-paper) objective equals the true concatenated
    pipeline latency and is never worse than the paper's additive objective."""
    cfg = get_config("gpt3-13b")
    K, L, B = 8, 512, 8
    def per_b(b):
        return AnalyticCostModel(cfg, V100_AWS, layers_per_stage=2, batch=b)
    pipe = joint_batch_token(per_b, L, B, K, granularity=64,
                             batch_candidates=[1, 2, 4, 8])
    paper = joint_batch_token(per_b, L, B, K, granularity=64,
                              batch_candidates=[1, 2, 4, 8], objective="paper")
    assert sum(b for b, _ in pipe.scheme) == B
    # objective value == async simulator on the concatenated schedule
    sch = SlicingScheme.from_dp(L, B, pipe.scheme)
    sim = simulate(sch, K, lambda b, l, c: per_b(b)(l, c))
    assert pipe.latency == pytest.approx(sim, rel=1e-9)
    # the paper scheme, evaluated truthfully, is never better
    sch_p = SlicingScheme.from_dp(L, B, paper.scheme)
    sim_p = simulate(sch_p, K, lambda b, l, c: per_b(b)(l, c))
    assert pipe.latency <= sim_p + 1e-12


def test_bilinear_fit_under_2pct():
    """The paper reports <2% relative error for the Eq. 9 estimator."""
    cm = AnalyticCostModel(get_config("gpt3-13b"), V100_AWS,
                           layers_per_stage=2)
    fit = BilinearFitCostModel.fit(cm, 1024)
    assert fit.relative_error(cm, 1024) < 0.02


def test_simulator_matches_eq5():
    cm = AnalyticCostModel(get_config("gpt3-1b"), TPU_V5E, layers_per_stage=2)
    slices = [512, 512, 512, 512]
    sch = SlicingScheme.from_dp(2048, 1, [(1, slices)])
    sim = simulate(sch, 8, lambda b, l, c: cm(l, c))
    assert sim == pytest.approx(eq5_latency(slices, 8, cm), rel=1e-12)


@pytest.mark.parametrize("K", [1, 2, 5, 8])
def test_lockstep_vectorized_matches_loop(K):
    """The numpy-broadcast lockstep tick sum equals the scalar reference
    loop (random durations, random per-stage slowdowns), like _cost_matrix's
    vectorization in PR 1."""
    rng = np.random.default_rng(K)
    for n in (1, 7, 23):
        items = list(rng.uniform(0.5, 2.0, n))
        slow = rng.uniform(1.0, 1.8, K)
        loop = _lockstep_loop(items, K, slow)
        vec = _lockstep_total(items, K, 1, slow)
        assert vec == pytest.approx(loop, rel=1e-14), (K, n)


def test_planner_virtual_stages_improves_bubble_dominated():
    """Planning WITH the interleave-aware objective (bubble weight (K-1)/V)
    must beat the V=1 plan when both are executed on the V=2 interleaved
    schedule — the paper shape K=24 on gpt3-1b is bubble-dominated enough
    that the optima differ (the V-aware plan takes fewer, longer slices)."""
    cm = AnalyticCostModel(get_config("gpt3-1b"), V100_AWS, layers_per_stage=1)
    K, L, g, V = 24, 2048, 128, 2
    p1 = optimal_slicing(cm, L, K, granularity=g)
    p2 = optimal_slicing(cm, L, K, granularity=g, virtual_stages=V)
    assert sum(p2.slices) == L
    assert len(p2.slices) < len(p1.slices), (p1.slices, p2.slices)
    t = lambda b, l, c: cm(l, c)
    # replicate each plan over K batch splits so the item count divides K
    lat = {}
    for name, p in (("v1", p1), ("v2", p2)):
        sch = SlicingScheme.from_dp(L, K, [(1, p.slices)] * K)
        lat[name] = simulate(sch, K, t, discipline="interleaved",
                             virtual_stages=V)
    assert lat["v2"] < lat["v1"], lat
    # V=1 objective/behavior is bit-identical to the original Eq. 5 planner
    assert optimal_slicing(cm, L, K, granularity=g,
                           virtual_stages=1).latency == p1.latency


def test_pad_slice_count_restores_executability():
    """Interleaved runs need M % K == 0; the post-pass splits the largest
    slices at granularity-aligned midpoints without raising t_max."""
    slices = [704, 688, 656]                    # the paper's 3-slice scheme
    out = pad_slice_count(slices, 4, granularity=8)
    assert len(out) % 4 == 0
    assert sum(out) == sum(slices)
    assert max(out) <= max(slices)              # splitting never raises t_max
    assert all(l % 8 == 0 and l >= 8 for l in out)
    # already divisible: untouched
    assert pad_slice_count([512, 512], 2, granularity=8) == [512, 512]
    with pytest.raises(ValueError):
        pad_slice_count([8, 8, 8], 4, granularity=8)   # nothing splittable


def test_joint_virtual_stages_never_worse():
    """The joint knapsack under the V-aware objective is <= the V=1 scheme
    evaluated under the same objective (optimality), and its latency field
    reflects the shrunken bubble weight."""
    cfg = get_config("gpt3-13b")
    K, L, B, V = 8, 512, 8, 2
    def per_b(b):
        return AnalyticCostModel(cfg, V100_AWS, layers_per_stage=2, batch=b)
    r1 = joint_batch_token(per_b, L, B, K, granularity=64,
                           batch_candidates=[1, 2, 4, 8])
    r2 = joint_batch_token(per_b, L, B, K, granularity=64,
                           batch_candidates=[1, 2, 4, 8], virtual_stages=V)
    assert sum(b for b, _ in r2.scheme) == B
    # evaluate r1's scheme under the V-aware objective: sum term + w*t_max
    def obj_v(scheme):
        total, tmax = 0.0, 0.0
        for b, sl in scheme:
            cm = per_b(b)
            c = 0
            for l in sl:
                ti = cm(l, c)
                total += ti
                tmax = max(tmax, ti)
                c += l
        return total + (K - 1) / V * tmax
    assert r2.latency <= obj_v(r1.scheme) + 1e-12
    assert r2.latency <= r1.latency + 1e-12


def test_lockstep_geq_async():
    """Lockstep (TPU SPMD) can never beat async stage progression."""
    cm = AnalyticCostModel(get_config("gpt3-1b"), TPU_V5E, layers_per_stage=2)
    sch = SlicingScheme.from_dp(2048, 2, [(1, [1024, 512, 512]),
                                          (1, [512] * 4)])
    t = lambda b, l, c: cm(l, c)
    assert simulate(sch, 8, t, discipline="lockstep") >= \
        simulate(sch, 8, t, discipline="async") - 1e-12


def test_straggler_replan_improves():
    """Re-solving the DP with a slowdown-aware cost model must not hurt."""
    cfg = get_config("gpt3-13b")
    K = 8
    slow = np.ones(K); slow[3] = 1.5            # one slow stage
    base = AnalyticCostModel(cfg, V100_AWS, layers_per_stage=2)
    worst = AnalyticCostModel(cfg, V100_AWS, layers_per_stage=2,
                              stage_slowdown=1.5)
    naive = optimal_slicing(base, 1024, K, granularity=64)
    replan = optimal_slicing(worst, 1024, K, granularity=64)
    t = lambda b, l, c: base(l, c)
    sch_n = SlicingScheme.from_dp(1024, 1, [(1, naive.slices)])
    sch_r = SlicingScheme.from_dp(1024, 1, [(1, replan.slices)])
    lat_n = simulate(sch_n, K, t, stage_slowdown=slow)
    lat_r = simulate(sch_r, K, t, stage_slowdown=slow)
    assert lat_r <= lat_n * 1.05   # replan never significantly worse


def test_hardware_for_device_kind():
    """``train --dp-plan`` plans with the spec of the device it runs on;
    a kind with no spec is an error, never a silent default."""
    from repro.core.cost_model import hardware_for
    assert hardware_for("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="no HardwareSpec"):
        hardware_for("cpu")
