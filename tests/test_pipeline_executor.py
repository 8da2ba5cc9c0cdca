"""The single schedule-driven tick-loop executor (ISSUE 5 tentpole): one
lax.scan interpreter of the tick-table IR runs every registered schedule —
rolled vs unrolled differential equivalence (ISSUE 1), the interleaved
virtual-stage schedule (ISSUE 2), the 1F1B explicit-backward tables +
idle-tick cache gating (ISSUE 3), and skew-buffered interleaved-1F1B
(ISSUE 5, the first IR-only schedule).

Properties:
  * differential equivalence — loss AND grads of the rolled executor match
    the Python-unrolled escape hatch (and the plain reference) on a real
    (data=1, pipe=2) mesh, for uniform and non-uniform ``slice_lens``;
  * interleaved equivalence — V=2 chunks on K=2 ranks is the SAME global
    layer->stage order as V=1 on K=4, so losses and grads must match each
    other (and the reference) layer-for-layer;
  * O(1) trace cost — the jaxpr of the pipeline body has the SAME equation
    count at M=4 and M=64, and grows only by a small constant in V (the
    chunk gather), so the DP planner's large-M schemes and deep interleaves
    stay cheap to trace/compile.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import count_eqns, raise_on_errors
from repro.analysis.rules import check_flat_growth

from test_system import _run_subprocess   # shared multi-device harness


def test_rolled_matches_unrolled_uniform_and_nonuniform():
    """K=2, D=2, M=4 (uniform) and K=2, D=2, slice_lens=(12,8,8,4): loss and
    every grad leaf allclose between the two executors, and both match the
    non-pipelined reference."""
    out = _run_subprocess(devices=2, code="""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh, use_mesh
        from repro.models.common import ModelConfig
        from repro.models import build_model
        from repro.core.pipeline import make_terapipe_loss, TeraPipeConfig
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256,
                          dtype=jnp.float32, remat=False)
        model = build_model(cfg)
        params, specs = model.init(jax.random.PRNGKey(0))
        B, S = 4, 32
        rng = jax.random.PRNGKey(7)
        batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
        mesh = make_mesh((1, 2), ("data", "pipe"))
        rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) /
                                 (1e-6 + jnp.max(jnp.abs(b))))
        lref = float(jax.jit(model.loss)(params, batch))
        gref = jax.grad(model.loss)(params, batch)
        for desc, kw in [("uniform", dict(n_token_slices=4)),
                         ("nonuniform", dict(slice_lens=(12, 8, 8, 4)))]:
            losses, grads = {}, {}
            for unroll in (False, True):
                tcfg = TeraPipeConfig(n_microbatches=2, data_axes=("data",),
                                      cache_dtype=jnp.float32, unroll=unroll,
                                      **kw)
                with use_mesh(mesh):
                    lf, _ = make_terapipe_loss(model, specs, mesh, tcfg, S, B)
                    losses[unroll] = float(jax.jit(lf)(params, batch))
                    grads[unroll] = jax.jit(jax.grad(lf))(params, batch)
            assert abs(losses[False] - losses[True]) < 1e-5 * max(
                1.0, abs(losses[True])), (desc, losses)
            gerr = max(jax.tree.leaves(
                jax.tree.map(rel, grads[False], grads[True])))
            assert gerr < 1e-5, (desc, gerr)
            # both executors also match the non-pipelined reference
            assert abs(losses[False] - lref) < 2e-5, (desc, losses, lref)
            gerr_ref = max(jax.tree.leaves(
                jax.tree.map(rel, grads[False], gref)))
            assert gerr_ref < 2e-3, (desc, gerr_ref)
            print(desc, "OK", losses, gerr, gerr_ref)
        print("EXEC-EQUIV-OK")
    """)
    assert "EXEC-EQUIV-OK" in out


def test_interleaved_matches_contiguous_and_reference():
    """V=2 on K=2 assigns global stage s = v*K + k the same contiguous layer
    run as V=1 on K=4 assigns stage k — identical math, different placement.
    Loss and every grad leaf must agree between the two schedules and with
    the non-pipelined reference, for uniform AND non-uniform slices."""
    out = _run_subprocess(devices=4, code="""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh, use_mesh
        from repro.models.common import ModelConfig
        from repro.models import build_model
        from repro.core.pipeline import make_terapipe_loss, TeraPipeConfig
        cfg = ModelConfig(name="t", family="dense", n_layers=4, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256,
                          dtype=jnp.float32, remat=False)
        model = build_model(cfg)
        params, specs = model.init(jax.random.PRNGKey(0))
        B, S = 4, 32
        rng = jax.random.PRNGKey(7)
        batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
        rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) /
                                 (1e-6 + jnp.max(jnp.abs(b))))
        lref = float(jax.jit(model.loss)(params, batch))
        gref = jax.grad(model.loss)(params, batch)
        for desc, kw in [("uniform", dict(n_token_slices=4)),
                         ("nonuniform", dict(slice_lens=(12, 8, 8, 4)))]:
            losses, grads = {}, {}
            for tag, K, V in [("K4V1", 4, 1), ("K2V2", 2, 2)]:
                mesh = make_mesh((4 // K, K), ("data", "pipe"))
                tcfg = TeraPipeConfig(n_microbatches=2, data_axes=("data",),
                                      cache_dtype=jnp.float32,
                                      virtual_stages=V, **kw)
                with use_mesh(mesh):
                    lf, _ = make_terapipe_loss(model, specs, mesh, tcfg, S, B)
                    losses[tag] = float(jax.jit(lf)(params, batch))
                    grads[tag] = jax.jit(jax.grad(lf))(params, batch)
            assert abs(losses["K2V2"] - losses["K4V1"]) < 1e-5 * max(
                1.0, abs(losses["K4V1"])), (desc, losses)
            gerr = max(jax.tree.leaves(
                jax.tree.map(rel, grads["K2V2"], grads["K4V1"])))
            assert gerr < 1e-5, (desc, gerr)
            assert abs(losses["K2V2"] - lref) < 2e-5, (desc, losses, lref)
            gerr_ref = max(jax.tree.leaves(
                jax.tree.map(rel, grads["K2V2"], gref)))
            assert gerr_ref < 2e-3, (desc, gerr_ref)
            print(desc, "OK", losses, gerr, gerr_ref)
        print("INTERLEAVE-EQUIV-OK")
    """)
    assert "INTERLEAVE-EQUIV-OK" in out


_ONE_F_ONE_B_EQUIV = """
    import jax, jax.numpy as jnp
    from repro.compat import make_mesh, use_mesh
    from repro.models.common import ModelConfig
    from repro.models import build_model
    from repro.core.pipeline import (make_terapipe_loss,
                                     make_terapipe_value_and_grad,
                                     TeraPipeConfig)
    K = {K}
    cfg = ModelConfig(name="t", family="dense", n_layers={n_layers},
                      d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                      vocab_size=256, dtype=jnp.float32, remat=False)
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    B, S = 4, 32
    rng = jax.random.PRNGKey(7)
    batch = {{"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
              "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}}
    mesh = make_mesh((1, K), ("data", "pipe"))
    rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) /
                             (1e-6 + jnp.max(jnp.abs(b))))
    lref = float(jax.jit(model.loss)(params, batch))
    gref = jax.grad(model.loss)(params, batch)
    for desc, kw in [("uniform", dict(n_token_slices=4)),
                     ("nonuniform", dict(slice_lens=(12, 8, 8, 4)))]:
        with use_mesh(mesh):
            tc = TeraPipeConfig(n_microbatches=2, data_axes=("data",),
                                cache_dtype=jnp.float32, **kw)
            lf, _ = make_terapipe_loss(model, specs, mesh, tc, S, B)
            lc, gc = jax.jit(jax.value_and_grad(lf))(params, batch)
            t1 = TeraPipeConfig(n_microbatches=2, data_axes=("data",),
                                cache_dtype=jnp.float32, schedule="1f1b",
                                **kw)
            vg, _ = make_terapipe_value_and_grad(model, specs, mesh, t1, S, B)
            l1, g1 = jax.jit(vg)(params, batch)
        # 1f1b vs the contiguous (autodiff-backward) executor
        assert abs(float(l1) - float(lc)) < 1e-5 * max(
            1.0, abs(float(lc))), (desc, float(l1), float(lc))
        gerr = max(jax.tree.leaves(jax.tree.map(rel, g1, gc)))
        assert gerr < 1e-4, (desc, gerr)
        # and vs the non-pipelined reference
        assert abs(float(l1) - lref) < 2e-5, (desc, float(l1), lref)
        gerr_ref = max(jax.tree.leaves(jax.tree.map(rel, g1, gref)))
        assert gerr_ref < 2e-3, (desc, gerr_ref)
        print(desc, "OK", float(l1), float(lc), gerr, gerr_ref)
    print("1F1B-EQUIV-OK")
"""


@pytest.mark.parametrize("K,n_layers", [(2, 2), (4, 4)])
def test_one_f_one_b_matches_contiguous_and_reference(K, n_layers):
    """The explicit per-unit-vjp backward path of the unified executor
    (schedule='1f1b'): loss and every grad leaf match both the contiguous
    autodiff-backward path and the non-pipelined reference, on K=2 and
    K=4, uniform AND non-uniform (DP-style) slices, D=2 microbatches."""
    out = _run_subprocess(devices=K,
                          code=_ONE_F_ONE_B_EQUIV.format(K=K,
                                                         n_layers=n_layers))
    assert "1F1B-EQUIV-OK" in out


_ALL_SCHEDULES_EQUIV = """
    import jax, jax.numpy as jnp
    from repro.compat import make_mesh, use_mesh
    from repro.models.common import ModelConfig
    from repro.models import build_model
    from repro.core.pipeline import (make_terapipe_value_and_grad,
                                     TeraPipeConfig)
    K = {K}
    cfg = ModelConfig(name="t", family="dense", n_layers={n_layers},
                      d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                      vocab_size=256, dtype=jnp.float32, remat=False)
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    B, S = 4, 32
    rng = jax.random.PRNGKey(7)
    batch = {{"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
              "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}}
    mesh = make_mesh((1, K), ("data", "pipe"))
    rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) /
                             (1e-6 + jnp.max(jnp.abs(b))))
    lref = float(jax.jit(model.loss)(params, batch))
    gref = jax.grad(model.loss)(params, batch)
    for sched, V in [("contiguous", 1), ("interleaved", 2), ("1f1b", 1),
                     ("interleaved-1f1b", 2), ("zb-h1", 1)]:
        for desc, kw in [("uniform", dict(n_token_slices=4)),
                         ("nonuniform", dict(slice_lens=(12, 8, 8, 4)))]:
            with use_mesh(mesh):
                tc = TeraPipeConfig(n_microbatches=2, data_axes=("data",),
                                    cache_dtype=jnp.float32, schedule=sched,
                                    virtual_stages=V, **kw)
                vg, _ = make_terapipe_value_and_grad(model, specs, mesh, tc,
                                                     S, B)
                l, g = jax.jit(vg)(params, batch)
            assert abs(float(l) - lref) < 2e-5, (sched, desc, float(l), lref)
            gerr = max(jax.tree.leaves(jax.tree.map(rel, g, gref)))
            assert gerr < 2e-3, (sched, desc, gerr)
            print(sched, desc, "OK", float(l), gerr)
    print("ALL-SCHEDULES-EQUIV-OK")
"""


@pytest.mark.parametrize("K,n_layers", [(2, 4), (4, 8)])
def test_unified_executor_runs_every_schedule(K, n_layers):
    """ISSUE 5/6 acceptance: the ONE executor entry point
    (make_terapipe_value_and_grad) runs every registered schedule —
    including skew-buffered interleaved-1F1B, whose wrap-around chunk
    handoffs ride the rings through K-tick skew buffers, and zero-bubble
    zb-h1, whose typed B/W units split each backward into an immediate
    input-cotangent tick and a deferred weight-grad tick — and loss +
    every grad leaf match the non-pipelined reference on K=2 and K=4,
    uniform AND non-uniform DP slices."""
    out = _run_subprocess(devices=K,
                          code=_ALL_SCHEDULES_EQUIV.format(
                              K=K, n_layers=n_layers))
    assert "ALL-SCHEDULES-EQUIV-OK" in out


_PIPE4_GEOMETRY = """
    import jax, jax.numpy as jnp
    from repro.compat import make_mesh, use_mesh
    from repro.configs import get_config
    from repro.models import build_model
    from repro.core.pipeline import (make_terapipe_value_and_grad,
                                     TeraPipeConfig)
    cfg = get_config("gpt3-1b", smoke=True).replace(dtype=jnp.float32)
    assert not cfg.tie_embeddings and cfg.n_layers % 4 == 0
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    B, S = 4, 128
    rng = jax.random.PRNGKey(11)
    batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
    mesh = make_mesh((1, 4), ("data", "pipe"))
    rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) /
                             (1e-6 + jnp.max(jnp.abs(b))))
    lref, gref = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    tc = TeraPipeConfig(n_token_slices=8, n_microbatches=4,
                        data_axes=("data",), cache_dtype=jnp.float32,
                        schedule="contiguous", use_kernel=True)
    with use_mesh(mesh):
        vg, _ = make_terapipe_value_and_grad(model, specs, mesh, tc, S, B)
        l, g = jax.jit(vg)(params, batch)
    assert abs(float(l) - float(lref)) < 2e-5, (float(l), float(lref))
    gerr = max(jax.tree.leaves(jax.tree.map(rel, g, gref)))
    assert gerr < 2e-3, gerr
    print("PIPE4-GEOMETRY-OK", float(l), gerr)
"""


def test_pipe4_cell_geometry_matches_model_loss():
    """The gpt3-1b.pipe4-2k cell's geometry at the gpt3 smoke widths
    (untied head, float32): the contiguous schedule on K=4 ranks, 4
    microbatches of one row, 8 equal token slices, attention through the
    flash kernels (interpret mode) at traced ``ctx``.  Loss and every grad
    leaf match the non-pipelined ``model.loss`` within the tolerances of
    ``test_unified_executor_runs_every_schedule``: everything is float32,
    so the gaps are round-off from the order of summation across slices,
    ticks and the kernels' online softmax."""
    out = _run_subprocess(devices=4, code=_PIPE4_GEOMETRY)
    assert "PIPE4-GEOMETRY-OK" in out


def test_idle_ticks_leave_caches_bit_identical():
    """Satellite bugfix audit: cache mutation is gated on ``valid``, so
    fill/drain (and appended extra) idle ticks are exact cache no-ops.
    Before the fix the drain ticks of a D=2, M=1 run zeroed every rank's
    cache except the last (clamped idle units aliased a fresh unit), so the
    final caches (a) no longer matched the reference prefill K/V of the
    last microbatch and (b) changed when pure-idle ticks were appended."""
    out = _run_subprocess(devices=2, code="""
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.compat import make_mesh, use_mesh
        from repro.models.common import ModelConfig
        from repro.models import build_model
        from repro.core.pipeline import make_terapipe_caches_fn, TeraPipeConfig
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                          n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=128,
                          dtype=jnp.float32, remat=False)
        model = build_model(cfg)
        params, specs = model.init(jax.random.PRNGKey(0))
        B, S, D = 4, 16, 2
        rng = jax.random.PRNGKey(5)
        batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
        mesh = make_mesh((1, 2), ("data", "pipe"))
        caches = {}
        for extra in (0, 3):
            tcfg = TeraPipeConfig(n_token_slices=1, n_microbatches=D,
                                  data_axes=("data",),
                                  cache_dtype=jnp.float32, extra_ticks=extra)
            with use_mesh(mesh):
                cf = make_terapipe_caches_fn(model, specs, mesh, tcfg, S, B)
                caches[extra] = jax.tree.map(np.asarray,
                                             jax.jit(cf)(params, batch))
        # (a) appended idle ticks: bit-identical caches
        for a, b in zip(jax.tree.leaves(caches[0]), jax.tree.leaves(caches[3])):
            np.testing.assert_array_equal(a, b)
        # (b) the final cache is the K/V of the LAST microbatch (drain idles
        # must not have zeroed it) == reference prefill on those rows
        last = {k: v[B // D:] for k, v in batch.items()}
        _, ref = model.prefill(params, last, S)
        for got, want in zip(jax.tree.leaves(caches[0]),
                             jax.tree.leaves(ref)):
            assert np.max(np.abs(want)) > 0          # the audit has teeth
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
        print("IDLE-TICK-CACHES-OK")
    """)
    assert "IDLE-TICK-CACHES-OK" in out


def _trace_loss(M: int, unroll: bool, virtual_stages: int = 1,
                n_layers: int = 2):
    from repro.compat import make_mesh, use_mesh
    from repro.core.pipeline import TeraPipeConfig, make_terapipe_loss
    from repro.models import build_model
    from repro.models.common import ModelConfig
    cfg = ModelConfig(name="t", family="dense", n_layers=n_layers, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=128,
                      dtype=jnp.float32, remat=False)
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    B, S = 2, 8 * M
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}
    mesh = make_mesh((1, 1), ("data", "pipe"))
    tcfg = TeraPipeConfig(n_token_slices=M, n_microbatches=1,
                          data_axes=("data",), cache_dtype=jnp.float32,
                          unroll=unroll, virtual_stages=virtual_stages)
    with use_mesh(mesh):
        lf, _ = make_terapipe_loss(model, specs, mesh, tcfg, S, B)
        return jax.make_jaxpr(lf)(params, batch)


def test_rolled_jaxpr_size_independent_of_M():
    """M=64 traces without unrolling 64 tick bodies: the rolled executor's
    jaxpr equation count is identical at M=4 and M=64 (the tick program is
    traced once; only the scan length changes)."""
    n4 = count_eqns(_trace_loss(4, unroll=False).jaxpr)
    n64 = count_eqns(_trace_loss(64, unroll=False).jaxpr)
    assert n64 <= n4 + 8, (n4, n64)    # O(1) in M (slack for reassembly)
    # sanity: the unrolled escape hatch DOES grow with M
    u4 = count_eqns(_trace_loss(4, unroll=True).jaxpr)
    u8 = count_eqns(_trace_loss(8, unroll=True).jaxpr)
    assert u8 > u4 + 4 and u4 > n4, (u4, u8, n4)


def test_rolled_jaxpr_size_independent_of_V():
    """Deeper interleaves do not grow the traced program: the one tick body
    gathers its chunk with dynamic_index (shape-stable in V), so V=2 and
    V=8 trace to the SAME equation count (n_layers=8 keeps the padding at 0
    for every V — padding, not the schedule, is the only shape-dependence),
    and the whole V>1 machinery is a flat constant over the V=1 trace
    (~250 eqns of chunk gather/scatter + rank-major relayout)."""
    n1 = count_eqns(_trace_loss(4, unroll=False, n_layers=8).jaxpr)
    n2 = count_eqns(_trace_loss(4, unroll=False, n_layers=8,
                                 virtual_stages=2).jaxpr)
    n8 = count_eqns(_trace_loss(4, unroll=False, n_layers=8,
                                 virtual_stages=8).jaxpr)
    assert n8 <= n2 + 8, (n2, n8)      # O(1) in V
    assert n2 <= n1 + 300, (n1, n2)    # chunk machinery = flat constant


def _trace_vg(M: int, schedule: str, virtual_stages: int = 1, D: int = 1,
              n_layers: int = 2):
    """Jaxpr of the full loss+grad program of the unified executor (any
    schedule) on a (1, 1) mesh — trace cost needs no devices."""
    from repro.compat import make_mesh, use_mesh
    from repro.core.pipeline import (TeraPipeConfig,
                                     make_terapipe_value_and_grad)
    from repro.models import build_model
    from repro.models.common import ModelConfig
    cfg = ModelConfig(name="t", family="dense", n_layers=n_layers, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=128,
                      dtype=jnp.float32, remat=False)
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    B, S = 2 * D, 8 * M
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}
    mesh = make_mesh((1, 1), ("data", "pipe"))
    tcfg = TeraPipeConfig(n_token_slices=M, n_microbatches=D,
                          data_axes=("data",), cache_dtype=jnp.float32,
                          schedule=schedule, virtual_stages=virtual_stages)
    with use_mesh(mesh):
        vg, _ = make_terapipe_value_and_grad(model, specs, mesh, tcfg, S, B)
        return jax.make_jaxpr(vg)(params, batch)


def test_vg_jaxpr_size_independent_of_DMV_every_schedule():
    """ISSUE 5 acceptance: the traced loss+grad program of the ONE executor
    stays O(1) in D·M·V for every registered schedule — only the scan
    length and the (constant) gather tables change.  The explicit-bwd
    schedules' per-unit-vjp tick must not re-trace per item either.
    Enforced through the analyzer's scale.flat-growth rule (ISSUE 8): the
    same pass `make lint-ir` runs over the registry matrix."""
    for sched, V in [("contiguous", 1), ("interleaved", 2), ("1f1b", 1),
                     ("interleaved-1f1b", 2), ("zb-h1", 1)]:
        small = _trace_vg(4, sched, V, D=1, n_layers=4)
        raise_on_errors(
            check_flat_growth(small, _trace_vg(32, sched, V, D=1,
                                               n_layers=4),
                              label=f"{sched} M 4->32")
            + check_flat_growth(small, _trace_vg(4, sched, V, D=4,
                                                 n_layers=4),
                                label=f"{sched} D 1->4"), context=sched)
    # deeper interleaves of the explicit-bwd table are also flat
    raise_on_errors(check_flat_growth(
        _trace_vg(4, "interleaved-1f1b", 2, n_layers=4),
        _trace_vg(4, "interleaved-1f1b", 4, n_layers=4),
        label="interleaved-1f1b V 2->4"))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_one_device_executor_matches_model_loss(use_kernel):
    """On one device the executor runs on a (data=1, pipe=1) mesh — the
    path ``train --mode terapipe`` takes on a single chip — and its loss
    and grads match ``model.loss`` with the jnp attention and with the
    Pallas kernels."""
    from repro.compat import make_mesh, use_mesh
    from repro.core.pipeline import (TeraPipeConfig,
                                     make_terapipe_value_and_grad)
    from repro.models import build_model
    from repro.models.common import ModelConfig

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype=jnp.float32, remat=False)
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    B, S = 2, 32
    rng = jax.random.PRNGKey(3)
    batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
    mesh = make_mesh((1, 1), ("data", "pipe"))
    tcfg = TeraPipeConfig(n_token_slices=4, cache_dtype=jnp.float32,
                          use_kernel=use_kernel)
    with use_mesh(mesh):
        vg, _ = make_terapipe_value_and_grad(model, specs, mesh, tcfg, S, B)
        loss, grads = jax.jit(vg)(params, batch)
    lref, gref = jax.value_and_grad(model.loss)(params, batch)
    assert abs(float(loss) - float(lref)) < 2e-5, (float(loss), float(lref))
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(gref)):
        err = float(jnp.max(jnp.abs(g - r)) / (1e-6 + jnp.max(jnp.abs(r))))
        assert err < 2e-3, err
