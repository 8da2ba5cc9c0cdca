"""Fused flash-backward Pallas kernels vs the reference vjp (ISSUE 4).

Gradchecks run the WHOLE custom_vjp (fwd saves (O, lse); bwd runs the dQ and
dK/dV kernels) against jax.vjp of the dense reference, in interpret mode,
across GQA ratios, ragged non-128-multiple slice lengths, ctx=0 / ctx>0 and
fp32/bf16 — plus traced-ctx equivalence (the scalar-prefetch operand the
pipeline executor drives) and an end-to-end check that the unified executor
under every registered schedule with ``use_kernel=True`` reproduces the
reference loss+grads.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.kernels import ops
from repro.kernels.ref import terapipe_attention_ref

from test_system import _run_subprocess   # shared multi-device harness

pytestmark = pytest.mark.kernels


def _qkvg(b, l, ctx, hq, hkv, hd, dtype, sk_extra=0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    sk = ctx + l + sk_extra
    return (jax.random.normal(ks[0], (b, l, hq, hd), dtype),
            jax.random.normal(ks[1], (b, sk, hkv, hd), dtype),
            jax.random.normal(ks[2], (b, sk, hkv, hd), dtype),
            jax.random.normal(ks[3], (b, l, hq, hd), dtype))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("b,l,ctx,hq,hkv,hd", [
    (1, 8, 0, 1, 1, 64),       # tiny, no context, Hq/Hkv = 1
    (2, 64, 64, 4, 4, 64),     # ctx == l, dense heads
    (1, 96, 160, 4, 1, 64),    # GQA 4x, ragged 96 (the DP planner shape)
    (2, 33, 7, 4, 1, 32),      # GQA 4x, tiny odd shapes
    (1, 100, 0, 4, 4, 64),     # ragged, pure causal
    (1, 2048, 0, 2, 1, 32),    # default blocks: 2 x 2 tiles of 1024
])
def test_fused_vjp_matches_reference(b, l, ctx, hq, hkv, hd, dtype, tol):
    q, k, v, g = _qkvg(b, l, ctx, hq, hkv, hd, dtype)
    out, vjp = jax.vjp(
        lambda q, k, v: ops.terapipe_attention(q, k, v, ctx_len=ctx), q, k, v)
    out_r, vjp_r = jax.vjp(
        lambda q, k, v: terapipe_attention_ref(q, k, v, ctx), q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)
    for got, want, name in zip(vjp(g), vjp_r(g), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


def test_fused_vjp_stale_cache_tail():
    """Sk > ctx + l (the executors' fixed-size cache): keys at and beyond
    ctx + l are excluded from O and get exactly zero dK/dV."""
    q, k, v, g = _qkvg(1, 33, 17, 8, 2, 32, jnp.float32, sk_extra=23)
    _, vjp = jax.vjp(
        lambda q, k, v: ops.terapipe_attention(q, k, v, ctx_len=17), q, k, v)
    _, vjp_r = jax.vjp(
        lambda q, k, v: terapipe_attention_ref(q, k, v, 17), q, k, v)
    for got, want, name in zip(vjp(g), vjp_r(g), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4, err_msg=name)
    dk = vjp(g)[1]
    assert float(jnp.abs(dk[:, 17 + 33:]).max()) == 0.0


def test_traced_ctx_matches_static():
    """ctx as a traced int32 (the scalar-prefetch path the executors run)
    matches the static-offset call, for values AND gradients, from ONE
    jit trace."""
    q, k, v, g = _qkvg(1, 16, 48, 4, 2, 32, jnp.float32)

    @jax.jit
    def dyn(q, k, v, c):
        out, vjp = jax.vjp(
            lambda q, k, v: ops.terapipe_attention(q, k, v, ctx_len=c),
            q, k, v)
        return out, vjp(g)

    for c in (0, 5, 48):
        out_d, grads_d = dyn(q, k, v, jnp.int32(c))
        out_s, vjp_s = jax.vjp(
            lambda q, k, v: terapipe_attention_ref(q, k, v, c), q, k, v)
        np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_s),
                                   rtol=2e-4, atol=2e-4)
        for got, want in zip(grads_d, vjp_s(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4)


def test_default_blocks_ragged_traced_ctx_gqa_tail():
    """Shape-chosen blocks (128-row query blocks, two 1024-wide key blocks)
    at a ragged l=96 slice, GQA rep 2, a stale cache tail, and ctx traced:
    the forward and the fused VJP match the reference."""
    l, hq, hkv, hd, sk = 96, 4, 2, 32, 2048
    q, k, v, g = _qkvg(1, l, sk - l, hq, hkv, hd, jnp.float32, seed=3)

    @jax.jit
    def dyn(q, k, v, c):
        out, vjp = jax.vjp(
            lambda q, k, v: ops.terapipe_attention(q, k, v, ctx_len=c),
            q, k, v)
        return out, vjp(g)

    for c in (0, 1000, 1900):               # tail of 1952, 952 and 52 keys
        out_d, grads_d = dyn(q, k, v, jnp.int32(c))
        out_r, vjp_r = jax.vjp(
            lambda q, k, v: terapipe_attention_ref(q, k, v, c), q, k, v)
        np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_r),
                                   rtol=2e-4, atol=2e-4)
        for got, want, name in zip(grads_d, vjp_r(g), ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4, err_msg=name)
        assert float(jnp.abs(grads_d[1][:, c + l:]).max(initial=0.0)) == 0.0


def test_fused_vjp_jaxpr_clean_via_analyzer():
    """The fused op's full vjp jaxpr passes the repro.analysis buffer rules
    (no (l, ctx+l) score matrix, no GQA-repeated (Sk, Hq) K/V) and the
    Pallas VMEM estimator sees all three kernels under the 16 MiB budget —
    the same rules `make lint-ir` runs over the schedule matrix."""
    from repro.analysis import errors, raise_on_errors
    from repro.analysis.rules import (check_kv_repeat, check_score_matrix,
                                      check_vmem)
    l, ctx, hq, hkv, hd = 96, 160, 4, 1, 64
    sk = ctx + l
    q, k, v, g = _qkvg(1, l, ctx, hq, hkv, hd, jnp.float32)

    def grads(q, k, v):
        out, vjp = jax.vjp(
            lambda q, k, v: ops.terapipe_attention(q, k, v, ctx_len=ctx),
            q, k, v)
        return vjp(g)

    jaxpr = jax.make_jaxpr(grads)(q, k, v)
    raise_on_errors(check_score_matrix(jaxpr, l=l, sk=sk)
                    + check_kv_repeat(jaxpr, sk=sk, hq=hq, hkv=hkv),
                    context="fused-vjp")
    vmem = check_vmem(jaxpr)
    kernels = {f.data["kernel"] for f in vmem}
    assert not errors(vmem), vmem
    assert {tracing.FLASH_FWD, tracing.FLASH_BWD_DQ,
            tracing.FLASH_BWD_DKV} <= kernels, kernels


def test_custom_vjp_closure_is_cached():
    """The custom_vjp wrapper is built once per static config (satellite:
    a per-call closure defeats jit caching and retraces every call)."""
    f1 = ops._make_flash_attention(128, 128, True)
    f2 = ops._make_flash_attention(128, 128, True)
    assert f1 is f2
    assert f1 is not ops._make_flash_attention(128, 256, True)


def test_executors_with_kernel_match_reference():
    """The unified executor under EVERY registered schedule (autodiff-bwd
    contiguous/interleaved + explicit-bwd 1f1b/interleaved-1f1b +
    split-bwd zb-h1) with ``use_kernel=True`` routes attention through the
    traced-ctx Pallas kernels (attn_sliced_dyn) and reproduces the
    reference loss AND grads — K=2 and K=4, uniform and non-uniform
    slices, GQA heads."""
    out = _run_subprocess(devices=4, code="""
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh, use_mesh
        from repro.models.common import ModelConfig
        from repro.models import build_model
        from repro.core.pipeline import (TeraPipeConfig,
                                         make_terapipe_value_and_grad)
        cfg = ModelConfig(name="t", family="dense", n_layers=4, d_model=64,
                          n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
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
        for K in (2, 4):
            mesh = make_mesh((1, K), ("data", "pipe"))
            for sched, V in (("contiguous", 1), ("interleaved", 2),
                             ("1f1b", 1), ("interleaved-1f1b", 2),
                             ("zb-h1", 1)):
                for desc, kw in [("uniform", dict(n_token_slices=4)),
                                 ("nonuniform",
                                  dict(slice_lens=(12, 8, 8, 4)))]:
                    tcfg = TeraPipeConfig(n_microbatches=2,
                                          data_axes=("data",),
                                          cache_dtype=jnp.float32,
                                          schedule=sched, use_kernel=True,
                                          virtual_stages=V,
                                          **kw)
                    with use_mesh(mesh):
                        vg, _ = make_terapipe_value_and_grad(
                            model, specs, mesh, tcfg, S, B)
                        loss, grads = jax.jit(vg)(params, batch)
                    gerr = max(jax.tree.leaves(
                        jax.tree.map(rel, grads, gref)))
                    assert abs(float(loss) - lref) < 2e-5, (
                        K, sched, desc, float(loss), lref)
                    assert gerr < 2e-3, (K, sched, desc, gerr)
                    print("OK", K, sched, desc, float(loss), gerr)
        print("KERNEL-EXEC-EQUIV-OK")
    """)
    assert "KERNEL-EXEC-EQUIV-OK" in out
