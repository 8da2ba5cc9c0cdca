"""Pallas kernel vs pure-jnp oracle: shape/dtype sweep in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st   # hypothesis or skip-stub (tests/_hyp.py)

from repro import tracing
from repro.analysis.rules import check_vmem
from repro.kernels import ops
from repro.kernels.ref import terapipe_attention_ref
from repro.kernels.terapipe_attention import (MXU_ALIGN, flash_plan,
                                              round_up,
                                              terapipe_attention_fwd,
                                              terapipe_attention_kernel)
from repro.kernels.terapipe_attention_bwd import terapipe_attention_bwd

pytestmark = pytest.mark.kernels


def _rand(shape, dtype, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("b,l,ctx,h,hd", [
    (1, 8, 0, 1, 64),          # tiny, no context
    (2, 64, 64, 2, 64),        # ctx == l
    (1, 128, 256, 4, 128),     # long context, MXU-aligned
    (2, 100, 52, 3, 64),       # unaligned (padding path)
    (1, 256, 0, 2, 128),       # pure causal
    (1, 33, 7, 1, 32),         # tiny odd shapes
    (1, 2048, 0, 2, 32),       # default blocks: 2 x 2 tiles of 1024
])
def test_kernel_matches_oracle(b, l, ctx, h, hd, dtype, tol):
    q = _rand((b, l, h, hd), dtype, 0)
    k = _rand((b, ctx + l, h, hd), dtype, 1)
    v = _rand((b, ctx + l, h, hd), dtype, 2)
    out = terapipe_attention_kernel(q, k, v, ctx_len=ctx, interpret=True)
    ref = terapipe_attention_ref(q, k, v, ctx)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@given(l=st.integers(1, 96), ctx=st.integers(0, 96),
       hd=st.sampled_from([32, 64]), blk=st.sampled_from([16, 32, 128]))
@settings(max_examples=12, deadline=None)
def test_kernel_property_shapes(l, ctx, hd, blk):
    """Property: any (l, ctx, block) combination matches the oracle."""
    q = _rand((1, l, 1, hd), jnp.float32, 10)
    k = _rand((1, ctx + l, 1, hd), jnp.float32, 11)
    v = _rand((1, ctx + l, 1, hd), jnp.float32, 12)
    out = terapipe_attention_kernel(q, k, v, ctx_len=ctx, blk_q=blk,
                                    blk_kv=blk, interpret=True)
    ref = terapipe_attention_ref(q, k, v, ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def _check_plan_blocks(plan, l, sk):
    """128-aligned blocks that divide the aligned extents (so never exceed
    them), and compute steps equal to the grid points the guard admits."""
    for kernel, p in plan.items():
        for blk, n in ((p.blk_q, l), (p.blk_kv, sk)):
            assert blk % MXU_ALIGN == 0 and blk > 0, (kernel, p)
            assert round_up(n, MXU_ALIGN) % blk == 0, (kernel, p, n)


def _guarded_steps(l, ctx, sk, blk_q, blk_kv):
    """Grid points (q block, kv block) whose kv block starts before the q
    block's causal frontier — the kernels' pl.when guard, point by point."""
    iq = np.arange(round_up(l, blk_q) // blk_q)[:, None]
    ikv = np.arange(round_up(sk, blk_kv) // blk_kv)[None, :]
    return int(np.sum(ikv * blk_kv < ctx + np.minimum((iq + 1) * blk_q, l)))


@pytest.mark.parametrize("b,l,ctx,sk,hq,hkv,hd", [
    (4, 2048, 0, 2048, 16, 8, 128),     # qwen3-0.6b.train-2k
    (1, 8192, 0, 8192, 16, 8, 128),     # qwen3-0.6b.train-8k
    (1, 256, 0, 2048, 16, 16, 128),     # the executor's 256-token slices
    (1, 256, 1792, 2048, 16, 16, 128),
    (1, 96, 1900, 2048, 4, 2, 32),      # ragged slice, stale cache tail
])
def test_flash_plan_matches_grids(b, l, ctx, sk, hq, hkv, hd):
    """``flash_plan``'s blocks are legal, its grid steps are the product of
    each kernel's real grid, its compute steps what the guard admits; a
    256-token slice keeps 256-row query blocks."""
    plan = flash_plan(l, ctx, sk, b, hq)
    assert set(plan) == {tracing.FLASH_FWD, tracing.FLASH_BWD_DQ,
                         tracing.FLASH_BWD_DKV}
    _check_plan_blocks(plan, l, sk)
    q = jax.ShapeDtypeStruct((b, l, hq, hd), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, sk, hkv, hd), jnp.bfloat16)
    rows = jax.ShapeDtypeStruct((b, hq, l), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, do, lse, dl, c: (
            terapipe_attention_fwd(q, k, v, c, interpret=True),
            terapipe_attention_bwd(q, k, v, do, lse, dl, c, interpret=True)))(
        q, kv, kv, q, rows, rows, jnp.int32(ctx))
    grids = {f.data["kernel"]: f.data["grid"] for f in check_vmem(jaxpr)}
    for kernel, p in plan.items():
        assert np.prod(grids[kernel]) == p.grid_steps, (kernel, grids, p)
        assert p.compute_steps == b * hq * _guarded_steps(
            l, ctx, sk, p.blk_q, p.blk_kv), (kernel, p)
        if l == 256:
            assert p.blk_q == 256, (kernel, p)


@given(l=st.integers(1, 3000), ctx=st.integers(0, 3000),
       tail=st.integers(0, 600))
@settings(max_examples=50, deadline=None)
def test_flash_plan_property(l, ctx, tail):
    """Property: at any slice, context and stale tail the blocks are legal
    and the compute steps are the guard's."""
    sk = ctx + l + tail
    plan = flash_plan(l, ctx, sk, 1, 1)
    _check_plan_blocks(plan, l, sk)
    for p in plan.values():
        assert p.compute_steps == _guarded_steps(l, ctx, sk, p.blk_q,
                                                 p.blk_kv), p
        assert p.grid_steps == (round_up(l, p.blk_q) // p.blk_q
                                * (round_up(sk, p.blk_kv) // p.blk_kv))


def test_ops_wrapper_gqa_and_grad():
    q = _rand((2, 32, 8, 32), jnp.float32, 0)
    k = _rand((2, 48, 2, 32), jnp.float32, 1)   # GQA: 4x fewer kv heads
    v = _rand((2, 48, 2, 32), jnp.float32, 2)
    out = ops.terapipe_attention(q, k, v, ctx_len=16)
    kf = jnp.repeat(k, 4, axis=2)
    vf = jnp.repeat(v, 4, axis=2)
    ref = terapipe_attention_ref(q, kf, vf, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # gradient flows through the custom-vjp (fused flash backward kernels)
    g = jax.grad(lambda q: ops.terapipe_attention(q, k, v, ctx_len=16).sum())(q)
    gr = jax.grad(lambda q: terapipe_attention_ref(q, kf, vf, 16).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=2e-5,
                               atol=2e-5)


def test_kernel_softmax_stability():
    """Large logits must not overflow the running softmax."""
    q = 30.0 * _rand((1, 64, 1, 64), jnp.float32, 3)
    k = 30.0 * _rand((1, 128, 1, 64), jnp.float32, 4)
    v = _rand((1, 128, 1, 64), jnp.float32, 5)
    out = terapipe_attention_kernel(q, k, v, ctx_len=64, interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))
    ref = terapipe_attention_ref(q, k, v, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    """Interpret mode on the CPU, compiled on a TPU, an error elsewhere —
    decided by the backend, with no option to override it."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="cannot run on 'gpu'"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret
