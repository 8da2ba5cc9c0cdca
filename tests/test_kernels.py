"""Pallas kernel vs pure-jnp oracle: shape/dtype sweep in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st   # hypothesis or skip-stub (tests/_hyp.py)

from repro.kernels import ops
from repro.kernels.ref import terapipe_attention_ref
from repro.kernels.terapipe_attention import terapipe_attention_kernel

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
