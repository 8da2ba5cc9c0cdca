"""Jit'd public wrappers around the Pallas kernels.

The backend decides how a kernel runs: on a TPU it is compiled by Mosaic;
on the CPU it runs in interpret mode (the kernel body as plain JAX ops), so
tests and CPU tools check the same code; any other backend is an error.

``terapipe_attention`` is fully fused fwd+bwd: the forward saves (O, lse)
residuals and the backward runs the flash dQ / dK-dV Pallas kernels
(terapipe_attention_bwd.py) — no (l, ctx+l) score matrix and no repeated GQA
K/V ever touch HBM in either direction.  ``ctx_len`` may be a traced int32
scalar (scalar-prefetch operand): the pipeline executors' ``attn_sliced_dyn``
path routes through here with the per-tick context offset.

The custom_vjp wrapper is defined ONCE per static configuration (block
sizes, interpret mode) at module scope via an lru_cache — a per-call closure
would defeat jit caching and retrace on every call.  Blocks left as None are
chosen per kernel from the shapes (``terapipe_attention.flash_plan``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import tracing

from .decode_attention import decode_attention_kernel
from .terapipe_attention import terapipe_attention_fwd
from .terapipe_attention_bwd import terapipe_attention_bwd


def _interpret() -> bool:
    """True on the CPU (interpret mode), False on a TPU (compiled)."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas TPU kernels cannot run on {backend!r}")
    return backend == "cpu"


@functools.lru_cache(maxsize=None)
def _make_flash_attention(blk_q: Optional[int], blk_kv: Optional[int],
                          interpret: bool):
    """custom_vjp-wrapped flash attention for one static kernel config.

    Module-level + cached: the returned function object is stable across
    calls, so jit tracing caches hit.  ``ctx`` is a traced operand (int32
    scalar), NOT part of the cache key.
    """

    @jax.custom_vjp
    def attn(q, k, v, ctx):
        out, _ = terapipe_attention_fwd(q, k, v, ctx, blk_q=blk_q,
                                        blk_kv=blk_kv, interpret=interpret)
        return out

    def _fwd(q, k, v, ctx):
        out, lse = terapipe_attention_fwd(q, k, v, ctx, blk_q=blk_q,
                                          blk_kv=blk_kv, interpret=interpret)
        return out, (q, k, v, ctx, out, lse)

    def _bwd(res, g):
        q, k, v, ctx, out, lse = res
        # delta = rowsum(dO ∘ O): linear in l, plain jnp
        with jax.named_scope(tracing.ATTN_RELAYOUT):
            delta = jnp.einsum("blhd,blhd->bhl", g.astype(jnp.float32),
                               out.astype(jnp.float32))
        dq, dk, dv = terapipe_attention_bwd(
            q, k, v, g.astype(q.dtype), lse, delta, ctx,
            blk_q=blk_q, blk_kv=blk_kv, interpret=interpret)
        return dq, dk, dv, None

    attn.defvjp(_fwd, _bwd)
    return attn


def terapipe_attention(q, k, v, *, ctx_len,
                       blk_q: Optional[int] = None,
                       blk_kv: Optional[int] = None) -> jnp.ndarray:
    """Flash attention of a query slice at context offset ``ctx_len``.

    q: (B, l, Hq, hd); k/v: (B, Sk, Hkv, hd) with Sk >= ctx_len + l.  GQA
    (Hkv < Hq) is resolved inside the kernels' BlockSpec index maps — no
    repeat in HBM.  ``ctx_len`` may be a python int (static TeraPipe slices)
    or a traced int32 scalar (the executors' lockstep dynamic-ctx path).
    Differentiable via the fused flash backward kernels.
    """
    attn = _make_flash_attention(blk_q, blk_kv, _interpret())
    return attn(q, k, v, jnp.asarray(ctx_len, jnp.int32))


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     kv_len) -> jnp.ndarray:
    """Flash decode: q (B,1,Hq,hd) vs cache (B,L,Hkv,hd) valid to kv_len —
    a scalar, or a per-batch (B,) vector for continuous-batching rounds
    that mix context depths.  GQA resolved inside the kernel's BlockSpec
    index map (no K/V repeat)."""
    return decode_attention_kernel(q, k, v, kv_len, interpret=_interpret())
