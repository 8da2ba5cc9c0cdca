"""Pallas TPU flash-decode kernel: one query token vs a long KV cache.

The serving hot spot (decode_32k / long_500k cells): q (B, 1, Hq, hd) against
a cache (B, L, Hkv, hd) valid up to ``kv_len``.  Decode is memory-bound — the
win is (a) GQA handled by BlockSpec index mapping (kv head = q head // rep),
so the repeated K/V are NEVER materialized in HBM, and (b) a single streaming
pass over the cache with running softmax in VMEM scratch.

Grid (B, Hq, L/blk_kv), KV block innermost (TPU grids are sequential
minor-to-major so the scratch accumulator persists across the KV sweep).
Like the flash kernels it runs head-major (``to_heads``), so each block is
a (rows, hd) tile of one head, as Mosaic's tiling rule requires.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .terapipe_attention import from_heads, to_heads

DEFAULT_BLOCK_KV = 512
NEG_INF = float("-inf")


def _decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, s_scr,
                   acc_scr, *, blk_kv: int, scale: float):
    ikv = pl.program_id(2)
    n_kv = pl.num_programs(2)
    # per-BATCH valid length (continuous batching serves requests at
    # heterogeneous context depths in one round); scalar callers are
    # broadcast to (B,) by the wrapper
    kv_len = kvlen_ref[pl.program_id(0)]

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ikv * blk_kv < kv_len)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                     # (1, hd)
        k = k_ref[...].astype(jnp.float32)                     # (blk_kv, hd)
        v = v_ref[...].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (1, blk_kv)
        valid = ikv * blk_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, blk_kv), 1) < kv_len
        logits = jnp.where(valid, logits, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
        s_scr[...] = s_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(s_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_kv", "interpret"))
def decode_attention_kernel(q, k, v, kv_len, *,
                            blk_kv: int = DEFAULT_BLOCK_KV,
                            interpret: bool = False):
    """q: (B, 1, Hq, hd); k, v: (B, L, Hkv, hd); kv_len: scalar int32 OR a
    per-batch (B,) vector (continuous-batching rounds mix context depths).
    GQA is resolved in the BlockSpec index map — no K/V expansion."""
    b, one, hq, hd = q.shape
    assert one == 1
    L, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    blk_kv = min(blk_kv, L)
    kh, vh = to_heads(k, -L % blk_kv), to_heads(v, -L % blk_kv)
    scale = 1.0 / math.sqrt(hd)
    kv_len_arr = jnp.broadcast_to(
        jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))

    q_spec = pl.BlockSpec((None, None, 1, hd), lambda bi, hi, ki: (bi, hi, 0, 0))
    # GQA: the kv-head block index is hq // rep — no repeat in HBM
    kv_spec = pl.BlockSpec((None, None, blk_kv, hd),
                           lambda bi, hi, ki: (bi, hi // rep, ki, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, blk_kv=blk_kv, scale=scale),
        grid=(b, hq, kh.shape[2] // blk_kv),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),  # kv_len
                  q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),     # running max
            pltpu.VMEM((1, 1), jnp.float32),     # running denom
            pltpu.VMEM((1, hd), jnp.float32),    # output acc
        ],
        interpret=interpret,
    )(kv_len_arr, to_heads(q, 0), kh, vh)
    return from_heads(out, 1)
