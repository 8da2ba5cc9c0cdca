"""Pallas TPU flash-attention backward kernels for the TeraPipe inner op.

Given the forward's saved (O, lse) residuals (terapipe_attention.py) and the
upstream cotangent dO, computes (dQ, dK, dV) without ever materializing the
(l, ctx+l) probability or score matrix in HBM.  Standard flash-attention
backward (Dao et al.), split into two sweeps so each accumulator lives in
VMEM scratch across its innermost grid dimension:

* ``dQ`` kernel — grid (B, Hq, n_q, n_kv), KV innermost: for one q block,
  sweep the KV blocks rebuilding P = exp(S - lse) tile-by-tile,
  dS = P ∘ (dO·Vᵀ − delta), dQ += scale · dS · K.
* ``dK/dV`` kernel — grid (B, Hkv, n_kv, rep, n_q), q sweep innermost: for
  one KV block, sweep every q block of every q head in the GQA group
  (kv head = q head // rep — the ``rep`` grid dim walks the group, so the
  repeated K/V never exist in HBM and the dK/dV accumulation over the group
  happens in scratch), dV += Pᵀ·dO, dK += scale · dSᵀ·Q.

``delta = rowsum(dO ∘ O)`` is linear in l and computed by the caller
(kernels/ops.py) in plain jnp.  ``ctx`` is a scalar-prefetch operand exactly
as in the forward — traced offsets from the pipeline executors drive the
causal-frontier block skip.  Each kernel takes its own (blk_q, blk_kv) from
``flash_plan`` (terapipe_attention.py): dK/dV aims for 512-row query blocks,
which ran no slower on a v5e than dQ's 1024.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import tracing

from .terapipe_attention import (flash_plan, from_heads, kernel_blocks,
                                 row_spec, to_heads)


def _masked_p(q, k, lse, ctx, l, iq, ikv, blk_q, blk_kv, scale):
    """Rebuild one probability tile P = exp(scale·QKᵀ − lse) with the causal
    + valid-key mask; returns (p, mask)."""
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale         # (blk_q, blk_kv)
    q_pos = ctx + iq * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_kv), 0)
    kv_pos = ikv * blk_kv + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_kv), 1)
    mask = (q_pos >= kv_pos) & (kv_pos < ctx + l)
    p = jnp.where(mask, jnp.exp(logits - lse), 0.0)
    return p


def _dq_kernel(ctx_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_scr, *,
               l: int, blk_q: int, blk_kv: int, scale: float):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)
    n_kv = pl.num_programs(3)
    ctx = ctx_ref[0]

    @pl.when(ikv == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ikv * blk_kv < ctx + jnp.minimum((iq + 1) * blk_q, l))
    def _compute():
        q = q_ref[...].astype(jnp.float32)                  # (blk_q, hd)
        k = k_ref[...].astype(jnp.float32)                  # (blk_kv, hd)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...]                                  # (blk_q, 1)
        delta = delta_ref[...]
        p = _masked_p(q, k, lse, ctx, l, iq, ikv, blk_q, blk_kv, scale)
        dp = jax.lax.dot_general(                           # dO · Vᵀ
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_scr[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        dq_ref[...] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(ctx_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                l: int, blk_q: int, blk_kv: int, scale: float, rep: int):
    ikv = pl.program_id(2)
    r = pl.program_id(3)
    iq = pl.program_id(4)
    n_q = pl.num_programs(4)
    ctx = ctx_ref[0]

    @pl.when((r == 0) & (iq == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(ikv * blk_kv < ctx + jnp.minimum((iq + 1) * blk_q, l))
    def _compute():
        q = q_ref[...].astype(jnp.float32)                  # (blk_q, hd)
        k = k_ref[...].astype(jnp.float32)                  # (blk_kv, hd)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...]
        delta = delta_ref[...]
        p = _masked_p(q, k, lse, ctx, l, iq, ikv, blk_q, blk_kv, scale)
        dv_scr[...] += jax.lax.dot_general(                 # Pᵀ · dO
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] += scale * jax.lax.dot_general(         # dSᵀ · Q
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((r == rep - 1) & (iq == n_q - 1))
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _rows(a, pad):
    """(B, H, l) lse/delta -> the kernels' (B, H, l + pad, 1) column."""
    return jnp.pad(a, ((0, 0), (0, 0), (0, pad)))[..., None]


def _dq_call(ctx_arr, q, k, v, do, lse, delta, *, l: int, rep: int,
            blk_q: int, blk_kv: int, interpret: bool):
    """The dQ kernel on head-major operands, padded to its blocks."""
    b, hq, lp, hd = q.shape
    skp = k.shape[2]
    scale = 1.0 / math.sqrt(hd)

    # the kv block index is clamped to the causal frontier (from the
    # prefetched ctx): grid steps the pl.when guard skips revisit the same
    # block and their HBM->VMEM copies are elided (see terapipe_attention).
    def _kv_index(bi, hi, qi, ki, ctx_ref):
        last = (ctx_ref[0] + jnp.minimum((qi + 1) * blk_q, l) - 1) // blk_kv
        return (bi, hi // rep, jnp.minimum(ki, last), 0)

    def _q_index(bi, hi, qi, ki, *_):
        return (bi, hi, qi, 0)

    q_spec = pl.BlockSpec((None, None, blk_q, hd), _q_index)
    kv_spec = pl.BlockSpec((None, None, blk_kv, hd), _kv_index)
    rspec = row_spec(blk_q, _q_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hq, lp // blk_q, skp // blk_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, rspec, rspec],
        out_specs=[q_spec],
        scratch_shapes=[pltpu.VMEM((blk_q, hd), jnp.float32)],
    )
    (dq,) = pl.pallas_call(
        functools.partial(_dq_kernel, l=l, blk_q=blk_q, blk_kv=blk_kv,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        interpret=interpret,
        name=tracing.FLASH_BWD_DQ,
    )(ctx_arr, q, k, v, do, lse, delta)
    return dq


def _dkv_call(ctx_arr, q, k, v, do, lse, delta, *, l: int, rep: int,
             blk_q: int, blk_kv: int, interpret: bool):
    """The dK/dV kernel on head-major operands, padded to its blocks.

    kv blocks outer, (GQA group member, q block) inner: the output block
    index is constant across the inner sweep, so the accumulators persist
    in scratch and each dK/dV block is written once."""
    b, hq, lp, hd = q.shape
    hkv, skp = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    n_q = lp // blk_q

    def _gq_block(qi, ki, ctx_ref):
        # first q block whose causal frontier reaches this kv block; clamped
        # into range for kv blocks beyond every frontier (stale tail — the
        # pl.when guard already skips their compute)
        first = (ki * blk_kv - ctx_ref[0]) // blk_q
        return jnp.minimum(jnp.maximum(qi, first), n_q - 1)

    def _gq_index(bi, hk, ki, r, qi, ctx_ref):
        return (bi, hk * rep + r, _gq_block(qi, ki, ctx_ref), 0)

    gq_spec = pl.BlockSpec((None, None, blk_q, hd), _gq_index)
    gkv_spec = pl.BlockSpec((None, None, blk_kv, hd),
                            lambda bi, hk, ki, r, qi, *_: (bi, hk, ki, 0))
    grow_spec = row_spec(blk_q, _gq_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, skp // blk_kv, rep, n_q),
        in_specs=[gq_spec, gkv_spec, gkv_spec, gq_spec, grow_spec, grow_spec],
        out_specs=[gkv_spec, gkv_spec],
        scratch_shapes=[pltpu.VMEM((blk_kv, hd), jnp.float32),
                        pltpu.VMEM((blk_kv, hd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_dkv_kernel, l=l, blk_q=blk_q, blk_kv=blk_kv,
                          scale=scale, rep=rep),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
        name=tracing.FLASH_BWD_DKV,
    )(ctx_arr, q, k, v, do, lse, delta)


@functools.partial(jax.jit, static_argnames=("blk_q", "blk_kv", "interpret"))
def terapipe_attention_bwd(q, k, v, do, lse, delta, ctx, *,
                           blk_q: Optional[int] = None,
                           blk_kv: Optional[int] = None,
                           interpret: bool = False):
    """Fused backward: returns (dq, dk, dv).

    q/do: (B, l, Hq, hd); k/v: (B, Sk, Hkv, hd) GQA-native; lse/delta:
    (B, Hq, l) f32; ctx: int32 scalar, may be traced.  dk/dv come back in
    the GQA-native (Hkv) layout — no repeated-head buffers anywhere.  The
    kernels run head-major, as the forward does (``to_heads``), each with
    its own blocks: those given, else ``flash_plan``'s.  Both pad to the
    least common multiple of their blocks.
    """
    b, l, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    plan = flash_plan(l, None, sk, b, hq)
    dq_blk = kernel_blocks(plan[tracing.FLASH_BWD_DQ], blk_q, blk_kv, l, sk)
    dkv_blk = kernel_blocks(plan[tracing.FLASH_BWD_DKV], blk_q, blk_kv, l, sk)

    l_pad = -l % math.lcm(dq_blk[0], dkv_blk[0])
    sk_pad = -sk % math.lcm(dq_blk[1], dkv_blk[1])
    with jax.named_scope(tracing.ATTN_RELAYOUT):
        q, do = to_heads(q, l_pad), to_heads(do, l_pad)
        k, v = to_heads(k, sk_pad), to_heads(v, sk_pad)
        lse, delta = _rows(lse, l_pad), _rows(delta, l_pad)
    ctx_arr = jnp.asarray(ctx, jnp.int32).reshape((1,))
    args = (ctx_arr, q, k, v, do, lse, delta)
    dq = _dq_call(*args, l=l, rep=rep, blk_q=dq_blk[0], blk_kv=dq_blk[1],
                 interpret=interpret)
    dk, dv = _dkv_call(*args, l=l, rep=rep, blk_q=dkv_blk[0],
                      blk_kv=dkv_blk[1], interpret=interpret)
    with jax.named_scope(tracing.ATTN_RELAYOUT):
        return from_heads(dq, l), from_heads(dk, sk), from_heads(dv, sk)
