"""Pallas TPU flash-attention forward kernel for the TeraPipe inner op.

Computes attention of a query slice (length l, absolute offset ``ctx``) over
keys/values of length >= ctx + l — the paper's t_fwd(l, ctx) hot spot —
without materializing the (l, ctx+l) score matrix in HBM, and additionally
emits the per-row logsumexp so the fused backward (terapipe_attention_bwd)
can rebuild the probabilities block-by-block instead of recomputing the
whole forward through the dense reference.

TPU mapping (DESIGN.md §3): grid (B, Hq, n_q_blocks, n_kv_blocks) with the
KV block index innermost — TPU grids execute sequentially minor-to-major, so
the running-softmax state (m, s, acc) lives in VMEM scratch and persists
across the KV sweep of one query block.  The wrapper takes (B, L, H, hd)
and runs the kernel head-major, (B, H, L, hd) (``to_heads``): every block is
then a (rows, hd) tile of one head, which Mosaic's (8, 128) tiling rule
accepts; lse is a (B, H, L, 1) column for the same reason.  Also:

* ``ctx`` is a SCALAR-PREFETCH operand (``pltpu.PrefetchScalarGridSpec``):
  it may be a traced int32 — the lockstep pipeline executors run every stage
  at a different, data-dependent context offset (``attn_sliced_dyn``), and
  the causal-frontier block skip is computed from the prefetched scalar, so
  blocks past ``ctx + l`` compute and copy nothing even though the grid
  spans the whole (static-size) KV cache.
* GQA is resolved in the K/V BlockSpec index map (kv head = q head // rep,
  as in decode_attention.py) — the repeated heads never exist in HBM.
* Blocks come from the shapes (``flash_plan``): each kernel aims for its
  own (blk_q, blk_kv) tile, tuned on a v5e, and takes the largest multiple
  of 128 up to it that divides the 128-aligned extent.  A grid step costs a
  fixed overhead (DMA issue and wait, pipeline bookkeeping) whether it
  computes or the causal guard skips it; 1024-wide tiles cut the steps 64x
  against 128-wide ones.  A ragged slice (the DP planner emits e.g. l=96)
  pads to 128, never to a 96-wide block; the position masks make the pad
  exact.  A 256-token slice keeps 256-row query blocks.

Checked against kernels.ref in interpret mode on CPU; tests/test_tpu_compile.py
compiles it for TPU v5e.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import tracing

MXU_ALIGN = 128
NEG_INF = float("-inf")

# (blk_q, blk_kv) each kernel aims for; ``flash_plan`` fits them to the
# shapes.  Tuned on a TPU v5e (PERF.md section 6, PR 15).
TILE_TARGET = {
    tracing.FLASH_FWD: (1024, 1024),
    tracing.FLASH_BWD_DQ: (1024, 1024),
    tracing.FLASH_BWD_DKV: (512, 1024),
}


def round_up(n: int, align: int) -> int:
    return -(-n // align) * align


def align_block(blk: int, n: int, align: int = MXU_ALIGN) -> int:
    """Block size for an extent of ``n``: never larger than the aligned-up
    extent, never clamped to an UNALIGNED extent (a ragged l=96 slice gets a
    full 128-wide MXU block + mask, not a 96-wide one)."""
    return min(blk, round_up(max(n, 1), align))


def fit_block(target: int, n: int) -> int:
    """The largest multiple of 128 up to ``target`` that divides the
    128-aligned extent of ``n``: every kernel then pads ``n`` alike, to that
    extent, whatever its block."""
    ext = round_up(max(n, 1), MXU_ALIGN)
    blk = align_block(target, n)
    while ext % blk:
        blk -= MXU_ALIGN
    return blk


class KernelPlan(NamedTuple):
    blk_q: int
    blk_kv: int
    grid_steps: int                 # grid steps per call
    compute_steps: Optional[int]    # those the causal guard lets compute


def flash_plan(l: int, ctx: Optional[int], sk: int, b: int,
               hq: int) -> Dict[str, KernelPlan]:
    """Blocks of each flash kernel for a slice of ``l`` queries at context
    ``ctx`` against ``sk`` keys with B x Hq query heads, and what they cost:
    grid steps per call and the steps that compute (None for a traced
    ``ctx``).  Every kernel's grid spans (B, Hq, query blocks, key blocks)
    in some order (dK/dV's splits Hq into Hkv x the GQA group); a step
    computes where its key block starts before the query block's causal
    frontier.

    The blocks depend on the shapes alone, so every caller (whole
    sequences, the executor's slices at a traced ctx, serve prefill, the
    cost model's tables) runs the same rule.  The 1024-wide tiles fit the
    v5e's default scoped VMEM (tests/test_tpu_compile.py compiles them at
    the benchmark's widths); 2048-wide ones do not."""
    plan = {}
    for name, (tq, tkv) in TILE_TARGET.items():
        blk_q, blk_kv = fit_block(tq, l), fit_block(tkv, sk)
        n_q = round_up(l, blk_q) // blk_q
        n_kv = round_up(sk, blk_kv) // blk_kv
        steps = None
        if ctx is not None:
            steps = b * hq * sum(
                min(n_kv, -(-(ctx + min((iq + 1) * blk_q, l)) // blk_kv))
                for iq in range(n_q))
        plan[name] = KernelPlan(blk_q, blk_kv, b * hq * n_q * n_kv, steps)
    return plan


def kernel_blocks(plan: KernelPlan, blk_q: Optional[int],
                  blk_kv: Optional[int], l: int, sk: int) -> Tuple[int, int]:
    """The plan's blocks, or the caller's where it names them (rounded to
    the aligned extent, as ``align_block`` does)."""
    return (align_block(blk_q, l) if blk_q else plan.blk_q,
            align_block(blk_kv, sk) if blk_kv else plan.blk_kv)


def _fwd_kernel(ctx_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, s_scr, acc_scr, *,
                l: int, blk_q: int, blk_kv: int, scale: float):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)
    n_kv = pl.num_programs(3)
    ctx = ctx_ref[0]

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip blocks fully beyond this q block's causal frontier (and beyond the
    # ctx + l valid-key limit: pad rows would otherwise attend stale cache)
    @pl.when(ikv * blk_kv < ctx + jnp.minimum((iq + 1) * blk_q, l))
    def _compute():
        q = q_ref[...].astype(jnp.float32)                  # (blk_q, hd)
        k = k_ref[...].astype(jnp.float32)                  # (blk_kv, hd)
        v = v_ref[...].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (blk_q, blk_kv)
        q_pos = ctx + iq * blk_q + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_kv), 0)
        kv_pos = ikv * blk_kv + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_kv), 1)
        mask = (q_pos >= kv_pos) & (kv_pos < ctx + l)
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_scr[...]                                 # (blk_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
        s_scr[...] = s_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        s = jnp.maximum(s_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / s).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(s)


def to_heads(a, pad):
    """(B, L, H, hd) -> head-major (B, H, L + pad, hd), the kernels' layout.

    Mosaic tiles the last two dims of every block by (8, 128) or takes them
    whole, so a block may not hold one head of a (B, L, H, hd) array; head-
    major makes each block a (rows, hd) tile of one head."""
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return jnp.swapaxes(a, 1, 2)


def from_heads(a, n: int):
    """Head-major (B, H, Lp, hd) -> (B, n, H, hd): drop the pad rows."""
    return jnp.swapaxes(a[:, :, :n], 1, 2)


def row_spec(blk: int, index_map):
    """BlockSpec of a per-row f32 statistic (lse, delta) kept as a
    (B, H, L, 1) column: its (blk, 1) block meets Mosaic's tiling rule and
    broadcasts against a (blk, blk_kv) tile without a relayout."""
    return pl.BlockSpec((None, None, blk, 1), index_map)


def _fwd_call(ctx_arr, q, k, v, *, l: int, rep: int, blk_q: int,
              blk_kv: int, interpret: bool):
    """The forward kernel on head-major operands, padded to its blocks:
    returns head-major (out, lse)."""
    b, hq, lp, hd = q.shape
    skp = k.shape[2]
    scale = 1.0 / math.sqrt(hd)

    # GQA: kv-head block = q head // rep — no repeat in HBM.  The kv BLOCK
    # index is clamped to this q block's causal frontier (computed from the
    # prefetched ctx): grid steps the pl.when guard skips revisit the same
    # block, so their HBM->VMEM copies are elided — per-block KV traffic is
    # O(ctx + l), not O(Sk), even though the grid spans the whole cache.
    def _kv_index(bi, hi, qi, ki, ctx_ref):
        last = (ctx_ref[0] + jnp.minimum((qi + 1) * blk_q, l) - 1) // blk_kv
        return (bi, hi // rep, jnp.minimum(ki, last), 0)

    def _q_index(bi, hi, qi, ki, *_):
        return (bi, hi, qi, 0)

    q_spec = pl.BlockSpec((None, None, blk_q, hd), _q_index)
    kv_spec = pl.BlockSpec((None, None, blk_kv, hd), _kv_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hq, lp // blk_q, skp // blk_kv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec(blk_q, _q_index)],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),    # running max
            pltpu.VMEM((blk_q, 1), jnp.float32),    # running denom
            pltpu.VMEM((blk_q, hd), jnp.float32),   # output acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, l=l, blk_q=blk_q, blk_kv=blk_kv,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, hq, lp, 1), jnp.float32),
        ],
        interpret=interpret,
        name=tracing.FLASH_FWD,
    )(ctx_arr, q, k, v)


@functools.partial(jax.jit, static_argnames=("blk_q", "blk_kv", "interpret"))
def terapipe_attention_fwd(q, k, v, ctx, *,
                           blk_q: Optional[int] = None,
                           blk_kv: Optional[int] = None,
                           interpret: bool = False):
    """Fused forward: returns (out, lse).

    q: (B, l, Hq, hd); k, v: (B, Sk, Hkv, hd) GQA-native, Sk >= ctx + l;
    ctx: int32 scalar, may be TRACED (scalar-prefetch).  lse: (B, Hq, l) f32.
    Blocks not given come from ``flash_plan``.
    """
    b, l, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    assert k.shape == v.shape and hq % hkv == 0, (q.shape, k.shape)
    plan = flash_plan(l, None, sk, b, hq)[tracing.FLASH_FWD]
    blk_q, blk_kv = kernel_blocks(plan, blk_q, blk_kv, l, sk)
    with jax.named_scope(tracing.ATTN_RELAYOUT):
        qh = to_heads(q, -l % blk_q)
        kh = to_heads(k, -sk % blk_kv)
        vh = to_heads(v, -sk % blk_kv)
    ctx_arr = jnp.asarray(ctx, jnp.int32).reshape((1,))
    out, lse = _fwd_call(ctx_arr, qh, kh, vh, l=l, rep=hq // hkv,
                         blk_q=blk_q, blk_kv=blk_kv, interpret=interpret)
    with jax.named_scope(tracing.ATTN_RELAYOUT):
        return from_heads(out, l), lse[:, :, :l, 0]


@functools.partial(jax.jit, static_argnames=("ctx_len", "blk_q", "blk_kv",
                                             "interpret"))
def terapipe_attention_kernel(q, k, v, *, ctx_len: int,
                              blk_q: Optional[int] = None,
                              blk_kv: Optional[int] = None,
                              interpret: bool = False):
    """Back-compat forward-only entry (static ctx_len; heads may be GQA or
    already expanded).  New code should use ops.terapipe_attention."""
    out, _ = terapipe_attention_fwd(q, k, v, jnp.int32(ctx_len), blk_q=blk_q,
                                    blk_kv=blk_kv, interpret=interpret)
    return out
