"""GQA attention layer: init + three execution modes.

Modes
-----
* ``full``    – causal self-attention over the whole sequence (train fwd).
* ``sliced``  – TeraPipe mode: queries are a token slice at a static context
                offset; keys/values are [prefix KV cache ++ this slice].
* ``decode``  – one new token against a fixed-capacity KV cache (serving).

The sliced mode is the paper's inner computation t_fwd(l, ctx).  When
``cfg.use_kernel`` is set, the full/sliced/sliced_dyn modes route through
the Pallas flash kernel in :mod:`repro.kernels` (GQA heads stay native —
the kernels resolve the group in their BlockSpec index maps) — including
the TRACED-ctx ``sliced_dyn`` path both pipeline executors actually run,
with a fully fused flash backward.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro import tracing

from .common import (ModelConfig, apply_rope, attention_scores,
                     attention_scores_gqa, causal_mask, dense_init,
                     local_causal_mask, repeat_kv, rms_norm)


def init_attn(key, cfg: ModelConfig):
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.n_heads * hd)),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.n_kv_heads * hd)),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.n_kv_heads * hd)),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, cfg.d_model)),
    }
    s = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
        s["q_norm"] = (None,)
        s["k_norm"] = (None,)
    return p, s


def _project_qkv(p, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray,
                 rope: bool = True):
    """Head counts are derived from the weight shapes, not cfg — under manual
    TP (cfg.tp_axis) the weights arrive sharded over heads."""
    b, s, _ = x.shape
    hd = cfg.hd
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, -1, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(b, s, -1, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(b, s, -1, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


_BLOCKED_THRESHOLD = 2048   # above this seq len, use the q-chunked softmax path
_Q_CHUNK = 1024


def attention_blocked(q, k, v, *, q_offset: int = 0, q_chunk: int = _Q_CHUNK,
                      window: int = 0) -> jnp.ndarray:
    """Causal attention without materializing the full (Sq, Sk) score matrix.

    Python-unrolled over query chunks; chunk at absolute offset ``o`` only
    reads keys[: o + qc] (exact causal FLOPs, static shapes — the pure-jnp
    analogue of the Pallas kernel's tiling, used on long sequences where the
    dense mask would not fit).
    q: (B, Sq, H, hd); k/v: (B, Sk, H, hd) — already GQA-repeated.
    """
    b, sq, h, hd = q.shape
    outs = []
    for start in range(0, sq, q_chunk):
        qc = min(q_chunk, sq - start)
        off = q_offset + start
        k_end = min(off + qc, k.shape[1])
        qs = jax.lax.slice_in_dim(q, start, start + qc, axis=1)
        ks = jax.lax.slice_in_dim(k, 0, k_end, axis=1)
        vs = jax.lax.slice_in_dim(v, 0, k_end, axis=1)
        if window:
            lo = max(0, off - window + 1)
            ks = jax.lax.slice_in_dim(ks, lo, k_end, axis=1)
            vs = jax.lax.slice_in_dim(vs, lo, k_end, axis=1)
            mask = local_causal_mask(qc, k_end - lo, window, q_offset=off - lo)
        else:
            mask = causal_mask(qc, k_end, q_offset=off)
        outs.append(attention_scores(qs, ks, vs, mask=mask))
    return jnp.concatenate(outs, axis=1)


def attention_blocked_bidir(q, k, v, *, q_chunk: int = _Q_CHUNK):
    """Bidirectional attention without the (Sq, Sk) score matrix: scan over
    query chunks, each attending the full keys (encoder stacks at 32k frames
    — the whisper-prefill roofline hog; see EXPERIMENTS §Perf cell D).
    q: (B, Sq, Hq, hd); k/v: (B, Sk, Hkv, hd) (GQA-native)."""
    from .common import attention_scores_gqa
    b, sq, hq, hd = q.shape
    if sq % q_chunk != 0:
        q_chunk = sq
    nc = sq // q_chunk
    qr = jnp.moveaxis(q.reshape(b, nc, q_chunk, hq, hd), 1, 0)

    def body(_, qc):
        return None, attention_scores_gqa(qc, k, v, mask=None)

    _, out = jax.lax.scan(body, None, qr)
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, hd)


def _n_rep(q, k):
    return q.shape[2] // k.shape[2]


def _out_proj(p, cfg: ModelConfig, out, b, s, dtype):
    out = out.reshape(b, s, -1)
    y = out @ p["wo"].astype(dtype)
    if cfg.tp_axis is not None:
        y = jax.lax.psum(y, cfg.tp_axis)
    return y


def attn_full(p, cfg: ModelConfig, x: jnp.ndarray, *, causal: bool = True,
              window: int = 0) -> jnp.ndarray:
    """(B, S, D) -> (B, S, D).  Full self-attention (train / encoder)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(p, cfg, x, positions, rope=cfg.rope_theta > 0)
    if cfg.use_kernel and causal and window == 0:
        from repro.kernels import ops as kops
        out = kops.terapipe_attention(q, k, v, ctx_len=0)
    else:
        if causal and s > _BLOCKED_THRESHOLD:
            kf, vf = repeat_kv(k, _n_rep(q, k)), repeat_kv(v, _n_rep(q, k))
            out = attention_blocked(q, kf, vf, window=window)
        elif window:
            out = attention_scores_gqa(q, k, v,
                                       mask=local_causal_mask(s, s, window)[None])
        elif causal:
            out = attention_scores_gqa(q, k, v, mask=causal_mask(s, s)[None])
        elif s > _BLOCKED_THRESHOLD:
            out = attention_blocked_bidir(q, k, v)
        else:
            out = attention_scores_gqa(q, k, v, mask=None)
    return _out_proj(p, cfg, out, b, s, x.dtype)


def attn_sliced(p, cfg: ModelConfig, x_slice: jnp.ndarray, kv_cache, ctx_len: int,
                *, window: int = 0):
    """TeraPipe inner op: attention of a slice at static context offset.

    x_slice : (B, l, D) hidden states of this token slice
    kv_cache: (k, v) each (B, L_max, kv_heads, hd) — prefix written in [0, ctx_len)
    ctx_len : static int, tokens already processed for this sequence
    Returns (out_slice, new_kv_cache).
    """
    b, l, _ = x_slice.shape
    positions = (jnp.arange(l) + ctx_len)[None, :]
    q, k, v = _project_qkv(p, cfg, x_slice, positions, rope=cfg.rope_theta > 0)
    ck, cv = kv_cache
    with jax.named_scope(tracing.CACHE):
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, ctx_len, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, ctx_len, 0, 0))
        # keys for this slice: the prefix plus the slice itself (static size)
        k_all = jax.lax.dynamic_slice(ck, (0, 0, 0, 0),
                                      (b, ctx_len + l, ck.shape[2], ck.shape[3]))
        v_all = jax.lax.dynamic_slice(cv, (0, 0, 0, 0),
                                      (b, ctx_len + l, cv.shape[2], cv.shape[3]))
    if cfg.use_kernel and window == 0:
        from repro.kernels import ops as kops
        out = kops.terapipe_attention(q, k_all.astype(q.dtype), v_all.astype(q.dtype),
                                      ctx_len=ctx_len)
    else:
        if l > _BLOCKED_THRESHOLD:
            kf = repeat_kv(k_all.astype(q.dtype), _n_rep(q, k_all))
            vf = repeat_kv(v_all.astype(q.dtype), _n_rep(q, k_all))
            out = attention_blocked(q, kf, vf, q_offset=ctx_len, window=window)
        elif window:
            mask = local_causal_mask(l, ctx_len + l, window, q_offset=ctx_len)
            out = attention_scores_gqa(q, k_all.astype(q.dtype),
                                       v_all.astype(q.dtype), mask=mask[None])
        else:
            mask = causal_mask(l, ctx_len + l, q_offset=ctx_len)
            out = attention_scores_gqa(q, k_all.astype(q.dtype),
                                       v_all.astype(q.dtype), mask=mask[None])
    return _out_proj(p, cfg, out, b, l, x_slice.dtype), (ck, cv)


def attn_sliced_dyn(p, cfg: ModelConfig, x_slice: jnp.ndarray, kv_cache, ctx,
                    *, window: int = 0):
    """TeraPipe inner op with a TRACED context offset (lockstep SPMD pipeline:
    at a given tick each stage works at a different ctx, so ctx is data).

    Attends over the FULL cache with an absolute-position causal mask; entries
    beyond ctx+iq are unwritten/stale and masked out.  Under ``cfg.use_kernel``
    this routes through the Pallas flash kernel with ``ctx`` as a
    scalar-prefetch operand — the causal-frontier block skip recovers the
    ~2x FLOPs the pure-jnp path pays for not statically trimming the key
    range, and the fused backward keeps the 1F1B executor's per-tick bwd off
    the dense (l, ctx+l) score matrix.
    """
    b, l, _ = x_slice.shape
    positions = jnp.arange(l)[None, :] + ctx
    q, k, v = _project_qkv(p, cfg, x_slice, positions, rope=cfg.rope_theta > 0)
    ck, cv = kv_cache
    with jax.named_scope(tracing.CACHE):
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, ctx, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, ctx, 0, 0))
    lmax = ck.shape[1]
    if cfg.use_kernel and window == 0:
        from repro.kernels import ops as kops
        out = kops.terapipe_attention(q, ck.astype(q.dtype),
                                      cv.astype(q.dtype), ctx_len=ctx)
    else:
        qp = jnp.arange(l)[:, None] + ctx          # absolute query positions
        kp = jnp.arange(lmax)[None, :]
        mask = qp >= kp
        if window:
            mask &= (qp - kp) < window
        out = attention_scores_gqa(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                   mask=mask[None])
    return _out_proj(p, cfg, out, b, l, x_slice.dtype), (ck, cv)


def attn_decode(p, cfg: ModelConfig, x_tok: jnp.ndarray, kv_cache, pos: jnp.ndarray,
                *, window: int = 0, ring: bool = False):
    """One-token decode. x_tok (B, 1, D); pos scalar int32 (current position)
    OR a per-batch (B,) vector — a continuous-batching round where every
    slot sits at its own context depth (repro.serve).

    kv_cache: (k, v) each (B, L_max, kv_heads, hd).
    ring=True: L_max == window and the cache is a ring buffer indexed by
    ``pos % window`` (bounded memory for local-attention archs at 500k+ ctx).
    """
    b = x_tok.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim > 0:
        return _attn_decode_batched(p, cfg, x_tok, kv_cache, pos,
                                    window=window, ring=ring)
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k, v = _project_qkv(p, cfg, x_tok, positions, rope=cfg.rope_theta > 0)
    ck, cv = kv_cache
    lmax = ck.shape[1]
    slot = pos % lmax if ring else pos
    ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, slot, 0, 0))
    kp = jnp.arange(lmax)[None, :]
    if ring:
        # slot i holds absolute position p_i = pos - ((pos - i) mod L_max)
        abs_pos = pos - jnp.mod(pos - kp, lmax)
        valid = abs_pos >= 0                    # window constraint is implicit
        out = attention_scores_gqa(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                   mask=valid[None])             # (1, 1, Lmax)
    elif cfg.use_kernel and window == 0:
        from repro.kernels import ops as kops
        out = kops.decode_attention(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                    pos + 1)
    else:
        valid = kp <= pos
        if window:
            valid &= kp > pos - window
        out = attention_scores_gqa(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                   mask=valid[None])             # (1, 1, Lmax)
    return _out_proj(p, cfg, out, b, 1, x_tok.dtype), (ck, cv)


def _attn_decode_batched(p, cfg: ModelConfig, x_tok: jnp.ndarray, kv_cache,
                         pos: jnp.ndarray, *, window: int, ring: bool):
    """attn_decode with a per-batch (B,) position vector: each slot writes
    its token at its OWN cache depth and attends over its own valid prefix.
    Every op is row-independent, so slot b's output depends only on slot
    b's inputs — the bit-identity the serving engine's continuous-vs-
    sequential contract rests on."""
    assert not ring, "ring caches decode a single stream (scalar pos)"
    b = x_tok.shape[0]
    positions = pos[:, None]                                   # (B, 1)
    q, k, v = _project_qkv(p, cfg, x_tok, positions, rope=cfg.rope_theta > 0)
    ck, cv = kv_cache
    lmax = ck.shape[1]
    rows = jnp.arange(b)
    ck = ck.at[rows, pos].set(k[:, 0].astype(ck.dtype))
    cv = cv.at[rows, pos].set(v[:, 0].astype(cv.dtype))
    if cfg.use_kernel and window == 0:
        from repro.kernels import ops as kops
        out = kops.decode_attention(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                    pos + 1)
    else:
        kp = jnp.arange(lmax)[None, :]
        valid = kp <= positions                                # (B, Lmax)
        if window:
            valid &= kp > positions - window
        out = attention_scores_gqa(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                   mask=valid[:, None, :])     # (B, 1, Lmax)
    return _out_proj(p, cfg, out, b, 1, x_tok.dtype), (ck, cv)


def attn_cross(p, cfg: ModelConfig, x: jnp.ndarray, enc_k: jnp.ndarray,
               enc_v: jnp.ndarray) -> jnp.ndarray:
    """Cross-attention (decoder over precomputed encoder K/V). No RoPE, no mask."""
    b, s, _ = x.shape
    hd = cfg.hd
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, -1, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
    ek, ev = enc_k.astype(q.dtype), enc_v.astype(q.dtype)
    if s > _BLOCKED_THRESHOLD or ek.shape[1] > _BLOCKED_THRESHOLD:
        out = attention_blocked_bidir(q, ek, ev)
    else:
        out = attention_scores_gqa(q, ek, ev, mask=None)
    return _out_proj(p, cfg, out, b, s, x.dtype)


def cross_kv(p, cfg: ModelConfig, enc_out: jnp.ndarray):
    """Precompute encoder K/V for cross-attention (once per sequence)."""
    b, s, _ = enc_out.shape
    hd = cfg.hd
    k = (enc_out @ p["wk"].astype(enc_out.dtype)).reshape(b, s, -1, hd)
    v = (enc_out @ p["wv"].astype(enc_out.dtype)).reshape(b, s, -1, hd)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"])
    return k, v


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                  dtype=jnp.bfloat16):
    """Stacked (layers-first) KV cache for scan-based stacks."""
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
