"""Plain float32 reference of the dense decoder the training cells run.

Written from the published equations, in ``jax.numpy`` with every matmul
at ``Precision.HIGHEST``; it imports nothing of the program.  It reads the
parameters in the program's layout (layers stacked on a leading axis; norm
weights applied as ``1 + scale``), which is the interface of the system
under test, not its code.

Block (per layer): pre-RMSNorm attention with q/k/v/o projections, optional
per-head RMSNorm of q and k (Qwen3), rotary embeddings on the two halves of
each head, causal grouped-query softmax attention scaled by
``1/sqrt(head_dim)``; then pre-RMSNorm SwiGLU (``silu(x Wg) * (x Wu) Wd``).
Final RMSNorm, output head (the embedding, transposed, when tied), mean
token cross-entropy.  AdamW with global-norm clipping follows the
hyperparameters of the cell's traffic file.

``prec="fp8"`` is the control: every matmul operand, forward and backward,
rounded to float8 e4m3 with a per-tensor scale before a float32 product,
one step of precision below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from weights import leaf_norms

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 1024          # query rows per attention block
LOSS_CHUNK = 256        # positions per block of the output head


def _fp8(x):
    """Round to float8 e4m3 with a per-tensor scale (largest |x| to 448)."""
    s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8_operand(x):
    return _fp8(x)


# the backward's matmuls take the rounded forward operand (saved) and an
# incoming gradient rounded the same way, so every matmul operand is fp8
_fp8_operand.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))


def _round(x, prec):
    if prec == "f32":
        return x
    if prec == "fp8":
        return _fp8_operand(x)
    raise ValueError(f"unknown precision {prec!r}")


def _mm(a, b, prec):
    return jnp.matmul(_round(a, prec), _round(b, prec), precision=HIGHEST)


def _ein(spec, a, b, prec):
    return jnp.einsum(spec, _round(a, prec), _round(b, prec),
                      precision=HIGHEST)


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale)


def rope(x, pos, theta):
    """x (B, S, H, hd): rotate the pairs (i, i + hd/2) by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]      # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, prec):
    """Causal GQA attention, one block of query rows at a time (each block
    sees the keys up to its last row)."""
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    outs = []
    for lo in range(0, s, Q_CHUNK):
        hi = min(s, lo + Q_CHUNK)

        @jax.checkpoint
        def block(qc, kc, vc, lo=lo, hi=hi):
            qg = qc.reshape(b, hi - lo, hkv, rep, hd)
            sc = _ein("bqgrd,bkgd->bgrqk", qg, kc, prec) / math.sqrt(hd)
            qp = jnp.arange(lo, hi)[:, None]
            kp = jnp.arange(hi)[None, :]
            sc = jnp.where(qp >= kp, sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            o = _ein("bgrqk,bkgd->bqgrd", p, vc, prec)
            return o.reshape(b, hi - lo, hq, hd)
        outs.append(block(q[:, lo:hi], k[:, :hi], v[:, :hi]))
    return jnp.concatenate(outs, axis=1)


def _layer(cfg, prec, x, lp):
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    b, s, _ = x.shape
    pos = jnp.arange(s)
    a = lp["attn"]
    h = rms_norm(x, lp["ln_attn"], eps)
    q = _mm(h, a["wq"], prec).reshape(b, s, -1, hd)
    k = _mm(h, a["wk"], prec).reshape(b, s, -1, hd)
    v = _mm(h, a["wv"], prec).reshape(b, s, -1, hd)
    if cfg.get("qk_norm"):
        q = rms_norm(q, a["q_norm"], eps)
        k = rms_norm(k, a["k_norm"], eps)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    o = attention(q, k, v, prec).reshape(b, s, -1)
    x = x + _mm(o, a["wo"], prec)
    f = lp["ffn"]
    h = rms_norm(x, lp["ln_ffn"], eps)
    g = _mm(h, f["w_gate"], prec)
    x = x + _mm(jax.nn.silu(g) * _mm(h, f["w_up"], prec), f["w_down"], prec)
    return x


def loss(cfg, prec, params, tokens, labels):
    """Mean next-token cross-entropy of the batch."""
    x = params["embed"][tokens]
    blocks = params["groups"]["blocks"]
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, lp: (_layer(cfg, prec, x, lp), None)),
        x, blocks)
    x = rms_norm(x, params["final_ln"], cfg["rms_norm_eps"])
    w = (params["embed"].T if cfg["tie_word_embeddings"]
         else params["lm_head"])
    b, s, d = x.shape
    n = s // LOSS_CHUNK if s % LOSS_CHUNK == 0 else 1
    xs = jnp.moveaxis(x.reshape(b, n, s // n, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, n, s // n), 1, 0)

    @jax.checkpoint
    def chunk(tot, xl):
        xc, lc = xl
        logits = _mm(xc, w, prec)
        gold = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        return tot + jnp.sum(jax.nn.logsumexp(logits, -1) - gold), None
    tot, _ = jax.lax.scan(chunk, jnp.float32(0), (xs, ls))
    return tot / (b * s)


def lr_at(opt, step):
    """Cosine schedule with linear warm-up, at optimizer step ``step``."""
    peak, warm, total = opt["lr"], opt["warmup"], opt["total_steps"]
    floor = opt["min_lr_ratio"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * prog)))


def adamw(opt, step, params, m, v, grads):
    """One AdamW step (moments from zero, bias-corrected, decoupled weight
    decay) after clipping the gradients to global norm ``clip_norm``.
    Returns (params, m, v, per-leaf norms of the clipped gradient, each
    layer's share of a stacked leaf apart, ``weights.leaf_norms``)."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    gl = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in gl))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    c1, c2, lr = 1 - b1 ** step, 1 - b2 ** step, lr_at(opt, step)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p),
        params, m, v)
    return params, m, v, leaf_norms(grads)


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items, opt_items, prec):
    cfg, opt = dict(cfg_items), dict(opt_items)
    grad = jax.jit(jax.value_and_grad(functools.partial(loss, cfg, prec)))
    updates = {t: jax.jit(functools.partial(adamw, opt, t),
                          donate_argnums=(0, 1, 2, 3)) for t in (1, 2, 3)}
    return grad, updates


def _freeze(d):
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float, bool, str))))


def train_steps(cfg, opt, params, batches, prec="f32"):
    """Follow the program's first steps from ``params`` on ``batches`` (a
    list of (tokens, labels)).  Returns (losses, first clipped gradient's
    per-leaf norms, params after the last step)."""
    grad, updates = _compiled(_freeze(cfg), _freeze(opt), prec)
    with jax.default_matmul_precision("highest"):
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, first = [], None
        for t, (tokens, labels) in enumerate(batches, start=1):
            lval, grads = grad(params, tokens, labels)
            losses.append(float(lval))
            params, m, v, norms = updates[t](params, m, v, grads)
            if first is None:
                first = [float(n) for n in jax.device_get(norms)]
        del m, v
    return losses, first, params
