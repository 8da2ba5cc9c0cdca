"""Operations and bytes of the training step, from its shapes.

The benchmark's own copy of the arithmetic: the program's cost model is not
imported, so no change to the program moves this yardstick.

* :func:`model_flops_per_token` — forward + backward FLOPs the model needs
  per token (matmuls against weights, causal attention counted as the half
  of the score matrix it needs, the LM head); no recompute, no padding, no
  embedding lookup.
* :func:`flash_fwd_work` / :func:`flash_bwd_work` — FLOPs and HBM bytes of
  one call of the flash forward kernel, and of the dQ and dK/dV kernels
  together, for ``b`` sequences of a slice of ``l`` queries at context
  ``ctx``.
* :func:`peaks` — the chip's published peaks by ``device_kind``.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
BF16, F32 = 2, 4


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind raises."""
    table = json.loads(Path(path).read_text())
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}"
                       f" (known: {sorted(k for k in table if k[0] != '_')})")
    return table[device_kind]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_flops(cfg: dict) -> float:
    """Forward FLOPs per token of one block's weight matmuls: q, k, v, o
    projections and the three SwiGLU matrices."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    qo = 2 * d * cfg["num_attention_heads"] * hd * 2
    kv = 2 * d * cfg["num_key_value_heads"] * hd * 2
    ff = 2 * d * cfg["intermediate_size"] * 3
    return qo + kv + ff


def attention_pairs(l: int, ctx: int) -> float:
    """(query, key) pairs a causal slice of ``l`` queries at context ``ctx``
    needs: each query sees the context and the slice up to itself."""
    return l * ctx + l * (l + 1) / 2


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Training FLOPs per token (forward x 3) of a sequence of ``seq``."""
    hq_hd = cfg["num_attention_heads"] * head_dim(cfg)
    attn = 4 * hq_hd * attention_pairs(seq, 0) / seq     # QK^T + PV
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    fwd = cfg["num_hidden_layers"] * (layer_matmul_flops(cfg) + attn) + head
    return 3 * fwd


def flash_fwd_work(cfg: dict, b: int, l: int, ctx: int):
    """(FLOPs, bytes) of one flash forward call: QK^T and PV over the causal
    pairs; reads q and the keys/values up to ctx + l, writes O (bf16) and the
    log-sum-exp rows (f32)."""
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    flops = 4 * b * hq * hd * attention_pairs(l, ctx)
    nbytes = (2 * b * l * hq * hd * BF16                 # q in, O out
              + 2 * b * (ctx + l) * hkv * hd * BF16      # k, v in
              + b * hq * l * F32)                        # lse out
    return flops, nbytes


def flash_bwd_work(cfg: dict, b: int, l: int, ctx: int):
    """(FLOPs, bytes) of one backward: the dQ and dK/dV kernels together.

    FLOPs are the backward's own four matmuls per pair (dO·V^T, dS·K,
    dS^T·Q, P^T·dO), twice the forward's, without the QK^T (and dO·V^T)
    that the kernels rebuild.  Bytes: q, dO, dQ and the keys/values with
    their gradients once each, plus the lse and delta rows."""
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    flops = 8 * b * hq * hd * attention_pairs(l, ctx)
    nbytes = (3 * b * l * hq * hd * BF16                 # q, dO in; dQ out
              + 4 * b * (ctx + l) * hkv * hd * BF16      # k, v in; dK, dV out
              + 2 * b * hq * l * F32)                    # lse, delta in
    return flops, nbytes


def pipeline_geometry(cfg: dict, traffic: dict, slices, n_devices: int):
    """Per device of a contiguous pipeline over all ``n_devices``: the
    layers it holds, the microbatch rows, and the ticks of one pass
    (``D * M + K - 1``: every tick computes, idle ones included).  None for
    a schedule whose tick count this file does not know."""
    if traffic["schedule"] != "contiguous":
        return None
    k = n_devices
    d = traffic["microbatches"]
    return {"layers": cfg["num_hidden_layers"] // k,
            "rows": traffic["batch"] // d, "microbatches": d,
            "ticks": d * len(slices) + k - 1}


def flash_pass_work(cfg: dict, traffic: dict, slices, n_devices: int,
                    work=flash_fwd_work):
    """(FLOPs, bytes, calls) of one pass of a flash kernel over the step's
    useful slices on one device: each slice at its own context, unpadded,
    for every layer and microbatch the device holds.  ``calls`` is the
    number of kernel calls in that pass (layers x ticks)."""
    geo = pipeline_geometry(cfg, traffic, slices, n_devices)
    if geo is None:
        return None
    f = b = 0.0
    ctx = 0
    for l in slices:
        df, db = work(cfg, geo["rows"], l, ctx)
        f, b = f + df, b + db
        ctx += l
    n = geo["layers"] * geo["microbatches"]
    return f * n, b * n, geo["layers"] * geo["ticks"]
