"""Kernel microbenchmarks + memory-shape assertions for the fused attention.

Timing cells sweep (l, ctx) over the pure-jnp reference and the fused Pallas
op (fwd and fwd+bwd, dense and GQA) — the empirical t_fwd/t_bwd(l, ctx)
table the DP can consume via TableCostModel / measure_kernel_cost_table.
(The Pallas kernels run in interpret mode on this CPU container; TPU is the
compile target.)

Self-asserting cells (``--assert-only``, the ``make bench-smoke`` entry)
check the ISSUE-4 memory claims on the ACTUAL compiled programs:

* HBM traffic of the fused op — fwd AND grad, dense AND GQA — stays LINEAR
  in ctx+l (``cost_analysis()`` bytes accessed; the dense reference's
  score matrix would scale quadratically);
* no intermediate in the jaxpr has an (l, ctx+l)-shaped score-matrix buffer
  or a GQA-repeated (Sk, Hq) K/V buffer, in forward or backward — via the
  ``repro.analysis`` buffer rules (the walker lives there now, not here);
* the analyzer itself has teeth: the same rule FIRES on the dense
  reference's jaxpr (which really does materialize the score matrix).
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp

from repro.analysis import raise_on_errors, rules as arules
from repro.kernels import ops as kops
from repro.kernels.ref import terapipe_attention_ref


def _time(fn, *args, n=10):
    jax.tree.leaves(fn(*args))[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        jax.tree.leaves(fn(*args))[0].block_until_ready()
    return (time.perf_counter() - t0) / n


def _qkv(l, ctx, hq, hkv, hd=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, l, hq, hd), jnp.float32)
    k = jax.random.normal(ks[1], (1, ctx + l, hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (1, ctx + l, hkv, hd), jnp.float32)
    return q, k, v


# ---------------------------------------------------------------------------
# memory-shape assertions (via the repro.analysis buffer rules)
# ---------------------------------------------------------------------------
def _audit_jaxpr(fn, args, *, l, sk, hq, hkv, tag):
    """No (l, sk) score-matrix dims and no GQA-repeated (sk, hq) K/V dims
    anywhere in the jaxpr of ``fn`` (rules: buffer.score-matrix,
    buffer.repeated-kv)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    findings = arules.check_score_matrix(jaxpr, l=l, sk=sk)
    findings += arules.check_kv_repeat(jaxpr, sk=sk, hq=hq, hkv=hkv)
    raise_on_errors(findings, context=tag)


def _bytes_accessed(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis()
    return float(cost.get("bytes accessed", 0.0))


def run_analyzer_self_assert(emit):
    """The analyzer has teeth: the dense reference DOES materialize the
    (l, ctx+l) score matrix, and buffer.score-matrix must flag it (a rule
    regression would silently green-light every fused-kernel claim)."""
    l, ctx, hq, hd = 64, 64, 4, 32
    q, k, v = _qkv(l, ctx, hq, hq, hd)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: terapipe_attention_ref(q, k, v, ctx))(q, k, v)
    findings = arules.check_score_matrix(jaxpr, l=l, sk=ctx + l)
    fired = [f for f in findings if f.severity == "error"]
    assert fired, ("buffer.score-matrix failed to fire on the dense "
                   "reference — the analyzer lost its teeth")
    emit("kernel/analysis_self_assert", 0.0,
         f"rule={fired[0].rule} eqn={fired[0].eqn} n={len(fired)}")


def run_asserts(emit):
    """Fused fwd and bwd, dense and GQA: linear HBM traffic + clean jaxprs."""
    run_analyzer_self_assert(emit)
    l, hd = 128, 64
    for hq, hkv in ((4, 4), (8, 2)):
        tag = "dense" if hq == hkv else f"gqa{hq}/{hkv}"
        fwd = lambda q, k, v, c: kops.terapipe_attention(q, k, v, ctx_len=c)

        def grads(q, k, v, c):
            out, vjp = jax.vjp(lambda q, k, v: fwd(q, k, v, c), q, k, v)
            return vjp(jnp.ones_like(out))

        byt = {}
        for ctx in (896, 1920):
            sk = ctx + l
            args = _qkv(l, ctx, hq, hkv, hd) + (jnp.int32(ctx),)
            _audit_jaxpr(fwd, args, l=l, sk=sk, hq=hq, hkv=hkv,
                         tag=f"{tag}-fwd")
            _audit_jaxpr(grads, args, l=l, sk=sk, hq=hq, hkv=hkv,
                         tag=f"{tag}-bwd")
            byt[ctx] = (_bytes_accessed(fwd, args), _bytes_accessed(grads, args))
        for i, kind in enumerate(("fwd", "bwd")):
            b1, b2 = byt[896][i], byt[1920][i]
            # ctx+l doubles (1024 -> 2048): linear HBM doubles, a quadratic
            # score matrix would 4x.  Slack for the ctx-independent terms.
            ratio = b2 / max(b1, 1.0)
            assert ratio < 2.6, (
                f"{tag}-{kind}: bytes accessed scaled x{ratio:.2f} when "
                f"ctx+l doubled — superlinear HBM traffic "
                f"({b1:.3e} -> {b2:.3e})")
            emit(f"kernel/hbm_{tag}_{kind}", 0.0,
                 f"bytes@1k={b1:.3e} bytes@2k={b2:.3e} ratio={ratio:.2f}")
    print("kernel_bench asserts: OK", flush=True)


# ---------------------------------------------------------------------------
# timing cells
# ---------------------------------------------------------------------------
def run_timings(emit):
    """Fused cells come from measure_kernel_cost_table — the ONE timing
    harness (repro.core.cost_model) the DP planner also consumes — so the
    bench numbers and the planner's t_fwd/t_bwd entries cannot drift."""
    from repro.core.cost_model import measure_kernel_cost_table

    ref = jax.jit(lambda q, k, v, c: terapipe_attention_ref(q, k, v, c),
                  static_argnums=3)
    pairs = [(128, 0), (128, 512), (128, 1920),
             (512, 0), (512, 1536), (1024, 1024)]
    tab = measure_kernel_cost_table(pairs, n_heads=8, head_dim=64)
    for l, ctx in pairs:
        q, k, v = _qkv(l, ctx, 8, 8)
        flops = 4 * l * (ctx + l / 2) * 8 * 64
        dt = _time(ref, q, k, v, ctx)
        emit(f"kernel/ref_l{l}_ctx{ctx}", dt * 1e6,
             f"gflops={flops / dt / 1e9:.1f}")
        dt = tab.t_fwd(l, ctx)
        emit(f"kernel/fused_fwd_l{l}_ctx{ctx}", dt * 1e6,
             f"gflops={flops / dt / 1e9:.1f}")
        dt = tab.t_fwd(l, ctx) + tab.t_bwd(l, ctx)
        emit(f"kernel/fused_fwdbwd_l{l}_ctx{ctx}", dt * 1e6,
             f"gflops={4.5 * flops / dt / 1e9:.1f}")
    # GQA cell: repeated-KV HBM expansion would 4x the K/V traffic
    gtab = measure_kernel_cost_table([(256, 768)], n_heads=8, n_kv_heads=2,
                                     head_dim=64)
    emit("kernel/fused_fwd_gqa8_2_l256_ctx768", gtab.t_fwd(256, 768) * 1e6, "")
    emit("kernel/fused_fwdbwd_gqa8_2_l256_ctx768",
         (gtab.t_fwd(256, 768) + gtab.t_bwd(256, 768)) * 1e6, "")


def run(emit, assert_only: bool = False):
    run_asserts(emit)
    if not assert_only:
        run_timings(emit)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--assert-only", action="store_true",
                    help="memory-shape assertions only (CI smoke); skip the "
                    "timing sweep")
    args = ap.parse_args()

    def emit(name, us, derived=""):
        print(f"{name},{us:.1f},{derived}", flush=True)

    run(emit, assert_only=args.assert_only)
    print("kernel_bench: OK", flush=True)


if __name__ == "__main__":
    main()
