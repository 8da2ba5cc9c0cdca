"""Smoke run of the training and serving paths on TPU chips.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: pipeline phase only

One chip, in one process:

* kernels — the compiled flash fwd/bwd and decode kernels against the jnp
  references in ``repro.kernels.ref`` at qwen3-0.6b widths (bf16);
* train — qwen3-0.6b at full depth and width through ``repro.launch.train``
  in ``--mode terapipe`` (the executor on a (data=1, pipe=1) mesh, 4 token
  slices, the Pallas kernels), batch 1 x seq 2048, 3 steps.  Before step 1
  the executor's step-0 loss is compared with a plain-jnp ``model.loss``
  on the same params and batch; the compiled step must hold the kernels
  (``tpu_custom_call``);
* serve — 4 requests through ``DecodeEngine`` with the trained params; each
  greedy token is checked against a teacher-forced jnp forward.

Four chips (``--chips 4``): gpt3-1b at full depth and width on a
(data=1, pipe=4) mesh, batch 4 x seq 2048 in 4 microbatches, 3 steps of
terapipe (contiguous schedule, ``--dp-plan`` slices) and of gpipe (one
token slice).  Their step-0 losses must agree, and each device must hold
only its stage's share of the params.

Any failed check raises, so the script exits non-zero; it does the same
when JAX finds no TPU.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# tolerances, stated up front
KERNEL_TOL = 2e-2      # max |kernel - ref| / max |ref|, bf16 in and out
LOSS_TOL = 1e-2        # |executor step-0 loss - jnp model.loss|, absolute
PIPE_LOSS_TOL = 1e-2   # |terapipe step-0 loss - gpipe step-0 loss|
GREEDY_TOL = 0.02      # (ref max logit - ref logit of the token) / ref spread
STEPS = 3


def check(ok, msg):
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def _devices(count):
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX sees {devs[0].platform} devices")
    check(len(devs) == count,
          f"--chips {count} needs {count} TPU devices, JAX sees {len(devs)}")
    return devs


def _gib(n):
    return n / 2 ** 30


def _peaks(devs):
    return [d.memory_stats()["peak_bytes_in_use"] for d in devs]


def _cache_entries(path):
    p = Path(path)
    return len(list(p.iterdir())) if p.is_dir() else 0


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-6))


# ---------------------------------------------------------------- kernels
def phase_kernels():
    from repro.kernels import ops
    from repro.kernels.ref import decode_attention_ref, terapipe_attention_ref

    b, l, sk, hq, hkv, hd = 1, 512, 2048, 16, 8, 128   # qwen3-0.6b slice
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (b, l, hq, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, sk, hkv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, sk, hkv, hd), jnp.bfloat16)
    g = jax.random.normal(ks[3], (b, l, hq, hd), jnp.bfloat16)
    for ctx in (0, 1024, sk - l):
        c = jnp.int32(ctx)      # traced: the executor's scalar-prefetch path
        out, vjp = jax.vjp(
            lambda q, k, v: ops.terapipe_attention(q, k, v, ctx_len=c),
            q, k, v)
        grads = vjp(g)
        f32 = [a.astype(jnp.float32) for a in (q, k, v)]
        ref, ref_vjp = jax.vjp(
            lambda q, k, v: terapipe_attention_ref(q, k, v, c), *f32)
        ref_grads = ref_vjp(g.astype(jnp.float32))
        errs = [_rel_err(out, ref)] + [_rel_err(a, r)
                                       for a, r in zip(grads, ref_grads)]
        print(f"[kernels] flash ctx={ctx}: rel err out/dq/dk/dv "
              + " ".join(f"{e:.2e}" for e in errs))
        check(max(errs) < KERNEL_TOL, f"flash kernel ctx={ctx} errs {errs}")
    kv_len = jnp.asarray([1, 700, 1500, sk], jnp.int32)
    qd = jax.random.normal(ks[4], (4, 1, hq, hd), jnp.bfloat16)
    kd, vd = (jnp.broadcast_to(a, (4,) + a.shape[1:]) for a in (k, v))
    out = ops.decode_attention(qd, kd, vd, kv_len)
    ref = decode_attention_ref(qd.astype(jnp.float32),
                               jnp.repeat(kd, hq // hkv, 2).astype(jnp.float32),
                               jnp.repeat(vd, hq // hkv, 2).astype(jnp.float32),
                               kv_len)
    err = _rel_err(out, ref)
    print(f"[kernels] decode kv_len={kv_len.tolist()}: rel err {err:.2e}")
    check(err < KERNEL_TOL, f"decode kernel err {err}")


# ------------------------------------------------------------------ train
def _train(argv, cache_dir, ref_loss_fn=None):
    """Set up ``repro.launch.train`` for ``argv``, compile its step (timed,
    donated), check the kernels are in it, run STEPS steps.  Returns
    (run, params, losses)."""
    from repro.compat import use_mesh
    from repro.launch import train

    args = train.parse_args(argv)
    run = train.setup(args)
    params, opt_state = run.params, run.opt_state
    batch = run.data.batch_at(0)
    with use_mesh(run.mesh):
        ref = None
        if ref_loss_fn is not None:
            ref = float(ref_loss_fn(params, batch))
        step = jax.jit(run.train_step, donate_argnums=(0, 1))
        entries = _cache_entries(cache_dir)
        t0 = time.perf_counter()
        compiled = step.lower(params, opt_state, batch).compile()
        t_compile = time.perf_counter() - t0
        print(f"[train] {args.arch} {args.mode}: compile {t_compile:.3f} s "
              f"(compile-cache entries before {entries}, after "
              f"{_cache_entries(cache_dir)})")
        mem = compiled.memory_analysis()
        n_kernels = compiled.as_text().count("tpu_custom_call")
        print(f"[train] compiled step per device: arguments "
              f"{_gib(mem.argument_size_in_bytes):.3f} GiB, temporaries "
              f"{_gib(mem.temp_size_in_bytes):.3f} GiB; tpu_custom_call "
              f"sites: {n_kernels}")
        check(n_kernels > 0, "no Pallas kernel in the compiled step")
        losses = []
        for i in range(STEPS):
            batch = run.data.batch_at(i)
            t0 = time.perf_counter()
            params, opt_state, loss = compiled(params, opt_state, batch)
            jax.block_until_ready((params, opt_state, loss))
            dt = time.perf_counter() - t0
            losses.append(float(loss))
            print(f"[train] step {i}: loss {losses[-1]:.6f} "
                  f"{dt:.4f} s ({batch['tokens'].size / dt:.1f} tok/s)")
        check(all(math.isfinite(x) for x in losses), f"losses {losses}")
        if ref is not None:
            diff = abs(losses[0] - ref)
            print(f"[train] step-0 loss {losses[0]:.6f} vs jnp model.loss "
                  f"{ref:.6f}: |diff| {diff:.3e} (tol {LOSS_TOL})")
            check(diff < LOSS_TOL, f"executor loss off by {diff}")
    del opt_state
    return run, params, losses


def phase_train(cache_dir):
    from repro.configs import get_config
    from repro.models import build_model

    ref_model = build_model(get_config("qwen3-0.6b").replace(use_kernel=False))
    run, params, _ = _train(
        ["--arch", "qwen3-0.6b", "--mode", "terapipe", "--batch", "1",
         "--seq", "2048", "--token-slices", "4", "--use-kernel",
         "--steps", str(STEPS)], cache_dir, jax.jit(ref_model.loss))
    return run.cfg, params


# ------------------------------------------------------------------ serve
def phase_serve(cfg, params):
    from repro.models import build_model
    from repro.serve import DecodeEngine, EngineConfig

    n_req, plen, gen = 4, 64, 16
    model = build_model(cfg.replace(use_kernel=True))
    engine = DecodeEngine(model, params, EngineConfig(
        max_batch=4, max_len=128, page_size=16, n_pages=4 * 8 + 1))
    rng = np.random.RandomState(1)
    prompts = rng.randint(0, cfg.vocab_size, size=(n_req, plen))
    rids = [engine.submit(p.tolist(), gen) for p in prompts]
    t0 = time.perf_counter()
    engine.run()
    dt = time.perf_counter() - t0
    done = [engine.finished.get(r) for r in rids]
    check(all(r is not None and len(r.generated) == gen for r in done),
          "not every request finished")
    print(f"[serve] {n_req} requests x {gen} tokens in {engine.rounds} "
          f"rounds, {dt:.3f} s (compiles included)")

    # teacher-forced jnp reference: every greedy token must be the
    # reference's argmax up to GREEDY_TOL of the logit spread
    ref_model = build_model(cfg.replace(use_kernel=False))
    seqs = np.concatenate([prompts, [r.generated for r in done]], axis=1)
    logits = np.asarray(jax.jit(ref_model.forward)(
        params, {"tokens": jnp.asarray(seqs[:, :-1], jnp.int32)}))
    worst = 0.0
    for i, r in enumerate(done):
        for j, tok in enumerate(r.generated):
            row = logits[i, plen - 1 + j]
            worst = max(worst, float((row.max() - row[tok])
                                     / (row.max() - row.min())))
    print(f"[serve] worst greedy gap vs jnp reference: {worst:.2e} of the "
          f"logit spread (tol {GREEDY_TOL})")
    check(worst <= GREEDY_TOL, f"greedy tokens off the reference: {worst}")


# -------------------------------------------------------------- pipeline
def phase_pipeline(devs, cache_dir):
    common = ["--arch", "gpt3-1b", "--batch", "4", "--seq", "2048",
              "--microbatches", "4", "--use-kernel", "--steps", str(STEPS)]
    total = None
    step0 = {}
    for mode, extra in (("terapipe", ["--dp-plan"]), ("gpipe", [])):
        run, params, losses = _train(common + ["--mode", mode] + extra,
                                     cache_dir)
        step0[mode] = losses[0]
        leaves = jax.tree.leaves(params)
        total = sum(a.nbytes for a in leaves)
        held = [sum(s.data.nbytes for a in leaves
                    for s in a.addressable_shards if s.device == d)
                for d in devs]
        print(f"[pipeline] {mode}: params {_gib(total):.3f} GiB in all; per "
              f"device " + " ".join(f"{_gib(h):.3f}" for h in held)
              + " GiB; peak_bytes_in_use "
              + " ".join(f"{_gib(p):.3f}" for p in _peaks(devs)) + " GiB")
        check(max(held) < 0.5 * total,
              f"{mode}: a device holds {max(held)} of {total} param bytes")
        del run, params
    diff = abs(step0["terapipe"] - step0["gpipe"])
    print(f"[pipeline] step-0 loss terapipe {step0['terapipe']:.6f} gpipe "
          f"{step0['gpipe']:.6f}: |diff| {diff:.3e} (tol {PIPE_LOSS_TOL})")
    check(diff < PIPE_LOSS_TOL, f"terapipe and gpipe disagree by {diff}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = _devices(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    kind = devs[0].device_kind
    print(f"[device] {kind} x {len(devs)} ({devs[0].platform}); jax "
          f"{jax.__version__}; compile cache {cache_dir}")
    if args.chips == 1:
        phase_kernels()
        cfg, params = phase_train(cache_dir)
        print(f"[train] peak_bytes_in_use {_gib(_peaks(devs)[0]):.3f} GiB")
        phase_serve(cfg, params)
    else:
        phase_pipeline(devs, cache_dir)
    print("[peak_bytes_in_use] " + " ".join(
        f"{_gib(p):.3f}" for p in _peaks(devs)) + " GiB")
    print(f"[memory_stats] device 0: {devs[0].memory_stats()}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
