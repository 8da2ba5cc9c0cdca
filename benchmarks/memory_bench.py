"""Compiled peak-memory benchmark for the registered pipeline schedules.

The 1F1B-family memory claim, measured on the ACTUAL compiled programs
instead of the schedule-IR audit: for Table-1-style shapes (fixed microbatch
size, minibatch scaled by adding microbatches D — the paper's large-D·M DP
plans), XLA's ``memory_analysis().temp_size_in_bytes`` of the fused
loss+grad step must

* grow ~linearly in D for ``contiguous`` (whole-program autodiff holds every
  work item's saved activations until the drain, plus the D·M-row outbuf),
* stay ~flat for ``1f1b``, ``interleaved-1f1b`` AND ``zb-h1`` (residual ring
  buffers of D-independent depth — ``residual_spread()`` slots per chunk,
  plus the K-tick skew buffers for the interleaved wrap handoffs; grads
  accumulated in the carry).  zb-h1 splits each backward into B + W units
  and releases a residual slot only at W, but its deferral window is O(K),
  not O(D·M), so the flat-in-D signature must survive the split — the
  zero-bubble acceptance gate alongside interleave_bench's bubble assert.

Each cell compiles in a subprocess with forced host devices (the main
process must keep its 1-CPU invariant).  ``--quick`` (the ``make
bench-smoke`` entry) runs the corner grid (D ∈ {1, 4}); the full mode adds
``interleaved`` and the middle D.
"""
import argparse
import os
import subprocess
import sys
import textwrap
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

K, M, SEQ = 2, 2, 64     # tiny CPU-compilable stand-in for Table-1 ratios

_CELL_CODE = """
    import jax, jax.numpy as jnp
    from repro.compat import make_mesh, use_mesh
    from repro.models.common import ModelConfig
    from repro.models import build_model
    from repro.core.pipeline import TeraPipeConfig, make_terapipe_value_and_grad
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256,
                      dtype=jnp.float32, remat=False)
    model = build_model(cfg)
    _, specs = model.init(jax.random.PRNGKey(0))
    D, B, S = {D}, 2 * {D}, {S}
    batch = {{"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
              "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}}
    structs = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    mesh = make_mesh((1, {K}), ("data", "pipe"))
    tcfg = TeraPipeConfig(n_token_slices={M}, n_microbatches=D,
                          data_axes=("data",), cache_dtype=jnp.float32,
                          schedule="{sched}",
                          virtual_stages={V})
    with use_mesh(mesh):
        vg, _ = make_terapipe_value_and_grad(model, specs, mesh, tcfg, S, B)
        comp = jax.jit(vg).lower(structs, batch).compile()
    m = comp.memory_analysis()
    print("TEMP_BYTES", m.temp_size_in_bytes, flush=True)
"""


def _cell(sched: str, D: int) -> int:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={K}",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    code = textwrap.dedent(_CELL_CODE).format(
        D=D, S=SEQ, K=K, M=M, sched=sched,
        V=2 if "interleaved" in sched else 1)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    return int(r.stdout.split("TEMP_BYTES")[1].split()[0])


def run(emit, quick: bool = False):
    schedules = ("contiguous", "1f1b", "interleaved-1f1b", "zb-h1") if quick \
        else ("contiguous", "interleaved", "1f1b", "interleaved-1f1b",
              "zb-h1")
    ds = (1, 4) if quick else (1, 2, 4)
    temp = {}
    for sched in schedules:
        for D in ds:
            temp[sched, D] = _cell(sched, D)
            emit(f"memory/{sched}_K{K}_M{M}_D{D}_temp_bytes",
                 float(temp[sched, D]),
                 f"temp={temp[sched, D]/2**20:.2f}MiB")
    d_lo, d_hi = ds[0], ds[-1]
    growth = {s: temp[s, d_hi] / temp[s, d_lo] for s in schedules}
    for s, g in growth.items():
        emit(f"memory/{s}_growth_D{d_lo}to{d_hi}", g * 1e6, f"x{g:.2f}")
    # the acceptance assertions: compiled peak activation memory flat in
    # D·M for the explicit-bwd (1F1B-family) schedules, growing (~linearly)
    # for the autodiff-backward schedules
    assert growth["contiguous"] > 1.0 + 0.3 * (d_hi / d_lo - 1), growth
    assert growth["1f1b"] < 1.8, growth
    assert temp["1f1b", d_hi] < temp["contiguous", d_hi] / 2, temp
    # interleaved 1F1B: the same flat-in-D bound as plain 1F1B (its skew +
    # per-chunk residual buffers are a D-independent constant — at this tiny
    # shape roughly 2x plain 1f1b's bytes — while contiguous keeps growing),
    # and still far below the autodiff schedules' drain-time peak
    assert growth["interleaved-1f1b"] < 1.8, growth
    assert temp["interleaved-1f1b", d_hi] < temp["contiguous", d_hi] / 2, temp
    # zb-h1: deferring W into the drain must NOT cost flat-in-D memory —
    # temp bytes grow no faster than plain 1f1b's (W releases the residual
    # slot O(K) ticks after B, a D-independent window) and stay well under
    # the autodiff drain-time peak.  Both schedules' ring geometry
    # (residual_spread, peak_live_items) saturates at its D-independent cap
    # only at D >= 2 — the D=1 cell sits below the cap (and zb-h1's shorter
    # table compiles to a smaller baseline there), so a D1-anchored ratio
    # overstates growth; the flat-in-D claim is the SATURATED slope, so
    # compare D_mid -> D_hi against plain 1f1b's over the same range
    d_mid = max(2, d_hi // 2)
    for s in ("1f1b", "zb-h1"):
        if (s, d_mid) not in temp:
            temp[s, d_mid] = _cell(s, d_mid)
    sat = {s: temp[s, d_hi] / temp[s, d_mid] for s in ("1f1b", "zb-h1")}
    emit(f"memory/zb-h1_growth_D{d_mid}to{d_hi}", sat["zb-h1"] * 1e6,
         f"x{sat['zb-h1']:.3f} (1f1b x{sat['1f1b']:.3f})")
    assert sat["zb-h1"] <= sat["1f1b"] * 1.05, (sat, temp)
    assert temp["zb-h1", d_hi] < temp["contiguous", d_hi] / 2, temp
    if "interleaved" in schedules:
        assert growth["interleaved"] > 1.5, growth
    return temp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="4-cell corner grid (CI smoke); assertions run in "
                    "every mode")
    args = ap.parse_args()

    def emit(name, us, derived=""):
        print(f"{name},{us:.1f},{derived}", flush=True)

    run(emit, quick=args.quick)
    print("memory_bench: OK", flush=True)


if __name__ == "__main__":
    main()
