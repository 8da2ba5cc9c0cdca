"""End-to-end TeraPipe training: a GPT-style LM trained with the token-level
pipeline on a (data × pipe) device mesh, with checkpointing.

Default is a CPU-sized run (~20M params, 200 steps, 4 fake devices).  Pass
--full for a ~110M model (slower on CPU; the same config runs unchanged on a
real TPU mesh).

    PYTHONPATH=src python examples/terapipe_train.py [--full] [--steps 200]
"""
import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"    # host devices; never opens a chip

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.compat import make_mesh, use_mesh
from repro.core.pipeline import TeraPipeConfig, make_terapipe_loss
from repro.data.pipeline import DataPipeline, SyntheticSource
from repro.models import build_model
from repro.models.common import ModelConfig
from repro.optim.adamw import adamw, apply_updates, cosine_schedule


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--slices", type=int, default=4)
    ap.add_argument("--ckpt", default="/tmp/terapipe_example_ckpt")
    args = ap.parse_args()

    if args.full:
        cfg = ModelConfig(name="gpt-110m", family="dense", n_layers=12,
                          d_model=768, n_heads=12, n_kv_heads=12, d_ff=3072,
                          vocab_size=32000, remat=False)
    else:
        cfg = ModelConfig(name="gpt-20m", family="dense", n_layers=8,
                          d_model=384, n_heads=6, n_kv_heads=6, d_ff=1536,
                          vocab_size=8192, remat=False)

    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {n/1e6:.1f}M params on {len(jax.devices())} devices")

    n_dev = len(jax.devices())
    pipe = min(4, n_dev)
    mesh = make_mesh((n_dev // pipe, pipe), ("data", "pipe"))
    tcfg = TeraPipeConfig(n_token_slices=args.slices, n_microbatches=2,
                          data_axes=("data",))
    opt = adamw(cosine_schedule(3e-4, 20, args.steps))
    opt_state = opt.init(params)
    ckpt = CheckpointManager(args.ckpt, keep=2)

    with use_mesh(mesh):
        loss_fn, _ = make_terapipe_loss(model, specs, mesh, tcfg,
                                        args.seq, args.batch)

        @jax.jit
        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            upd, opt_state = opt.update(grads, opt_state, params)
            return apply_updates(params, upd), opt_state, loss

        data = DataPipeline(SyntheticSource(cfg.vocab_size), args.batch,
                            args.seq)
        import time
        t0 = time.time()
        for i in range(args.steps):
            params, opt_state, loss = step(params, opt_state, data.batch_at(i))
            if i % 20 == 0:
                tps = args.batch * args.seq * (i + 1) / (time.time() - t0)
                print(f"step {i:4d} loss {float(loss):.4f} ({tps:,.0f} tok/s)")
            if i and i % 100 == 0:
                ckpt.save(i, {"params": params, "opt": opt_state, "step": i})
    print(f"final loss {float(loss):.4f} "
          f"(started ~{jnp.log(cfg.vocab_size):.2f})")


if __name__ == "__main__":
    main()
