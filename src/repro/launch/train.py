"""End-to-end training driver.

Runs on anything from 1 CPU (smoke configs) to the production mesh:

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 200 --batch 8 --seq 256 --mode gspmd

Features (DESIGN.md §6): checkpoint/restart (atomic, resumable, exact data
position), supervisor loop that restores the last checkpoint on step failure,
optional fault injection, TeraPipe / GPipe / GSPMD execution modes with
selectable pipeline schedule (contiguous / interleaved / 1f1b), straggler
re-planning hook, throughput logging.

Fault tolerance vs buffer donation
----------------------------------

The train step donates ``params``/``opt_state`` (halves peak optimizer
memory), which DELETES the input buffers whenever the step has dispatched —
including a step that then faults.  The supervisor therefore only donates
when a checkpoint directory is configured (restore is the recovery path; the
restore target is rebuilt from ShapeDtypeStructs captured at init, never
from possibly-deleted live arrays).  Without ``--checkpoint-dir`` the
supervisor keeps donation OFF so the pre-step ``params``/``opt_state``
references stay alive as the rescue copy for the retry path.
"""
from __future__ import annotations

import argparse
import sys
import time
from types import SimpleNamespace

import jax
import numpy as np

from repro import tracing
from repro.checkpoint.manager import CheckpointManager
from repro.compat import make_mesh, use_mesh
from repro.configs import get_config
from repro.core.schedules import (check_virtual_stages, schedule_help,
                                  schedule_names)
from repro.data.pipeline import DataPipeline, SyntheticSource
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim.adamw import adamw, apply_updates, cosine_schedule


def build_value_and_grad(model, specs, mesh, args):
    """``(vg_fn, param_shardings_fn)`` for the selected execution mode:
    ``vg_fn(params, batch) -> (loss, grads)``; the shardings function is
    None for gspmd."""
    if args.mode == "gspmd":
        return jax.value_and_grad(model.loss), None
    from repro.core.pipeline import (TeraPipeConfig,
                                     make_terapipe_value_and_grad)
    schedule = args.schedule
    if args.virtual_stages > 1 and schedule == "contiguous":
        schedule = "interleaved"   # V>1 implies interleaving (back-compat);
        # promote BEFORE the plan post-pass so it applies the interleaved
        # divisibility constraint
    slice_lens = None
    if args.mode == "terapipe" and args.dp_plan:
        # Algorithm 1 end-to-end: plan the slicing with the DP, execute it
        from repro.core.cost_model import AnalyticCostModel, hardware_for
        from repro.core.dp import (ensure_executable, optimal_slicing,
                                   plan_schedule_info)
        hw = hardware_for(jax.devices()[0].device_kind)
        K = mesh.shape["pipe"]
        cm = AnalyticCostModel(model.cfg, hw,
                               layers_per_stage=max(1, model.n_blocks // K))
        g = max(1, args.seq // 16)
        plan = optimal_slicing(cm, args.seq, K, granularity=g,
                               virtual_stages=args.virtual_stages)
        # schedule-aware executability post-pass (e.g. the interleaved
        # schedules need D*M % K == 0; splitting the largest slices never
        # raises t_max)
        slices = ensure_executable(plan.slices, schedule=schedule,
                                   n_ranks=K,
                                   n_microbatches=args.microbatches,
                                   granularity=g)
        slice_lens = tuple(slices)
        info = plan_schedule_info(slice_lens, schedule=schedule, n_ranks=K,
                                  virtual_stages=args.virtual_stages,
                                  n_microbatches=args.microbatches)
        print(f"[dp-plan] slices {list(slice_lens)} "
              f"(predicted {plan.latency*1e3:.1f} ms/iter; "
              + " ".join(f"{k}={v}" for k, v in info.items()) + ")")
        # rank EVERY registered schedule on this plan (ROADMAP: the DP
        # should pick the winning schedule, not just evaluate the requested
        # one): per schedule, apply its executability post-pass, price the
        # resulting fwd(+typed bwd) tick table with the same analytic model,
        # and report the argmin alongside its memory geometry
        from repro.core.schedule import SlicingScheme
        from repro.core.schedules import REGISTRY
        from repro.core.simulator import simulate
        cm_u = AnalyticCostModel(model.cfg, hw,
                                 layers_per_stage=max(1, model.n_blocks // K),
                                 include_backward=False)
        D = args.microbatches
        best = None
        for name, spec in REGISTRY.items():
            V = (max(args.virtual_stages, spec.min_virtual)
                 if spec.max_virtual is None else spec.min_virtual)
            sl = ensure_executable(plan.slices, schedule=name, n_ranks=K,
                                   n_microbatches=D, granularity=g)
            sch = SlicingScheme.from_dp(args.seq, D, [(1, list(sl))] * D)
            if spec.has_backward:
                from repro.core.schedules import (KIND_BWD, KIND_BWD_INPUT,
                                                  KIND_BWD_WEIGHT)
                lat = simulate(
                    sch, K, lambda b, l, c: cm_u.unit_cost(l, c),
                    discipline=name, virtual_stages=V, include_backward=True,
                    t_bwd_of=lambda b, l, c: cm_u.unit_cost(
                        l, c, kind=KIND_BWD),
                    t_bwd_input_of=lambda b, l, c: cm_u.unit_cost(
                        l, c, kind=KIND_BWD_INPUT),
                    t_bwd_weight_of=lambda b, l, c: cm_u.unit_cost(
                        l, c, kind=KIND_BWD_WEIGHT))
            else:
                disc = "lockstep" if name == "contiguous" else name
                lat = simulate(sch, K, lambda b, l, c: cm(l, c),
                               discipline=disc, virtual_stages=V)
            sinfo = plan_schedule_info(sl, schedule=name, n_ranks=K,
                                       virtual_stages=V, n_microbatches=D)
            print(f"[dp-plan]   {name:<17} V={V} {lat*1e3:10.3f} ms/iter  "
                  + " ".join(f"{k}={v}" for k, v in sinfo.items()))
            if best is None or lat < best[1]:
                best = (name, lat, V)
        print(f"[dp-plan] winner: {best[0]} (V={best[2]}, "
              f"{best[1]*1e3:.3f} ms/iter simulated fwd+bwd)")
    tcfg = TeraPipeConfig(
        n_token_slices=args.token_slices if args.mode == "terapipe" else 1,
        slice_lens=slice_lens,
        n_microbatches=args.microbatches,
        pipe_axis="pipe", tp_axis=None, data_axes=("data",),
        unroll=args.unroll,
        schedule=schedule,
        virtual_stages=args.virtual_stages,
        use_kernel=True if args.use_kernel else None)
    return make_terapipe_value_and_grad(model, specs, mesh, tcfg, args.seq,
                                        args.batch)


def _param_specs(model, key):
    """The logical-axis spec tree of the model's params, from an abstract
    trace of its init (nothing is allocated)."""
    box = []

    def trace(k):
        params, specs = model.init(k)
        box.append(specs)
        return params
    jax.eval_shape(trace, key)
    return box[0]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--mode", default="gspmd",
                    choices=["gspmd", "terapipe", "gpipe"])
    ap.add_argument("--token-slices", type=int, default=4)
    ap.add_argument("--dp-plan", action="store_true",
                    help="plan slice lengths with the paper's DP (Alg. 1)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--schedule", default="contiguous",
                    choices=list(schedule_names()),
                    help="pipeline schedule (core/schedules registry — new "
                    "schedules appear here automatically): "
                    + schedule_help())
    ap.add_argument("--virtual-stages", type=int, default=1,
                    help="V layer chunks per pipeline rank (interleaved "
                    "schedule; V>1 implies --schedule interleaved). Needs "
                    "microbatches*token-slices divisible by the pipe degree")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route attention through the Pallas flash kernels "
                    "(fused fwd+bwd; both pipeline schedules and gspmd). "
                    "Compiled on a TPU, interpret mode on the CPU")
    ap.add_argument("--unroll", action="store_true",
                    help="unrolled tick loop (debug/differential testing; "
                    "trace time grows with D*M)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--simulate-failure-at", type=int, default=-1,
                    help="raise a fault at this step once, AFTER the step "
                    "has dispatched — donated buffers are really gone, as "
                    "in a mid-step hardware fault (FT test)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # validate (schedule, V) against the registry's per-schedule rules,
    # AFTER the back-compat promotion (V>1 under contiguous = interleaved)
    sched_eff = ("interleaved" if args.schedule == "contiguous"
                 and args.virtual_stages > 1 else args.schedule)
    try:
        check_virtual_stages(sched_eff, args.virtual_stages)
    except ValueError as e:
        ap.error(str(e))
    return args


def setup(args: argparse.Namespace) -> SimpleNamespace:
    """Model, initial state, mesh, the (un-jitted) train step and the data
    pipeline for ``args``.  The pipeline modes always run the executor: on
    one device its mesh is (data=1, pipe=1)."""
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.use_kernel:
        cfg = cfg.replace(use_kernel=True)   # gspmd path; terapipe overrides
    if cfg.family == "moe":
        args.seq = max(args.seq, cfg.moe_block)
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    specs = _param_specs(model, key)
    mesh = None
    if args.mode in ("terapipe", "gpipe"):
        n = len(jax.devices())
        pipe = min(4, n)
        mesh = make_mesh((n // pipe, pipe), ("data", "pipe"))
    vg_fn, shardings_fn = build_value_and_grad(model, specs, mesh, args)
    if shardings_fn is None:
        params = model.init(key)[0]
    else:
        # each device materializes only its own stage's share of the model
        params = jax.jit(lambda k: model.init(k)[0],
                         out_shardings=shardings_fn(specs))(key)
    opt = adamw(cosine_schedule(args.lr, args.warmup, args.steps))
    opt_state = opt.init(params)

    def train_step(params, opt_state, batch):
        loss, grads = vg_fn(params, batch)
        with jax.named_scope(tracing.OPTIMIZER):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
        return params, opt_state, loss

    extra = None
    if cfg.family == "vlm":
        extra = {"patch_embeds": ((cfg.n_patches, cfg.d_model), np.float32)}
    if cfg.family == "encdec":
        extra = {"frames": ((args.seq, cfg.d_model), np.float32)}
    # vlm: the image patches prefix the token stream, so only seq - patches
    # positions carry text tokens
    text_len = args.seq - cfg.n_patches if cfg.family == "vlm" else args.seq
    data = DataPipeline(SyntheticSource(cfg.vocab_size, args.seed),
                        args.batch, text_len, extra_specs=extra)
    return SimpleNamespace(cfg=cfg, model=model, params=params,
                           opt_state=opt_state, mesh=mesh,
                           train_step=train_step, data=data)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    run = setup(args)
    params, opt_state, data = run.params, run.opt_state, run.data
    with use_mesh(run.mesh):
        return _supervise(args, params, opt_state, data, run.train_step)


def _supervise(args, params, opt_state, data, train_step):
    """The step loop: checkpoints, and restore-and-retry on a fault.  A
    step is retried at most once; a step that faults again is a
    deterministic fault (an OOM, a compile error) and raises."""
    ckpt = CheckpointManager(args.checkpoint_dir) if args.checkpoint_dir else None
    # donation deletes the inputs of every dispatched step — only safe when a
    # checkpoint can restore them; without one, the live references ARE the
    # fault-recovery state (see module docstring)
    donate = (0, 1) if ckpt else ()
    step_fn = jax.jit(train_step, donate_argnums=donate)
    # restore target: structure template captured BEFORE any donation can
    # delete the live arrays (manager.restore only reads the treedef)
    state_template = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        {"params": params, "opt": opt_state})
    state_template["step"] = 0

    start_step = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        state = ckpt.restore(target=state_template)
        params, opt_state, start_step = (state["params"], state["opt"],
                                         int(state["step"]))
        print(f"[resume] restored step {start_step}")

    injected = False
    faulted = set()        # steps that faulted once
    step = start_step
    t_last, tok_count = time.perf_counter(), 0
    while step < args.steps:
        try:
            with jax.profiler.StepTraceAnnotation(tracing.STEP,
                                                  step_num=step):
                batch = data.batch_at(step)
                out = step_fn(params, opt_state, batch)
            if args.simulate_failure_at == step and not injected:
                # inject AFTER dispatch: with donation on, params/opt_state
                # are now deleted — exactly the state a real mid-step fault
                # leaves behind
                injected = True
                raise RuntimeError("injected fault (simulate-failure-at)")
            params, opt_state, loss = out
            tok_count += batch["tokens"].size
            step += 1
        except Exception as e:  # supervisor: restore-and-retry, once
            print(f"[fault] step {step}: {e}", file=sys.stderr)
            if step in faulted:
                print(f"[fault] step {step} faulted again; not retrying",
                      file=sys.stderr)
                raise
            faulted.add(step)
            if ckpt and ckpt.latest_step() is not None:
                state = ckpt.restore(target=state_template)
                params, opt_state, step = (state["params"], state["opt"],
                                           int(state["step"]))
                print(f"[fault] restored checkpoint at step {step}")
                continue
            if ckpt:
                # donation was on but nothing has been saved yet: the inputs
                # of the faulted step are deleted and unrecoverable
                print("[fault] no checkpoint saved yet and donation has "
                      "deleted the step inputs; cannot retry", file=sys.stderr)
                raise
            # no checkpointing configured: donation is off, so the pre-step
            # params/opt_state references are intact — retry
            print("[fault] no checkpoint dir; retrying step with rescue "
                  "references")
            continue

        if step % args.log_every == 0:
            dt = time.perf_counter() - t_last
            print(f"step {step:5d} loss {float(loss):.4f} "
                  f"{tok_count/dt:,.0f} tok/s")
            t_last, tok_count = time.perf_counter(), 0
        if ckpt and step % args.checkpoint_every == 0:
            path = ckpt.save(step, {"params": params, "opt": opt_state,
                                    "step": step})
            print(f"[ckpt] saved {path}")

    if ckpt:
        ckpt.save(args.steps, {"params": params, "opt": opt_state,
                               "step": args.steps})
    print(f"done: {args.steps} steps, final loss {float(loss):.4f}")
    return float(loss)


if __name__ == "__main__":
    main()
