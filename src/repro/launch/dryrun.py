import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
    " --xla_force_host_platform_device_count=" +
    os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()
os.environ["JAX_PLATFORMS"] = "cpu"          # host devices; never opens a chip

"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: the production
mesh (16×16 single pod / 2×16×16 multi-pod) is built from 512 placeholder
host devices; every step function is lowered with ShapeDtypeStruct inputs
(no allocation), compiled, and its memory_analysis / cost_analysis /
collective schedule recorded to JSON for the roofline (EXPERIMENTS.md).

Usage:
  python -m repro.launch.dryrun --arch phi3-mini-3.8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--mode gspmd|terapipe]
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import use_mesh
from repro.configs import ARCHS, SHAPES, get_config, input_specs, skip_reason
from repro.core.schedules import (REGISTRY, check_virtual_stages,
                                  schedule_help, schedule_names)
from repro.launch import hlo_analysis as ha
from repro.launch import hlo_tripcount as hlo_trip
from repro.launch.mesh import make_production_mesh, make_terapipe_mesh, data_axes
from repro.launch.steps import (abstract_caches, cache_shardings,
                                gspmd_shardings, make_decode_step,
                                make_prefill_step, make_train_step)
from repro.models import build_model
from repro.optim.adamw import adamw, cosine_schedule
from repro.distributed.sharding import batch_shardings


def cell_tag(arch: str, shape_name: str, multi_pod: bool, mode: str,
             virtual_stages: int = 1, variant: str = "",
             schedule: str = "contiguous") -> str:
    """Result-file tag for one cell — the single source of truth, used both
    when writing results (run_cell) and when probing the --skip-done cache."""
    tag = f"{arch}_{shape_name}_{'pod2' if multi_pod else 'pod1'}_{mode}"
    if virtual_stages > 1:
        tag += f"_v{virtual_stages}"
    if schedule not in ("contiguous", "interleaved"):
        tag += f"_{schedule}"       # interleaved is already the _v tag
    if variant:
        tag += f"_{variant}"
    return tag


def _mem_dict(mem) -> dict:
    return {k: getattr(mem, k) for k in (
        "generated_code_size_in_bytes", "argument_size_in_bytes",
        "output_size_in_bytes", "temp_size_in_bytes", "alias_size_in_bytes")}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             mode: str = "gspmd", save_hlo: bool = False,
             out_dir: str = "experiments/dryrun",
             terapipe_slices: int = 4, terapipe_pipe: int = 16,
             param_dtype=None, remat_policy: str = "full",
             layout: str = "tp", fsdp: bool = True, capacity=None,
             seqpar: bool = False, terapipe_dp: bool = False,
             virtual_stages: int = 1, variant: str = "",
             schedule: str = "contiguous", use_kernel: bool = False) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    if remat_policy != "full":
        cfg = cfg.replace(remat_policy=remat_policy)
    if capacity is not None:
        cfg = cfg.replace(capacity_factor=capacity)
    reason = skip_reason(arch, shape_name)
    if mode != "terapipe":
        virtual_stages = 1      # only the terapipe lowering consumes these —
        schedule = "contiguous"  # don't stamp tags onto identical cells
    tag = cell_tag(arch, shape_name, multi_pod, mode, virtual_stages, variant,
                   schedule)
    rec = {"arch": arch, "shape": shape_name, "mode": mode,
           "multi_pod": multi_pod, "n_chips": 512 if multi_pod else 256,
           "virtual_stages": virtual_stages, "schedule": schedule}
    if reason:
        rec["skipped"] = reason
        return _dump(rec, out_dir, tag)

    model = build_model(cfg)
    t0 = time.time()
    try:
        if mode == "terapipe":
            lowered, n_chips = _lower_terapipe(
                model, shape, multi_pod, terapipe_slices, terapipe_pipe,
                dp_plan=terapipe_dp, virtual_stages=virtual_stages,
                schedule=schedule, use_kernel=use_kernel)
        else:
            lowered, n_chips = _lower_gspmd(model, cfg, shape, multi_pod,
                                            param_dtype=param_dtype,
                                            layout=layout, fsdp=fsdp,
                                            seqpar=seqpar)
        rec["n_chips"] = n_chips
        rec["lower_s"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        rec["memory"] = _mem_dict(mem)
        # XLA's cost_analysis does NOT multiply while-loop bodies by their
        # trip counts (undercounts scan-over-layers by ~n_layers); use the
        # trip-count-aware analyzer and keep XLA's numbers for reference.
        trip = hlo_trip.analyze(hlo)
        rec["flops"] = float(trip["flops"])
        rec["bytes_accessed"] = float(trip["bytes"])
        rec["collectives"] = trip["collectives"]
        rec["xla_cost_flops"] = float(cost.get("flops", 0.0))
        rec["xla_cost_bytes"] = float(cost.get("bytes accessed", 0.0))

        rec["analytic_memory"] = ha.analytic_memory_per_device(
            cfg, shape.seq_len, shape.global_batch, shape.kind, n_chips)
        rec["min_bytes_per_dev"] = ha.analytic_min_bytes(
            cfg, shape.seq_len, shape.global_batch, shape.kind, n_chips)
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                       else 1)
        if shape.kind == "train":
            mf = ha.model_flops_train(cfg, shape.seq_len, shape.global_batch)
        else:
            mf = ha.model_flops_forward(cfg, tokens)
        roof = ha.Roofline(rec["flops"], rec["bytes_accessed"],
                           trip["collectives"]["total"], n_chips, mf)
        rec["roofline"] = roof.to_dict()
        if save_hlo:
            Path(out_dir).mkdir(parents=True, exist_ok=True)
            (Path(out_dir) / f"{tag}.hlo").write_text(hlo)
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return _dump(rec, out_dir, tag)


DP_ONLY_RULES = {"heads": None, "kv_heads": None, "ff": None,
                 "experts": None, "vocab": None, "embed": None}


def _lower_gspmd(model, cfg, shape, multi_pod, param_dtype=None,
                 layout: str = "tp", fsdp: bool = True, seqpar: bool = False):
    """layout="tp": Megatron TP over the model axis (default).
    layout="dp": no TP — the model axis joins the batch axes (pure DP+FSDP;
    the right call for <10B dense models where TP all-reduces of activations
    dominate the collective term).  Vocab stays sharded for the loss matmul.
    """
    mesh = make_production_mesh(multi_pod=multi_pod)
    daxes = data_axes(mesh)
    rules = None
    if layout == "dp":
        daxes = daxes + ("model",)
        rules = DP_ONLY_RULES
    seq_axis = "model" if (seqpar and layout == "tp") else None
    n_chips = int(np.prod(list(mesh.shape.values())))
    specs_in = input_specs(cfg, shape)
    b_sh = batch_shardings(specs_in, mesh, daxes)
    if param_dtype == "bf16":
        param_dtype = jnp.bfloat16

    with use_mesh(mesh):
        if shape.kind == "train":
            opt = adamw(cosine_schedule(3e-4, 100, 10_000),
                        master_weights=param_dtype is not None)
            structs, _, p_sh, o_structs, o_sh = gspmd_shardings(
                model, mesh, optimizer=opt, fsdp=fsdp, data_axes=daxes,
                param_dtype=param_dtype, rules=rules, seq_axis=seq_axis)
            step = make_train_step(model, opt)
            jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(structs, o_structs, specs_in)
        elif shape.kind == "prefill":
            structs, _, p_sh, _, _ = gspmd_shardings(
                model, mesh, fsdp=fsdp, data_axes=daxes,
                param_dtype=param_dtype, rules=rules, seq_axis=seq_axis)
            step = make_prefill_step(model, shape.seq_len)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(structs, specs_in)
        else:  # decode
            structs, _, p_sh, _, _ = gspmd_shardings(
                model, mesh, fsdp=fsdp, data_axes=daxes,
                param_dtype=param_dtype, rules=rules)
            caches = abstract_caches(model, shape.global_batch, shape.seq_len)
            c_sh = cache_shardings(caches, mesh, daxes)
            step = make_decode_step(model)
            jitted = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh, None),
                             donate_argnums=(1,))
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jitted.lower(structs, caches, specs_in, pos)
    return lowered, n_chips


def _lower_terapipe(model, shape, multi_pod, n_slices, n_pipe,
                    dp_plan: bool = False, unroll: bool = False,
                    virtual_stages: int = 1, schedule: str = "contiguous",
                    use_kernel: bool = False):
    from repro.core.pipeline import (TeraPipeConfig,
                                     make_terapipe_value_and_grad)
    from repro.launch.steps import abstract_init, abstract_opt_state
    from repro.optim.adamw import apply_updates

    assert shape.kind == "train", "terapipe mode lowers the train step"
    mesh = make_terapipe_mesh(n_pipe=n_pipe, multi_pod=multi_pod)
    daxes = data_axes(mesh)
    n_chips = int(np.prod(list(mesh.shape.values())))
    cfg = model.cfg
    specs_in = input_specs(cfg, shape)
    b_sh = batch_shardings(specs_in, mesh, daxes)
    tp = mesh.shape.get("tp", 1)
    if virtual_stages > 1 and schedule == "contiguous":
        schedule = "interleaved"     # back-compat: V>1 implies interleaving
    if REGISTRY[schedule].has_backward and tp > 1:
        raise NotImplementedError(
            f"--schedule {schedule} needs a TP-free pipe mesh; pipe={n_pipe} "
            f"leaves tp={tp} (pick --terapipe-pipe 16)")

    slice_lens = None
    if dp_plan:
        from repro.core.cost_model import AnalyticCostModel, TPU_V5E
        from repro.core.dp import ensure_executable, optimal_slicing
        cm = AnalyticCostModel(cfg, TPU_V5E,
                               layers_per_stage=max(1, model.n_blocks // n_pipe))
        plan = optimal_slicing(cm, shape.seq_len, n_pipe, granularity=128,
                               virtual_stages=virtual_stages)
        # schedule-aware executability post-pass (splitting the largest
        # slices never raises t_max)
        slices = ensure_executable(plan.slices, schedule=schedule,
                                   n_ranks=n_pipe, n_microbatches=1,
                                   granularity=128)
        slice_lens = tuple(slices)
        print(f"[dp-plan] {len(slice_lens)} slices: {list(slice_lens)}",
              flush=True)
    elif virtual_stages > 1 and n_slices % n_pipe:
        # interleaved work items advance in ring groups of K: adjust the
        # slice count so D*M (D=1 here) divides the pipe degree — while
        # keeping M a divisor of seq_len (uniform-slice executor requirement)
        ok = [m for m in range(n_pipe, shape.seq_len + 1, n_pipe)
              if shape.seq_len % m == 0]
        if not ok:
            raise ValueError(
                f"--virtual-stages {virtual_stages} needs a token-slice "
                f"count that is a multiple of pipe={n_pipe} AND divides "
                f"seq_len={shape.seq_len}; none exists — pick a pipe degree "
                f"whose factors divide the sequence length")
        snapped = min((m for m in ok if m >= n_slices), default=ok[-1])
        print(f"[terapipe] V={virtual_stages} needs M % pipe == 0; adjusting "
              f"token slices {n_slices} -> {snapped}"
              + (" (capped: no valid count >= request)"
                 if snapped < n_slices else ""), flush=True)
        n_slices = snapped
    tcfg = TeraPipeConfig(n_token_slices=n_slices, slice_lens=slice_lens,
                          n_microbatches=1,
                          pipe_axis="pipe",
                          tp_axis="tp" if tp > 1 else None,
                          data_axes=daxes, unroll=unroll,
                          schedule=schedule,
                          virtual_stages=virtual_stages,
                          use_kernel=True if use_kernel else None)
    structs, specs = abstract_init(model)
    with use_mesh(mesh):
        vg_fn, param_sh_fn = make_terapipe_value_and_grad(
            model, specs, mesh, tcfg, shape.seq_len, shape.global_batch)
        p_sh = param_sh_fn(specs)
        opt = adamw(cosine_schedule(3e-4, 100, 10_000))
        o_structs = abstract_opt_state(opt, structs)
        o_sh = type(o_structs)(None, p_sh, p_sh)

        def train_step(params, opt_state, batch):
            loss, grads = vg_fn(params, batch)
            updates, opt_state = opt.update(grads, opt_state, params)
            return apply_updates(params, updates), opt_state, loss

        jitted = jax.jit(train_step, in_shardings=(p_sh, o_sh, b_sh),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(structs, o_structs, specs_in)
    return lowered, n_chips


def compare_executors(arch: str, shape_name: str, *, terapipe_slices: int = 16,
                      terapipe_pipe: int = 16, multi_pod: bool = False,
                      do_compile: bool = False,
                      out_dir: str = "experiments/dryrun") -> dict:
    """Trace+lower (optionally compile) the terapipe train step with BOTH tick
    executors and report wall-times.  The rolled lax.scan executor's trace
    cost is O(1) in D*M; the unrolled escape hatch's grows linearly — at
    D*M >= 16 rolled must win."""
    shape = SHAPES[shape_name]
    model = build_model(get_config(arch))
    rec = {"arch": arch, "shape": shape_name, "mode": "terapipe",
           "n_slices": terapipe_slices, "pipe": terapipe_pipe,
           "executors": {}}
    for name, unroll in (("rolled", False), ("unrolled", True)):
        t0 = time.time()
        lowered, n_chips = _lower_terapipe(
            model, shape, multi_pod, terapipe_slices, terapipe_pipe,
            unroll=unroll)
        cell = {"lower_s": time.time() - t0}
        if do_compile:
            t1 = time.time()
            lowered.compile()
            cell["compile_s"] = time.time() - t1
        rec["executors"][name] = cell
        print(f"[exec] {arch} {shape_name} M={terapipe_slices} {name}: "
              + " ".join(f"{k}={v:.2f}s" for k, v in cell.items()),
              flush=True)
    r, u = (rec["executors"]["rolled"]["lower_s"],
            rec["executors"]["unrolled"]["lower_s"])
    rec["rolled_faster"] = bool(r < u)
    rec["ok"] = True
    print(f"[exec] rolled {'beats' if r < u else 'LOSES TO'} unrolled: "
          f"{r:.2f}s vs {u:.2f}s (trace+lower, M={terapipe_slices})",
          flush=True)
    return _dump(rec, out_dir, f"{arch}_{shape_name}_executors")


def _dump(rec: dict, out_dir: str, tag: str) -> dict:
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    with open(Path(out_dir) / f"{tag}.json", "w") as f:
        json.dump(rec, f, indent=1)
    status = ("SKIP" if rec.get("skipped") else
              "OK" if rec.get("ok") else "FAIL")
    extra = ""
    if rec.get("ok") and "executors" in rec:
        extra = " " + " ".join(
            f"{n}_lower={c['lower_s']:.2f}s" for n, c in rec["executors"].items())
    elif rec.get("ok"):
        m = rec["memory"]
        per_dev = (m["argument_size_in_bytes"] + m["temp_size_in_bytes"]
                   + m["output_size_in_bytes"] - m["alias_size_in_bytes"])
        extra = (f" mem/dev={per_dev/2**30:.2f}GiB "
                 f"flops={rec['flops']:.3e} "
                 f"coll={rec['collectives']['total']:.3e}B "
                 f"bottleneck={rec['roofline']['bottleneck']}")
    elif rec.get("error"):
        extra = " " + rec["error"][:160]
    print(f"[{status}] {tag}{extra}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", default="gspmd", choices=["gspmd", "terapipe"])
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--terapipe-slices", type=int, default=4)
    ap.add_argument("--terapipe-pipe", type=int, default=16)
    ap.add_argument("--schedule", default="contiguous",
                    choices=list(schedule_names()),
                    help="pipeline schedule (core/schedules registry; "
                    "terapipe mode only): " + schedule_help())
    ap.add_argument("--virtual-stages", type=int, default=1,
                    help="V layer chunks per pipeline rank (interleaved "
                    "schedule; terapipe mode only)")
    ap.add_argument("--param-dtype", default=None, choices=[None, "bf16"])
    ap.add_argument("--remat-policy", default="full", choices=["full", "dots"])
    ap.add_argument("--layout", default="tp", choices=["tp", "dp"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--capacity", type=float, default=None)
    ap.add_argument("--seqpar", action="store_true")
    ap.add_argument("--use-kernel", action="store_true",
                    help="terapipe mode: route stage attention through the "
                    "Pallas flash kernels (pair with --variant to tag cells)")
    ap.add_argument("--terapipe-dp", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--compare-executors", action="store_true",
                    help="report trace+lower wall-time for the rolled vs "
                    "unrolled tick executor (terapipe mode)")
    ap.add_argument("--compile", action="store_true",
                    help="with --compare-executors: also compile both")
    args = ap.parse_args()
    # validate up front: an invalid combination must not run (and, worse,
    # write its failure record under another schedule's cell tag).  The
    # per-schedule V rules come from the registry.
    sched_eff = ("interleaved" if args.schedule == "contiguous"
                 and args.virtual_stages > 1 else args.schedule)
    try:
        check_virtual_stages(sched_eff, args.virtual_stages)
    except ValueError as e:
        ap.error(str(e))

    if args.compare_executors:
        rec = compare_executors(
            args.arch or "gpt3-1b", args.shape or "train_4k",
            terapipe_slices=args.terapipe_slices,
            terapipe_pipe=args.terapipe_pipe, multi_pod=args.multi_pod,
            do_compile=args.compile, out_dir=args.out_dir)
        sys.exit(0 if rec.get("rolled_faster") else 1)

    cells = []
    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    n_fail = 0
    for a, s, mp in cells:
        tag = cell_tag(a, s, mp, args.mode,
                       args.virtual_stages if args.mode == "terapipe" else 1,
                       args.variant,
                       args.schedule if args.mode == "terapipe"
                       else "contiguous")
        if args.skip_done and (Path(args.out_dir) / f"{tag}.json").exists():
            prev = json.loads((Path(args.out_dir) / f"{tag}.json").read_text())
            if prev.get("ok") or prev.get("skipped"):
                print(f"[CACHED] {tag}", flush=True)
                continue
        rec = run_cell(a, s, multi_pod=mp, mode=args.mode,
                       save_hlo=args.save_hlo, out_dir=args.out_dir,
                       terapipe_slices=args.terapipe_slices,
                       terapipe_pipe=args.terapipe_pipe,
                       param_dtype=args.param_dtype,
                       remat_policy=args.remat_policy, layout=args.layout,
                       fsdp=not args.no_fsdp, capacity=args.capacity,
                       seqpar=args.seqpar, terapipe_dp=args.terapipe_dp,
                       virtual_stages=args.virtual_stages,
                       variant=args.variant, schedule=args.schedule,
                       use_kernel=args.use_kernel)
        if not (rec.get("ok") or rec.get("skipped")):
            n_fail += 1
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
