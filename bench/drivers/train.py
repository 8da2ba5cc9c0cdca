"""Training driver: the program's own train step, timed and checked.

Set-up builds the program's training run through its entry
(``repro.launch.train.parse_args`` and ``setup``: ``--mode terapipe
--use-kernel``, the cell's batch, sequence, microbatches, schedule and
slices), swaps in the benchmark's weights made from the seed, and jits
``train_step`` with params and optimizer state donated, as the program's
supervisor does when it checkpoints.  That one step object then runs the
first ``check_steps`` steps on the seed's batches (this compiles, or loads
the compile cache, and warms every shape the window uses), and the readings
the comparison needs are taken from its state.  The same object runs the
window: batches come from the program's ``DataPipeline.batch_at`` fed by the
benchmark's token source, and at most two steps are in flight.

After the window the device memory is read, the program's state is freed,
and the float32 reference follows the same first steps from the same
weights and batches (``references/dense.py``).
"""
from __future__ import annotations

import contextlib
import gc
import glob
import io
import math
import shutil
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

import compare
import data
import trace as trace_mod
import weights
from references import dense

#: the configuration file's keys, and the program config fields they must equal
CONFIG_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "head_dim": "hd", "qk_norm": "qk_norm", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def train_argv(config: dict, traffic: dict, seed: int) -> list:
    opt = traffic["optimizer"]
    argv = ["--arch", config["arch"], "--mode", "terapipe", "--use-kernel",
            "--batch", str(traffic["batch"]), "--seq", str(traffic["seq"]),
            "--microbatches", str(traffic["microbatches"]),
            "--schedule", traffic["schedule"],
            "--lr", repr(opt["lr"]), "--warmup", str(opt["warmup"]),
            "--steps", str(opt["total_steps"]),
            "--token-slices", str(traffic["slices"]),
            "--seed", str(seed % 2 ** 31)]
    if config.get("smoke"):
        argv.append("--smoke")
    return argv


def check_config(cfg, config: dict):
    """The program's model config must be the configuration file's."""
    bad = [f"{k}: file {config[k]!r}, program {getattr(cfg, f)!r}"
           for k, f in CONFIG_FIELDS.items()
           if k in config and getattr(cfg, f) != config[k]]
    if jnp.dtype(cfg.dtype).name != config["dtype"]:
        bad.append(f"dtype: file {config['dtype']}, program "
                   f"{jnp.dtype(cfg.dtype).name}")
    if bad:
        raise ValueError("program config differs from the configuration "
                         "file: " + "; ".join(bad))


def slice_plan(traffic: dict) -> list:
    """The token slices the executor is told to run: the traffic's count of
    equal slices of the sequence."""
    n = int(traffic["slices"])
    if traffic["seq"] % n:
        raise ValueError(f"{n} slices do not divide seq {traffic['seq']}")
    return [traffic["seq"] // n] * n


class CompileCounter:
    """Counts compile events (backend compiles and persistent-cache loads)
    while ``on``."""

    def __init__(self):
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, _secs, **_kw):
        self._event(name)

    def _event(self, name, **_kw):
        if self.on and name in COMPILE_EVENTS:
            self.count += 1


def reference_shardings(template, devices):
    """Shardings for the reference's state: on one device, that device; on
    several, each leaf split over all of them along its largest dimension
    that they divide (never the stacked-layer axis)."""
    if len(devices) == 1:
        s = jax.sharding.SingleDeviceSharding(devices[0])
        return jax.tree.map(lambda _: s, template)
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devices), ("x",))
    n = len(devices)

    def pick(a):
        dims = [i for i in range(a.ndim) if a.shape[i] % n == 0
                and not (a.ndim >= 3 and i == 0)]
        if not dims:
            return NamedSharding(mesh, P())
        best = max(dims, key=lambda i: a.shape[i])
        return NamedSharding(mesh, P(*[("x" if i == best else None)
                                       for i in range(a.ndim)]))
    return jax.tree.map(pick, template)


def memory(devices):
    """Per device: peak bytes in use plus peak bytes reserved (the TPU
    runtime keeps a program's temporaries in reserved memory)."""
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append(st.get("peak_bytes_in_use", 0)
                   + st.get("peak_bytes_reserved", 0))
    return out


class Trainer:
    """The program's training run for one cell, built once: weights for any
    seed, the first steps with their readings, the window, the reference."""

    def __init__(self, cell):
        from repro.compat import use_mesh
        from repro.launch import train

        self.cell = cell
        self.config, self.traffic = cell.config, cell.traffic
        self.opt = self.traffic["optimizer"]
        self.devices = jax.devices()
        log = io.StringIO()
        with contextlib.redirect_stdout(log):
            prog = train.setup(train.parse_args(
                train_argv(self.config, self.traffic, cell.seed)))
        sys.stderr.write(log.getvalue())
        check_config(prog.cfg, self.config)
        self.slices = slice_plan(self.traffic)
        self.template = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), prog.params)
        self.opt_template = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            prog.opt_state)
        self.names = weights.leaf_names(self.template)
        self.make = make = weights.init_fn(self.template)
        self.mesh, self.data = prog.mesh, prog.data
        mesh = prog.mesh
        self.mesh_ctx = lambda: use_mesh(mesh)
        self.rows, self.seq = self.traffic["batch"], self.traffic["seq"]
        step_fn = prog.train_step
        if cell.step_hook:
            step_fn = cell.step_hook(step_fn)
        self.step = jax.jit(step_fn, donate_argnums=(0, 1))
        self.init = jax.jit(make, out_shardings=jax.tree.map(
            lambda a: a.sharding, self.template))
        # AdamW state from zero, placed as the program places it (its step
        # counter is an uncommitted scalar)
        self.zeros = lambda: jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype, device=(
                a.sharding if isinstance(a.sharding, NamedSharding) else None)),
            self.opt_template)
        self.norms = jax.jit(weights.leaf_norms)
        self.change = jax.jit(lambda p, k: weights.leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, make(k))))
        del prog                                  # its own init and state

    def feed(self, i):
        return jax.device_put(self.data.batch_at(i))

    def first_steps(self, seed):
        """Weights and batches from ``seed``; the first ``check_steps``
        steps through the window's own call.  Returns (readings, params,
        opt_state)."""
        kd = jnp.asarray(weights.key_data(seed))
        self.data.source = data.TokenSource(self.config["vocab_size"], seed)
        opt_state = self.zeros()
        with self.mesh_ctx():
            params = self.init(kd)
            losses, grad = [], None
            for i in range(self.traffic["check_steps"]):
                params, opt_state, loss = self.step(params, opt_state,
                                                    self.feed(i))
                losses.append(loss)
                if i == 0:
                    grad = [float(x) / (1 - self.opt["b1"]) for x in
                            jax.device_get(self.norms(opt_state.m))]
            read = {"losses": [float(x) for x in jax.device_get(losses)],
                    "grad": grad,
                    "change": [float(x) for x in
                               jax.device_get(self.change(params, kd))]}
        return read, params, opt_state

    def reference(self, seed, prec="f32", rows=None, positions=None):
        """The float32 reference's readings (``prec="fp8"``: the control)
        over the same weights and batches.  For the readings of the faults
        that leave part of the batch out: ``rows`` keeps only the first rows
        of each batch, ``positions`` only the first positions of each row
        (causal, so those positions see what they saw)."""
        kd = jnp.asarray(weights.key_data(seed))
        sh = reference_shardings(self.template, self.devices)
        params = jax.jit(self.make, out_shardings=sh)(kd)
        source = data.TokenSource(self.config["vocab_size"], seed)
        batches = [data.batch(source, t, self.rows, self.seq)
                   for t in range(self.traffic["check_steps"])]
        if rows is not None:
            batches = [(t[:rows], lab[:rows]) for t, lab in batches]
        if positions is not None:
            batches = [(t[:, :positions], lab[:, :positions])
                       for t, lab in batches]
        losses, grad, params = dense.train_steps(self.config, self.opt,
                                                 params, batches, prec=prec)
        change = [float(x) for x in jax.device_get(self.change(params, kd))]
        return {"losses": losses, "grad": grad, "change": change}


def run(cell) -> dict:
    """Run one cell; returns the result's fields (see ``run.py``)."""
    counter = CompileCounter()
    tr = Trainer(cell)
    traffic = tr.traffic
    tokens_per_step = tr.rows * tr.seq
    prog_read, params, opt_state = tr.first_steps(cell.seed)
    jax.block_until_ready((params, opt_state))

    # the window: the same step object and state, batches from batch_at
    win = SimpleNamespace(steps=0, losses=[], data_s=[])
    trace_dir = cell.root / "bench" / ".traces" / "run"
    with tr.mesh_ctx():
        if cell.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
        t_start = time.perf_counter()
        setup_s = time.time() - cell.process_start
        counter.on = True
        i, pending = traffic["check_steps"], None
        ann = jax.profiler.TraceAnnotation
        with ann("bench_window"):
            while True:
                t0 = time.perf_counter()
                with ann("data"):
                    batch = tr.feed(i)
                win.data_s.append(time.perf_counter() - t0)
                with ann("dispatch"):
                    params, opt_state, loss = tr.step(params, opt_state, batch)
                with ann("sync"):
                    if pending is not None:
                        pending.block_until_ready()
                pending = loss
                win.losses.append(loss)
                win.steps += 1
                i += 1
                if cell.trace:
                    if win.steps >= traffic["trace_steps"]:
                        break
                elif time.perf_counter() - t_start >= cell.seconds:
                    break
            with ann("sync"):
                jax.block_until_ready((params, opt_state, loss))
        t_end = time.perf_counter()
        counter.on = False
        if cell.trace:
            jax.profiler.stop_trace()
    window_s = t_end - t_start
    win_losses = [float(x) for x in jax.device_get(win.losses)]
    mem = memory(tr.devices)
    del params, opt_state, loss, pending, batch, win.losses
    gc.collect()

    result = {"attempted": win.steps,
              "failed": sum(not math.isfinite(x) for x in win_losses),
              "memory_peak_bytes": max(mem)}
    if cell.trace:
        path = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        trc = trace_mod.load(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        twin = trace_mod.window(trc)
        wins = trace_mod.device_windows(trc)
        busy = trace_mod.device_busy_s(trc, wins)
        obs = SimpleNamespace(
            trace=trc, wins=wins, window_s=(twin[1] - twin[0]) / 1e9,
            steps=win.steps, tokens_per_step=tokens_per_step,
            data_s=win.data_s, compiles=counter.count, config=tr.config,
            traffic=traffic, slices=tr.slices, peaks=cell.peaks,
            n_devices=len(tr.devices), memory=mem)
        result["metrics"] = cell.read_metrics(obs)
        result["device_extra"] = {
            "busy_s": sum(busy.values()) / max(1, len(busy)),
            "window_s": obs.window_s}
        result["breakdown"] = {"device_ops": trace_mod.top_ops(trc, wins),
                               "idle_gaps": trace_mod.idle_gaps(trc, wins)}
    else:
        result["metrics"] = {
            "train_tok_s": {"value": win.steps * tokens_per_step / window_s,
                            "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}

    # the reference, once the program's state is gone
    ref_read = tr.reference(cell.seed)
    nums = compare.numbers(prog_read, ref_read)
    checks = compare.verdict(nums, cell.limits)
    checks.append({"name": "window_losses_finite", "value": result["failed"],
                   "limit": 0, "ok": result["failed"] == 0})
    result["checks"] = checks
    result["correct"] = all(r["ok"] for r in checks)
    result["readings"] = {"program": prog_read, "reference": ref_read,
                          "leaves": tr.names}
    return result
