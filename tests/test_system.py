"""End-to-end system tests.  Multi-device cases run in a subprocess with
--xla_force_host_platform_device_count (the main pytest process must keep
seeing exactly 1 CPU device)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=2400)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_single_device_sees_one_cpu():
    assert len(jax.devices()) == 1


def test_terapipe_pipeline_loss_and_grads_match_reference():
    """The paper's synchronous-equivalence claim, on a real (data=2, pipe=4)
    mesh: pipelined loss AND grads == plain execution."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build_model
        from repro.core.pipeline import make_terapipe_loss, TeraPipeConfig
        from repro.compat import make_mesh, use_mesh
        mesh = make_mesh((2, 4), ("data", "pipe"))
        cfg = get_config("phi3-mini-3.8b", smoke=True).replace(dtype=jnp.float32)
        model = build_model(cfg)
        params, specs = model.init(jax.random.PRNGKey(0))
        B, S = 4, 32
        rng = jax.random.PRNGKey(7)
        batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
        tcfg = TeraPipeConfig(n_token_slices=4, n_microbatches=2,
                              data_axes=("data",), cache_dtype=jnp.float32)
        with use_mesh(mesh):
            loss_fn, _ = make_terapipe_loss(model, specs, mesh, tcfg, S, B)
            lp = float(jax.jit(loss_fn)(params, batch))
            lr = float(jax.jit(model.loss)(params, batch))
            gp = jax.grad(loss_fn)(params, batch)
            gr = jax.grad(model.loss)(params, batch)
        rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) /
                                 (1e-6 + jnp.max(jnp.abs(b))))
        gerr = max(jax.tree.leaves(jax.tree.map(rel, gp, gr)))
        assert abs(lp - lr) < 2e-5, (lp, lr)
        assert gerr < 2e-3, gerr
        print("EQUIV-OK", lp, lr, gerr)
    """)
    assert "EQUIV-OK" in out


def test_terapipe_state_family_pipeline_matches():
    """SSM state carried across slices + reset at microbatch boundaries."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build_model
        from repro.core.pipeline import make_terapipe_loss, TeraPipeConfig
        from repro.compat import make_mesh, use_mesh
        mesh = make_mesh((2, 4), ("data", "pipe"))
        cfg = get_config("mamba2-2.7b", smoke=True).replace(dtype=jnp.float32)
        model = build_model(cfg)
        params, specs = model.init(jax.random.PRNGKey(0))
        B, S = 4, 32
        rng = jax.random.PRNGKey(2)
        batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
        tcfg = TeraPipeConfig(n_token_slices=2, n_microbatches=2,
                              data_axes=("data",), cache_dtype=jnp.float32)
        with use_mesh(mesh):
            loss_fn, _ = make_terapipe_loss(model, specs, mesh, tcfg, S, B)
            lp = float(jax.jit(loss_fn)(params, batch))
            lr = float(jax.jit(model.loss)(params, batch))
        assert abs(lp - lr) < 2e-5, (lp, lr)
        print("SSM-PIPE-OK")
    """)
    assert "SSM-PIPE-OK" in out


def test_gpipe_special_case_matches():
    """GPipe == TeraPipe with one token slice (the paper's baseline)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build_model
        from repro.core.pipeline import make_gpipe_loss
        from repro.compat import make_mesh, use_mesh
        mesh = make_mesh((2, 4), ("data", "pipe"))
        cfg = get_config("qwen3-0.6b", smoke=True).replace(dtype=jnp.float32)
        model = build_model(cfg)
        params, specs = model.init(jax.random.PRNGKey(0))
        B, S = 8, 16
        rng = jax.random.PRNGKey(3)
        batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
        with use_mesh(mesh):
            loss_fn, _ = make_gpipe_loss(model, specs, mesh, n_microbatches=4,
                                         seq_len=S, global_batch=B)
            lp = float(jax.jit(loss_fn)(params, batch))
            lr = float(jax.jit(model.loss)(params, batch))
        assert abs(lp - lr) < 5e-4, (lp, lr)   # bf16 KV-cache rounding
        print("GPIPE-OK")
    """)
    assert "GPIPE-OK" in out


def test_terapipe_with_tensor_parallel_stage():
    """pipe=2 × tp=2 × data=2: manual Megatron TP inside pipeline stages."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build_model
        from repro.core.pipeline import make_terapipe_loss, TeraPipeConfig
        from repro.compat import make_mesh, use_mesh
        mesh = make_mesh((2, 2, 2), ("data", "pipe", "tp"))
        cfg = get_config("phi3-mini-3.8b", smoke=True).replace(dtype=jnp.float32)
        model = build_model(cfg)
        params, specs = model.init(jax.random.PRNGKey(0))
        B, S = 4, 32
        rng = jax.random.PRNGKey(11)
        batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
        tcfg = TeraPipeConfig(n_token_slices=2, n_microbatches=1, tp_axis="tp",
                              data_axes=("data",), cache_dtype=jnp.float32)
        with use_mesh(mesh):
            loss_fn, _ = make_terapipe_loss(model, specs, mesh, tcfg, S, B)
            lp = float(jax.jit(loss_fn)(params, batch))
            lr = float(jax.jit(model.loss)(params, batch))
        assert abs(lp - lr) < 5e-4, (lp, lr)   # bf16 KV-cache rounding
        print("TP-OK", lp, lr)
    """)
    assert "TP-OK" in out


def test_nonuniform_dp_scheme_pipeline_matches():
    """The paper's actual DP output (non-uniform slice lengths) executed in
    the pipeline == plain execution."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build_model
        from repro.core.pipeline import make_terapipe_loss, TeraPipeConfig
        from repro.compat import make_mesh, use_mesh
        mesh = make_mesh((2, 4), ("data", "pipe"))
        cfg = get_config("phi3-mini-3.8b", smoke=True).replace(dtype=jnp.float32)
        model = build_model(cfg)
        params, specs = model.init(jax.random.PRNGKey(0))
        B, S = 4, 32
        rng = jax.random.PRNGKey(7)
        batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
        tcfg = TeraPipeConfig(slice_lens=(12, 8, 8, 4), n_microbatches=1,
                              data_axes=("data",), cache_dtype=jnp.float32)
        with use_mesh(mesh):
            loss_fn, _ = make_terapipe_loss(model, specs, mesh, tcfg, S, B)
            lp = float(jax.jit(loss_fn)(params, batch))
            lr = float(jax.jit(model.loss)(params, batch))
        assert abs(lp - lr) < 2e-5, (lp, lr)
        print("NONUNIFORM-OK")
    """)
    assert "NONUNIFORM-OK" in out


def test_train_driver_fault_tolerance(tmp_path):
    """Injected fault mid-run: the supervisor restores the checkpoint and the
    run completes with the same final state as an uninterrupted run."""
    env = dict(os.environ, PYTHONPATH=SRC)
    common = [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-0.6b",
              "--smoke", "--steps", "20", "--batch", "4", "--seq", "32",
              "--checkpoint-every", "5", "--log-every", "100"]
    r1 = subprocess.run(common + ["--checkpoint-dir", str(tmp_path / "a")],
                        capture_output=True, text=True, env=env, timeout=1200)
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = subprocess.run(common + ["--checkpoint-dir", str(tmp_path / "b"),
                                  "--simulate-failure-at", "12"],
                        capture_output=True, text=True, env=env, timeout=1200)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "[fault]" in r2.stdout + r2.stderr

    # bitwise-identical final checkpoints: synchronous training restored at
    # the last checkpoint and replayed the exact same data (stateless seek)
    a = np.load(tmp_path / "a" / "step_00000020" / "proc0.npz")
    b = np.load(tmp_path / "b" / "step_00000020" / "proc0.npz")
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k])


def test_train_driver_fault_no_checkpoint_dir(tmp_path):
    """FT regression (ISSUE 3): with no checkpoint dir the supervisor must
    keep donation OFF so the pre-step params/opt_state survive a fault as
    rescue references — the fault is injected AFTER the step dispatched, so
    under donation the inputs would be deleted and the old retry path
    crashed with 'Array has been deleted'.  The retried run must finish
    with the exact final state of an uninterrupted run (pure retry)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    common = [sys.executable, "-m", "repro.launch.train", "--arch",
              "qwen3-0.6b", "--smoke", "--steps", "8", "--batch", "4",
              "--seq", "32", "--log-every", "100"]
    r1 = subprocess.run(common + ["--checkpoint-dir", str(tmp_path / "ref"),
                                  "--checkpoint-every", "100"],
                        capture_output=True, text=True, env=env, timeout=1200)
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = subprocess.run(common + ["--checkpoint-dir", str(tmp_path / "ft"),
                                  "--checkpoint-every", "100",
                                  "--simulate-failure-at", "3"],
                        capture_output=True, text=True, env=env, timeout=1200)
    # NB: r2 has a ckpt dir but checkpoint-every > steps: nothing saved at
    # fault time, donation on -> documented unrecoverable path must raise
    assert r2.returncode != 0
    assert "cannot retry" in r2.stdout + r2.stderr
    r3 = subprocess.run(common + ["--simulate-failure-at", "3"],
                        capture_output=True, text=True, env=env, timeout=1200)
    assert r3.returncode == 0, r3.stderr[-2000:]
    assert "retrying step with rescue references" in r3.stdout + r3.stderr
    # r3 (retried, no ckpt) must reach the same loss as r1 (uninterrupted)
    final = [ln for ln in r1.stdout.splitlines() if ln.startswith("done:")]
    final3 = [ln for ln in r3.stdout.splitlines() if ln.startswith("done:")]
    assert final and final == final3, (final, final3)


def test_train_driver_terapipe_mode():
    out = _run_subprocess("""
        from repro.launch.train import main
        loss = main(["--arch", "phi3-mini-3.8b", "--smoke", "--mode", "terapipe",
                     "--steps", "6", "--batch", "4", "--seq", "32",
                     "--token-slices", "2", "--log-every", "3"])
        assert loss < 7.0
        print("TRAIN-TP-OK")
    """, devices=4)
    assert "TRAIN-TP-OK" in out


@pytest.mark.parametrize("with_checkpoint", [False, True])
def test_supervisor_retries_a_step_once(tmp_path, capsys, with_checkpoint):
    """A step that faults again after its retry is a deterministic fault
    (here a trace-time error on one batch shape, as a compile error or an
    OOM would be): the supervisor raises instead of restoring forever."""
    import argparse
    import jax.numpy as jnp
    from repro.launch import train

    class Data:
        def batch_at(self, step):
            return {"tokens": np.zeros((1, 3 if step == 2 else 2), np.int32)}

    def train_step(params, opt_state, batch):
        if batch["tokens"].shape[1] == 3:
            raise RuntimeError("deterministic fault")
        return params + 1, opt_state, jnp.float32(0)

    args = argparse.Namespace(
        checkpoint_dir=str(tmp_path) if with_checkpoint else None,
        checkpoint_every=1, resume=False, steps=4, simulate_failure_at=-1,
        log_every=100)
    with pytest.raises(RuntimeError, match="deterministic fault"):
        train._supervise(args, jnp.zeros(2), jnp.zeros(2), Data(),
                         train_step)
    err = capsys.readouterr().err
    assert err.count("[fault] step 2: deterministic fault") == 2, err
    assert "faulted again; not retrying" in err


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """The entry points' compile cache: where JAX_COMPILATION_CACHE_DIR
    says, else one fixed directory in the checkout."""
    from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()   # nothing compiles while it is set
        want = str(tmp_path) if from_env else str(REPO_CACHE_DIR)
        assert path == want == jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    assert REPO_CACHE_DIR == Path(__file__).resolve().parents[1] / ".jax_cache"
