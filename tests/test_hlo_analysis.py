"""The trip-count-aware HLO analyzer is load-bearing for the roofline —
validate it against programs with known exact costs."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_tripcount import analyze
from repro.launch import hlo_analysis as ha


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_trip_count_exact():
    """XLA's cost_analysis undercounts scans; ours must be exact."""
    def f(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=7)
        return h.sum()
    co = _compile(f, jax.ShapeDtypeStruct((8, 16), jnp.float32),
                  jax.ShapeDtypeStruct((16, 16), jnp.float32))
    true_flops = 7 * 2 * 8 * 16 * 16
    assert analyze(co.as_text())["flops"] == true_flops
    assert co.cost_analysis()["flops"] < true_flops   # XLA's undercount


def test_nested_scan():
    def f(x, w):
        def outer(c, _):
            def inner(d, _):
                return jnp.tanh(d @ w), None
            d, _ = jax.lax.scan(inner, c, None, length=3)
            return d, None
        c, _ = jax.lax.scan(outer, x, None, length=5)
        return c.sum()
    co = _compile(f, jax.ShapeDtypeStruct((8, 16), jnp.float32),
                  jax.ShapeDtypeStruct((16, 16), jnp.float32))
    assert analyze(co.as_text())["flops"] == 15 * 2 * 8 * 16 * 16


def test_plain_matmul_and_batched_dot():
    def f(a, b, c):
        return (a @ b).sum() + jnp.einsum("bij,bjk->bik", c, c).sum()
    co = _compile(f, jax.ShapeDtypeStruct((32, 64), jnp.float32),
                  jax.ShapeDtypeStruct((64, 16), jnp.float32),
                  jax.ShapeDtypeStruct((4, 8, 8), jnp.float32))
    true = 2 * 32 * 64 * 16 + 4 * 2 * 8 * 8 * 8
    assert analyze(co.as_text())["flops"] == true


def test_collective_bytes_sharded(tmp_path):
    """Sharded contraction -> all-reduce; analyzer counts ring-weighted
    per-device wire bytes.  Runs in a subprocess (needs >1 device)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.compat import make_mesh, use_mesh
        from repro.launch.hlo_tripcount import analyze
        mesh = make_mesh((4,), ("x",))
        sh_a = NamedSharding(mesh, P(None, "x"))
        sh_b = NamedSharding(mesh, P("x", None))
        a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        with use_mesh(mesh):
            co = jax.jit(lambda a, b: a @ b,
                         in_shardings=(sh_a, sh_b)).lower(a, a).compile()
        r = analyze(co.as_text())
        assert r["flops"] == 2 * 64 * 64 * 64 / 4, r["flops"]
        # all-reduce of the (64,64) f32 result, ring multiplier 2x
        assert r["collectives"]["all-reduce"] == 2 * 64 * 64 * 4
        print("COLL-OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "COLL-OK" in r.stdout


def test_model_flops_accounting():
    """active_param_count ~ true param count for a dense smoke model."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    true_n = sum(x.size for x in jax.tree.leaves(params))
    est = ha.active_param_count(cfg)
    assert abs(est - true_n) / true_n < 0.02   # ln scales etc. are the slack


def test_roofline_terms_and_bottleneck():
    r = ha.Roofline(flops=197e12, bytes_accessed=819e9 * 2,
                    coll_bytes=50e9 * 0.5, n_chips=256, model_flops=197e12 * 256)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(2.0)
    assert r.t_collective == pytest.approx(0.5)
    assert r.bottleneck == "memory"
    assert r.useful_ratio == pytest.approx(1.0)
