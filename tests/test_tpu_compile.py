"""Compile the Pallas kernels for a TPU v5e chip that is described, not
attached, at the widths of the models the chip runs.

Interpret mode runs the kernel body as plain JAX ops and checks no tiling,
so only a Mosaic compile shows whether a block layout is legal on the chip.
The topology is described inside a fixture, never at import: one process
at a time may load the TPU library, and every xdist worker imports this
file.
"""
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import tracing
from repro.kernels.decode_attention import decode_attention_kernel
from repro.kernels.terapipe_attention import terapipe_attention_fwd
from repro.kernels.terapipe_attention_bwd import terapipe_attention_bwd

ROOT = Path(__file__).resolve().parents[1]

# (name, B, l, Sk, Hq, Hkv, hd): a training slice of l tokens against the
# whole Sk-token KV cache the executor hands the kernel (traced ctx)
WIDTHS = [
    ("qwen3-0.6b", 1, 512, 2048, 16, 8, 128),
    ("gpt3-1b", 1, 512, 2048, 16, 16, 128),
    ("qwen3-0.6b", 4, 2048, 2048, 16, 8, 128),     # the benchmark's cells
    ("qwen3-0.6b", 1, 8192, 8192, 16, 8, 128),
    ("gpt3-1b", 1, 256, 2048, 16, 16, 128),        # gpt3-1b.pipe4-2k's slice
]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("name,b,l,sk,hq,hkv,hd", WIDTHS)
def test_flash_fwd_compiles(one_chip, name, b, l, sk, hq, hkv, hd):
    q = _sds((b, l, hq, hd), jnp.bfloat16, one_chip)
    kv = _sds((b, sk, hkv, hd), jnp.bfloat16, one_chip)
    ctx = _sds((), jnp.int32, one_chip)
    _compile(lambda q, k, v, c: terapipe_attention_fwd(q, k, v, c),
             q, kv, kv, ctx)


@pytest.mark.parametrize("name,b,l,sk,hq,hkv,hd", WIDTHS)
def test_flash_bwd_compiles(one_chip, name, b, l, sk, hq, hkv, hd):
    """dQ and dK/dV kernels in one program."""
    q = _sds((b, l, hq, hd), jnp.bfloat16, one_chip)
    kv = _sds((b, sk, hkv, hd), jnp.bfloat16, one_chip)
    rows = _sds((b, hq, l), jnp.float32, one_chip)
    ctx = _sds((), jnp.int32, one_chip)
    compiled = _compile(
        lambda q, k, v, do, lse, dl, c: terapipe_attention_bwd(
            q, k, v, do, lse, dl, c), q, kv, kv, q, rows, rows, ctx)
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("name,b,l,sk,hq,hkv,hd", WIDTHS)
def test_decode_compiles(one_chip, name, b, l, sk, hq, hkv, hd):
    """One token per slot against the Sk-deep cache, per-slot kv_len."""
    q = _sds((4, 1, hq, hd), jnp.bfloat16, one_chip)
    kv = _sds((4, sk, hkv, hd), jnp.bfloat16, one_chip)
    kv_len = _sds((4,), jnp.int32, one_chip)
    _compile(lambda q, k, v, n: decode_attention_kernel(q, k, v, n),
             q, kv, kv, kv_len)


def _reader_pattern(metric):
    """The kernel pattern of a benchmark reader, as its file states it."""
    text = (ROOT / "bench" / "metrics" / f"{metric}.py").read_text()
    return re.search(r'^PATTERN = r"([^"]+)"', text, re.M).group(1)


@pytest.mark.parametrize("name,b,l,sk,hq,hkv,hd", WIDTHS)
def test_flash_kernel_names(one_chip, name, b, l, sk, hq, hkv, hd):
    """The kernels' custom calls take their ``pallas_call`` names, which
    the benchmark's readers find: the forward pattern only the forward,
    the backward pattern both backward kernels (two per backward)."""
    q = _sds((b, l, hq, hd), jnp.bfloat16, one_chip)
    kv = _sds((b, sk, hkv, hd), jnp.bfloat16, one_chip)
    rows = _sds((b, hq, l), jnp.float32, one_chip)
    ctx = _sds((), jnp.int32, one_chip)

    def fwd_bwd(q, k, v, do, lse, dl, c):
        return (terapipe_attention_fwd(q, k, v, c),
                terapipe_attention_bwd(q, k, v, do, lse, dl, c))
    text = _compile(fwd_bwd, q, kv, kv, q, rows, rows, ctx).as_text()
    calls = sorted(re.findall(r"^\s*(?:ROOT )?%(\S+) = .*custom_call_target="
                              r'"tpu_custom_call"', text, re.M))
    assert [re.sub(r"\.\d+$", "", c) for c in calls] == sorted(
        [tracing.FLASH_BWD_DKV, tracing.FLASH_BWD_DQ, tracing.FLASH_FWD])
    fwd = re.compile(_reader_pattern("flash_fwd_roofline"))
    bwd = re.compile(_reader_pattern("flash_bwd_roofline"))
    assert [c for c in calls if fwd.search(c)] == [
        c for c in calls if c.startswith(tracing.FLASH_FWD + ".")]
    assert len([c for c in calls if bwd.search(c)]) == 2
    for kernel, metric in ((tracing.FLASH_BWD_DQ, "flash_dq_ms"),
                           (tracing.FLASH_BWD_DKV, "flash_dkv_ms")):
        one = re.compile(_reader_pattern(metric))
        assert [c for c in calls if one.search(c)] == [
            c for c in calls if c.startswith(kernel + ".")]
