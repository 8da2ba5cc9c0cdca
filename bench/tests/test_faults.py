"""A run with its timed path broken underneath comes out not correct.

Drives ``run.main`` past its look for a chip, on the CPU at the smoke sizes
of ``conftest.py``: once sound (correct), then once for each fault a
training cell can have: a step that returns its state unchanged, half of
the batch left out, and (on four virtual devices, in a child process) the
exchange between pipeline stages left out.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import faults
import run
from conftest import BENCH, TEST_PEAKS, make_root


def _run(root, workload="smoke.t", seed=3, **hooks):
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "1",
            "--trace", "0"]
    return run.main(argv, root=root, require_tpu=False,
                    hooks=dict(peaks=TEST_PEAKS, **hooks))


def test_sound_run_is_correct(smoke_root):
    line = _run(smoke_root)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_not_correct(smoke_root, fault):
    line = _run(smoke_root, step_hook=faults.HOOKS[fault])
    assert not line["correct"], line["checks"]


CHILD = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path[:0] = [{bench!r}, {tests!r}]
    import faults, run
    from conftest import TEST_PEAKS
    out = {{}}
    for name, hook in (("sound", None), ("drop_exchange", faults.drop_exchange)):
        line = run.main(["--workload", "pipe.t", "--seed", "5", "--seconds",
                         "1", "--trace", "0"], root=Path({root!r}),
                        require_tpu=False,
                        hooks=dict(peaks=TEST_PEAKS, step_hook=hook))
        out[name] = line["correct"]
    print("RESULT " + json.dumps(out))
""")


def test_dropped_exchange_is_not_correct(tmp_path):
    root = make_root(tmp_path, {"batch": 4, "seq": 64, "slices": 2,
                                "microbatches": 4, "trace_steps": 2},
                     name="pipe.t")
    cfg = {"arch": "gpt3-1b", "smoke": True, "reference": "dense",
           "head_dim": 16, "hidden_size": 64, "intermediate_size": 256,
           "num_attention_heads": 4, "num_hidden_layers": 4,
           "num_key_value_heads": 4, "qk_norm": False, "rms_norm_eps": 1e-6,
           "rope_theta": 10000.0, "tie_word_embeddings": False,
           "vocab_size": 256, "dtype": "bfloat16"}
    (root / "bench" / "configs" / "smoke.json").write_text(json.dumps(cfg))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"][0]["chips"] = 4
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(bench=str(BENCH), tests=str(BENCH / "tests"),
                        root=str(root))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, proc.stderr[-4000:]
    out = json.loads(lines[-1][len("RESULT "):])
    assert out == {"sound": True, "drop_exchange": False}
