"""Fixtures for the benchmark's own tests (``pytest bench/tests``).

``smoke_root`` is a checkout in miniature: ``BENCHMARK.json`` with one
workload at the program's smoke sizes (qwen3 family, 2 layers, width 64),
its configuration, traffic and limits, and links to the program's sources
and the benchmark's code.  Runs there use the CPU, with the Pallas kernels
in interpret mode.
"""
import json
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE_CONFIG = {
    "arch": "qwen3-0.6b", "smoke": True, "reference": "dense",
    "head_dim": 32, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "qk_norm": True, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "tie_word_embeddings": True, "vocab_size": 256,
    "dtype": "bfloat16"}
# set by set_limits.py's rule (lower^0.3 * upper^0.7) from smoke readings
# on the CPU (bench/readings.py at these sizes), seeds 100-129: program at
# most loss 7.2e-5, grad 4.0e-3, change 3.6e-3, their geometric mean
# 7.5e-4; control at least 1.8e-4, 1.3e-2, 5.3e-3 and 2.9e-3; half batch
# and half sequence (seeds 100-103) at least 8.4e-4, 5.1e-2, 1.2e-2 and
# 1.2e-2.  change_gap's upper reading is the unchanged state's 1.0.
SMOKE_LIMITS = {"loss_gap": 4.0e-4, "grad_gap": 9.1e-3, "change_gap": 0.18,
                "gap_geomean": 2.0e-3}
TEST_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def make_root(root: Path, traffic_update: dict, name="smoke.t"):
    (root / "bench").mkdir(parents=True, exist_ok=True)
    if not (root / "src").exists():
        os.symlink(ROOT / "src", root / "src")
    for f in BENCH.iterdir():
        if f.name not in ("configs", "traffic", "limits") and \
                not (root / "bench" / f.name).exists():
            os.symlink(f, root / "bench" / f.name)
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir(exist_ok=True)
    (root / "bench" / "configs" / "smoke.json").write_text(
        json.dumps(SMOKE_CONFIG))
    traffic = json.loads((BENCH / "traffic" / "train-2k.json").read_text())
    traffic.update(traffic_update)
    (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
    (root / "bench" / "limits" / f"{name}.json").write_text(
        json.dumps(SMOKE_LIMITS))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "smoke", "source": "test",
                        "file": "bench/configs/smoke.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": name, "config": "smoke", "traffic": name,
                          "chips": 1, "why": "test"}]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"),
                     {"batch": 2, "seq": 64, "slices": 2, "trace_steps": 2})
