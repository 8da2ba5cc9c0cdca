"""The benchmark's FLOP and byte arithmetic, and its table of peaks."""
import json

import pytest

import flops

QWEN3 = json.loads((flops.PEAKS_FILE.parent / "configs"
                    / "qwen3-0.6b.json").read_text())
GPT3 = json.loads((flops.PEAKS_FILE.parent / "configs"
                   / "gpt3-1b.json").read_text())


@pytest.mark.parametrize("cfg,seq,gflop", [
    (QWEN3, 2048, 4.28), (QWEN3, 8192, 6.40), (GPT3, 2048, 10.88)])
def test_model_flops_per_token(cfg, seq, gflop):
    """The per-token numbers quoted to two decimals (4.28, 6.40, 10.88)."""
    got = flops.model_flops_per_token(cfg, seq) / 1e9
    assert got == pytest.approx(gflop, abs=0.006)


def test_qwen3_8k_attention_share():
    """Attention is 2.82 of the 6.40 GFLOP/token at 8192 tokens."""
    f, _ = flops.flash_fwd_work(QWEN3, 1, 8192, 0)
    per_token = 3 * f * QWEN3["num_hidden_layers"] / 8192
    assert round(per_token / 1e9, 2) == 2.82


def test_slices_add_up_to_the_whole_sequence():
    """Slices at their contexts do the causal work of one whole pass."""
    whole, _ = flops.flash_fwd_work(GPT3, 1, 2048, 0)
    parts = sum(flops.flash_fwd_work(GPT3, 1, 256, c)[0]
                for c in range(0, 2048, 256))
    assert parts == pytest.approx(whole)
    bwd, _ = flops.flash_bwd_work(GPT3, 1, 2048, 0)
    assert bwd == pytest.approx(2 * whole)


def test_pass_work_counts_ticks():
    traffic = {"schedule": "contiguous", "microbatches": 4, "batch": 4}
    f, b, calls = flops.flash_pass_work(GPT3, traffic, [256] * 8, 4)
    assert calls == 6 * (4 * 8 + 4 - 1)
    whole, _ = flops.flash_fwd_work(GPT3, 1, 2048, 0)
    assert f == pytest.approx(6 * 4 * whole)
    assert flops.flash_pass_work(GPT3, dict(traffic, schedule="1f1b"),
                                 [256] * 8, 4) is None


def test_peaks_known_kind():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "_source"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        flops.peaks(kind)
