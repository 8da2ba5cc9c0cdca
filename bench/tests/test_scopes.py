"""The phase map (``scopes.py``) and the readers of device time per step,
on hand-written HLO text and hand-built traces; then the map of the smoke
cell's real train step, compiled on the CPU."""
from types import SimpleNamespace

import pytest

import run
import scopes
import trace
from conftest import SMOKE_CONFIG

HLO = r"""
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(train_step)/step.optimizer/add" stack_frame_id=3}
}

ENTRY %main.9 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0), metadata={op_name="params[\'embed\']"}
  %fusion.390 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/step.optimizer/add" source_file="train.py" source_line=261}
  %copy.7 = f32[8]{0} copy(%fusion.390), metadata={op_name="jit(train_step)/transpose(jvp(tp.embed))/gather"}
  %terapipe_attention_bwd-dq.1 = f32[8]{0} custom-call(%copy.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp())/while/body/tp.tick.fwd/while/body/jit(terapipe_attention_bwd)/terapipe_attention_bwd-dq/pallas_call" stack_frame_id=28}
  %dynamic-update-slice.2 = f32[8]{0} dynamic-update-slice(%copy.7), metadata={op_name="jit(train_step)/jvp()/tp.tick.fwd/while/body/tp.cache/dynamic_update_slice"}
  ROOT %tuple.3 = (f32[8]{0}) tuple(%dynamic-update-slice.2)
}
"""


def test_op_scopes_reads_each_instructions_op_name():
    got = scopes.op_scopes(HLO)
    assert got["fusion.390"] == "jit(train_step)/step.optimizer/add"
    assert got["add.1"] == "jit(train_step)/step.optimizer/add"
    assert got["copy.7"].endswith("transpose(jvp(tp.embed))/gather")
    assert got["terapipe_attention_bwd-dq.1"].endswith(
        "terapipe_attention_bwd-dq/pallas_call")
    assert got["p.1"] == r"params[\'embed\']"
    assert "tuple.3" not in got and "param_0" not in got  # no metadata


@pytest.mark.parametrize("op_name,phase", [
    ("jit(train_step)/step.optimizer/add", "step.optimizer"),
    ("jit(train_step)/transpose(jvp(tp.embed))/gather", "tp.embed"),
    ("a/tp.tick.fwd/while/body/tp.cache/dynamic_update_slice", "tp.cache"),
    ("a/transpose(jvp())/tp.tick.fwd/jit(terapipe_attention_bwd)/"
     "terapipe_attention_bwd-dq/pallas_call", "tp.tick.fwd"),
    ("a/tp.tick.bwd_input/x", "tp.tick.bwd_input"),     # not tp.tick.bwd
    ("a/tp.tick.bwd/x", "tp.tick.bwd"),
    ("a/my.tp.cache/x", None),                          # not a component
    ("a/tp.cache2/x", None),
    ("jit(train_step)/while/body/add", None),
    (None, None),
])
def test_phase_of_is_the_innermost_phase(op_name, phase):
    assert scopes.phase_of(op_name) == phase


MS = 1e6            # ns in a ms


def obs_of(dev_ops, host, steps=2, op_scopes=None):
    trc = trace.Trace({"/device:TPU:0": [trace.Op(n, a * MS, b * MS)
                                         for n, a, b in dev_ops]},
                      sorted((trace.Op(n, a * MS, b * MS) for n, a, b in host),
                             key=lambda o: o.start))
    return SimpleNamespace(trace=trc, wins=trace.device_windows(trc),
                           steps=steps, op_scopes=op_scopes)


# the device clock runs 10 ms behind the host's: the first op starts at 990
# on the device for the dispatch at 1000 on the host
HOST = [("bench_window", 1000, 2000), ("dispatch", 1000, 1100),
        ("data.batch_at", 1400, 1450), ("dispatch", 1500, 1550),
        ("sync", 1100, 1500)]
DEVICE = [("terapipe_attention_bwd-dq.3", 990, 1050),
          ("fusion.390", 1200, 1300), ("terapipe_attention_bwd-dkv.4", 1300,
                                        1480),
          ("terapipe_attention_bwd-dq.3", 1600, 1700),
          ("copy.7", 1700, 1990), ("fusion.390", 2500, 2600)]   # after


def test_kernel_readers_per_step_and_absent():
    obs = obs_of(DEVICE, HOST)
    assert run.reader("flash_dq_ms")(obs) == pytest.approx(80.0)
    assert run.reader("flash_dkv_ms")(obs) == pytest.approx(90.0)
    old = obs_of([("terapipe_attention_bwd.30", 990, 1500)], HOST)
    assert run.reader("flash_dq_ms")(old) is None
    assert run.reader("flash_dkv_ms")(old) is None


def test_phase_readers_per_step_and_absent():
    names = scopes.op_scopes(HLO)
    obs = obs_of(DEVICE, HOST, op_scopes=names)
    # fusion.390 once inside the window (100 ms), once after it
    assert run.reader("optimizer_ms")(obs) == pytest.approx(50.0)
    assert run.reader("kv_cache_ms")(obs) is None
    assert run.reader("attn_relayout_ms")(obs) is None
    names = dict(names, **{"copy.7": "jit(s)/transpose(jvp(attn.relayout))/t",
                           "fusion.390": "jit(s)/tp.tick.fwd/tp.cache/dus"})
    obs = obs_of(DEVICE, HOST, op_scopes=names)
    assert run.reader("attn_relayout_ms")(obs) == pytest.approx(145.0)
    assert run.reader("kv_cache_ms")(obs) == pytest.approx(50.0)
    assert run.reader("optimizer_ms")(obs) is None


def test_idle_host_ms_on_the_device_clock():
    """Idle on the device: 1050-1200 and 1480-1600.  The host spans, moved
    10 ms back onto the device's clock: dispatch 990-1090 and 1490-1540,
    batch_at 1390-1440.  Overlap 40 + 50 ms over 2 steps (on the host's
    clock it would read 50 + 50)."""
    obs = obs_of(DEVICE, HOST)
    assert obs.wins["/device:TPU:0"] == (990 * MS, 1990 * MS)
    assert run.reader("idle_host_ms")(obs) == pytest.approx(45.0)
    no_spans = obs_of(DEVICE, [h for h in HOST if h[0] == "bench_window"])
    assert run.reader("idle_host_ms")(no_spans) is None


def test_step_hlo_of_the_smoke_cell_names_its_phases():
    traffic = dict(run.load_cell("qwen3-0.6b.train-2k").traffic, batch=2,
                   seq=64, slices=2)
    obs = SimpleNamespace(config=SMOKE_CONFIG, traffic=traffic, op_scopes=None)
    found = {scopes.phase_of(n) for n in scopes.of_run(obs).values()}
    assert {"step.optimizer", "tp.embed", "tp.tick.fwd", "tp.cache",
            "tp.reassemble", "tp.head_loss", "attn.relayout"} <= found
