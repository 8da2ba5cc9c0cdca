"""Machine-readable per-schedule perf report (``BENCH_schedules.json``).

``python -m benchmarks.run --json`` collects, for EVERY schedule in the
``core/schedules`` registry:

* ``bubble_fraction`` — simulated on the paper's most bubble-dominated
  Table-1 row (setting 8: gpt3-44b, K=48, 48 work items), priced from the
  same tick table the executor interprets.  Fwd-only schedules report the
  forward bubble; the 1F1B family reports the fwd+bwd bubble (the tables
  are inherently fwd+bwd) — comparable within a family across PRs.
* ``trace_lower_s`` — wall time to trace+lower the full loss+grad program
  of a small model through the unified executor (subprocess with forced
  host devices; K=4, M=8, V=2 for the interleaved schedules).
* ``temp_bytes`` — compiled ``memory_analysis().temp_size_in_bytes`` of the
  loss+grad step at D=1 and D=4 (the memory_bench cells), plus the growth
  ratio: the flat-vs-linear-in-D memory signature per schedule.

The JSON lands at the repo root so the perf trajectory of every schedule is
tracked across PRs by diffing one file.  Re-collecting does NOT clobber
that trajectory: the previous run's headline numbers (bubble fraction,
trace-lower seconds, memory growth ratio) are folded into a bounded
``history`` list keyed by git revision before the fresh cells are written,
and the collector prints a per-schedule diff against the most recent
previous entry — a regression shows up in the run log, not only in ``git
diff``.  Re-runs at the SAME revision replace that revision's entry
instead of stacking duplicates.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

DEFAULT_OUT = Path(__file__).resolve().parents[1] / "BENCH_schedules.json"

#: V used for the interleaved schedules' cells
REPORT_V = 2

#: past runs kept in the JSON's ``history`` list (newest last)
HISTORY_KEEP = 20

#: per-schedule headline numbers preserved per past run
_HISTORY_KEYS = ("bubble_fraction", "trace_lower_s", "temp_growth_D1toD4")


def _git_rev() -> str:
    try:
        r = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                           cwd=Path(__file__).resolve().parents[1],
                           capture_output=True, text=True, timeout=30)
        return r.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _compact(schedules: dict) -> dict:
    return {name: {k: cell[k] for k in _HISTORY_KEYS if k in cell}
            for name, cell in schedules.items()}

_TRACE_CODE = """
    import jax, jax.numpy as jnp
    from repro.compat import make_mesh, use_mesh
    from repro.core.pipeline import TeraPipeConfig, make_terapipe_value_and_grad
    from repro.models import build_model
    from repro.models.common import ModelConfig
    cfg = ModelConfig(name="t", family="dense", n_layers=8, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256,
                      dtype=jnp.float32, remat=False)
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    B, S, M = 4, 256, 8
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    structs = jax.eval_shape(lambda r: model.init(r)[0], jax.random.PRNGKey(0))
    mesh = make_mesh((1, 4), ("data", "pipe"))
    tcfg = TeraPipeConfig(n_token_slices=M, n_microbatches=1,
                          data_axes=("data",), cache_dtype=jnp.float32,
                          schedule="{sched}", virtual_stages={V})
    with use_mesh(mesh):
        vg, _ = make_terapipe_value_and_grad(model, specs, mesh, tcfg, S, B)
        t0 = time.time()
        jax.jit(vg).lower(structs, batch)
        print(f"LOWER_S {time.time() - t0:.3f}", flush=True)
"""


def _trace_lower_s(sched: str, V: int) -> float:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    code = textwrap.dedent(_TRACE_CODE).replace("{sched}", sched) \
                                       .replace("{V}", str(V))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, (sched, r.stderr[-2000:])
    return float(r.stdout.split("LOWER_S")[1].split()[0])


def _bubble(sched: str, V: int) -> float:
    from benchmarks.common import cost_model_for, unit_cost_model_for
    from benchmarks.paper_settings import TABLE1, SEQ_LEN
    from repro.core.schedule import SlicingScheme
    from repro.core.schedules import REGISTRY
    from repro.core.simulator import bubble_fraction

    s = next(t for t in TABLE1 if t.idx == 8)
    scheme = SlicingScheme.uniform(SEQ_LEN, 6, n_token_slices=8, microbatch=1)
    disc = {"contiguous": "lockstep"}.get(sched, sched)
    if REGISTRY[sched].has_backward:
        # explicit-bwd tables: every unit KIND priced separately via the
        # SAME shared pricer interleave_bench asserts against (fused bwd
        # for the 1f1b family, the B/W split pair for zb-h1)
        t_of, t_bwd_of, t_b_of, t_w_of = unit_cost_model_for(s)
        return bubble_fraction(scheme, s.n_pipe, t_of, discipline=disc,
                               virtual_stages=V, include_backward=True,
                               t_bwd_of=t_bwd_of, t_bwd_input_of=t_b_of,
                               t_bwd_weight_of=t_w_of)
    cm = cost_model_for(s)
    return bubble_fraction(scheme, s.n_pipe, lambda b, l, c: cm(l, c),
                           discipline=disc, virtual_stages=V)


def collect(out_path: Path = DEFAULT_OUT) -> dict:
    from benchmarks import memory_bench
    from repro.core.schedules import REGISTRY

    # previous run -> history entry (keyed by git rev) + diff baseline
    prev = None
    history = []
    if out_path.exists():
        try:
            old = json.loads(out_path.read_text())
            history = list(old.get("history", []))
            if old.get("schedules"):
                prev = {"rev": old.get("rev", "unknown"),
                        "schedules": _compact(old["schedules"])}
                history.append(prev)
        except (json.JSONDecodeError, OSError) as e:
            print(f"[schedule-report] ignoring unreadable {out_path}: {e}",
                  file=sys.stderr, flush=True)
    rev = _git_rev()
    # a re-collect at the same rev replaces that rev's entry, never stacks
    history = [h for h in history if h.get("rev") != rev][-HISTORY_KEEP:]

    report = {"rev": rev,
              "setting": {"bubble": "table1-setting8 K=48 N=48",
                          "trace": "K=4 M=8 n_layers=8 loss+grad lower",
                          "memory": f"K={memory_bench.K} M={memory_bench.M} "
                                    f"seq={memory_bench.SEQ}",
                          "virtual_stages": REPORT_V},
              "schedules": {},
              "history": history}
    for name, spec in REGISTRY.items():
        V = max(spec.min_virtual, REPORT_V if spec.min_virtual > 1 else 1)
        cell = {"virtual_stages": V, "has_backward": spec.has_backward}
        cell["bubble_fraction"] = round(_bubble(name, V), 6)
        cell["trace_lower_s"] = round(_trace_lower_s(name, V), 3)
        d_lo, d_hi = 1, 4
        temp = {f"D{d}": memory_bench._cell(name, d) for d in (d_lo, d_hi)}
        cell["temp_bytes"] = temp
        cell["temp_growth_D1toD4"] = round(
            temp[f"D{d_hi}"] / temp[f"D{d_lo}"], 3)
        report["schedules"][name] = cell
        print(f"[schedule-report] {name}: bubble="
              f"{cell['bubble_fraction']:.4f} "
              f"lower={cell['trace_lower_s']:.2f}s "
              f"temp_D4={temp['D4']/2**20:.2f}MiB "
              f"(x{cell['temp_growth_D1toD4']:.2f} over D)", flush=True)
    if prev is not None:
        for name, cell in report["schedules"].items():
            p = prev["schedules"].get(name)
            if not p or "bubble_fraction" not in p:
                print(f"[schedule-report] {name}: new since {prev['rev']}",
                      flush=True)
                continue
            db = cell["bubble_fraction"] - p["bubble_fraction"]
            dg = cell["temp_growth_D1toD4"] - p.get("temp_growth_D1toD4", 0.0)
            print(f"[schedule-report] {name} vs {prev['rev']}: "
                  f"bubble {p['bubble_fraction']:.4f}->"
                  f"{cell['bubble_fraction']:.4f} ({db:+.4f}) "
                  f"temp_growth {dg:+.3f}", flush=True)
    out_path.write_text(json.dumps(report, indent=1) + "\n")
    print(f"[schedule-report] wrote {out_path} "
          f"(rev {rev}, {len(history)} history entries)", flush=True)
    return report


if __name__ == "__main__":
    collect()
