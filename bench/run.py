"""Benchmark entry: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload qwen3-0.6b.train-2k --seed 7 \
        --seconds 30 --trace 0

Everything is found by name: the workload's configuration file
(``configs``'s ``file``), its traffic file ``bench/traffic/<traffic>.json``
(which names the driver, ``bench/drivers/<driver>.py``), the limits of its
correctness numbers ``bench/limits/<workload>.json``, and one reader per
per-layer metric, ``bench/metrics/<metric>.py``.

The run needs exactly the cell's number of TPU chips; with anything else it
exits non-zero and prints no result.  The compile cache lives in
``bench/.jax_cache`` inside the checkout.  The last line of standard output
is the result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit.  The same checks are the last lines of
standard error.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]


def process_start() -> float:
    """Wall-clock time at which this process started (Linux /proc)."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        ticks = int(fields[19])
        btime = next(int(ln.split()[1]) for ln in
                     Path("/proc/stat").read_text().splitlines()
                     if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


PROCESS_START = process_start()


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(workload: str, root: Path = ROOT) -> SimpleNamespace:
    """The workload's entry, configuration, traffic, limits and metrics."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    try:
        wl = next(w for w in spec["workloads"] if w["name"] == workload)
    except StopIteration:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == wl["config"])
    limits_file = root / "bench" / "limits" / f"{workload}.json"
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return SimpleNamespace(
        workload=wl, root=root,
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((root / "bench" / "traffic"
                            / f"{wl['traffic']}.json").read_text()),
        limits=(json.loads(limits_file.read_text()) if limits_file.exists()
                else {}),
        per_layer=per_layer)


def reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metric_reader(per_layer, root: Path = ROOT):
    """``read(obs) -> metrics``: each per-layer metric whose reader finds
    something to read."""
    readers = [(m["name"], m["unit"], reader(m["name"], root))
               for m in per_layer]

    def read(obs):
        out = {}
        for name, unit, fn in readers:
            value = fn(obs)
            if value is not None:
                out[name] = {"value": float(value), "unit": unit}
        return out
    return read


def devices_or_exit(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"no TPU: JAX sees {len(devs)} {devs[0].platform} device(s)")
    if len(devs) != chips:
        sys.exit(f"the cell needs {chips} TPU chip(s); JAX sees {len(devs)}")
    return devs


def enable_cache(root: Path = ROOT):
    """The persistent compile cache at a fixed path inside the checkout;
    every program is cached, however fast it compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / "bench" / ".jax_cache")
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def result_line(res: dict, devs, trace: bool) -> dict:
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": res["memory_peak_bytes"]}
    if trace:
        dev.update(res["device_extra"])
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": res["metrics"], "device": dev}
    if trace:
        out["breakdown"] = res["breakdown"]
    out["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                     for r in res["checks"]}
    return out


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True,
         hooks=None) -> dict:
    args = parse_args(argv)
    cell = load_cell(args.workload, root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(root / "src"), str(root / "bench")):
        if p not in sys.path:
            sys.path.insert(0, p)
    if require_tpu:
        devs = devices_or_exit(cell.workload["chips"])
    else:
        import jax
        devs = jax.devices()
    enable_cache(root)
    from flops import peaks
    driver = importlib.import_module(f"drivers.{cell.traffic['driver']}")
    ctx = SimpleNamespace(
        config=cell.config, traffic=cell.traffic, limits=cell.limits,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        root=root, process_start=PROCESS_START,
        peaks=peaks(devs[0].device_kind) if require_tpu else {},
        read_metrics=metric_reader(cell.per_layer, root),
        step_hook=None)
    for k, v in (hooks or {}).items():
        setattr(ctx, k, v)
    res = driver.run(ctx)
    line = result_line(res, devs, ctx.trace)
    sys.stderr.write("readings " + json.dumps(res["readings"]) + "\n")
    for r in res["checks"]:
        sys.stderr.write(f"check {r['name']} {r['value']!r} limit "
                         f"{r['limit']!r} {'ok' if r['ok'] else 'FAIL'}\n")
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
