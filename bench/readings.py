"""Readings from which a cell's correctness limits are set.

    python bench/readings.py --workload qwen3-0.6b.train-2k \
        --seeds 11,12,13 --control 3 --half-batch 3 [--half-seq 3] \
        [--drop-exchange 3]

In one process on the cell's chips: the program is built once and, for each
seed, runs its first steps from that seed; the float32 reference follows
them, and the three compared numbers are printed, one JSON line per reading
(also appended to ``chiprun_out/readings/<workload>.jsonl``).  For the first
``--control`` seeds the control (the reference at float8 matmuls in the
program's place) is read against the same reference; ``--half-batch`` reads
the reference on the first half of each batch's rows in the program's
place; ``--drop-exchange`` reads the program with its pipeline ring
dropped (a program built apart).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import run as harness

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--half-batch", type=int, default=0)
    ap.add_argument("--half-seq", type=int, default=0)
    ap.add_argument("--drop-exchange", type=int, default=0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.load_cell(args.workload)
    for p in (str(ROOT / "src"), str(ROOT / "bench")):
        if p not in sys.path:
            sys.path.insert(0, p)
    harness.devices_or_exit(cell.workload["chips"])
    harness.enable_cache()
    import compare
    import faults
    from drivers.train import Trainer

    out = ROOT / "chiprun_out" / "readings" / f"{args.workload}.jsonl"
    out.parent.mkdir(parents=True, exist_ok=True)

    def emit(seed, what, read, ref, t0):
        rec = {"workload": args.workload, "seed": seed, "what": what,
               "seconds": round(time.perf_counter() - t0, 3),
               **compare.numbers(read, ref),
               "raw": {"prog": read, "ref": ref}}
        line = json.dumps(rec)
        print(line, flush=True)
        with out.open("a") as f:
            f.write(line + "\n")

    def ctx(hook=None):
        from types import SimpleNamespace
        return SimpleNamespace(config=cell.config, traffic=cell.traffic,
                               seed=seeds[0], step_hook=hook)

    refs = {}
    tr = Trainer(ctx())
    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        prog, params, opt_state = tr.first_steps(seed)
        del params, opt_state
        gc.collect()
        refs[seed] = ref = tr.reference(seed)
        emit(seed, "program", prog, ref, t0)
        if k < args.control:
            t0 = time.perf_counter()
            emit(seed, "control", tr.reference(seed, prec="fp8"), ref, t0)
        if k < args.half_batch:
            t0 = time.perf_counter()
            emit(seed, "half_batch", tr.reference(seed, rows=tr.rows // 2),
                 ref, t0)
        if k < args.half_seq:
            t0 = time.perf_counter()
            emit(seed, "half_seq", tr.reference(seed, positions=tr.seq // 2),
                 ref, t0)
    if args.drop_exchange:
        del tr
        gc.collect()
        tr = Trainer(ctx(faults.drop_exchange))
        for seed in seeds[:args.drop_exchange]:
            t0 = time.perf_counter()
            prog, params, opt_state = tr.first_steps(seed)
            del params, opt_state
            gc.collect()
            emit(seed, "drop_exchange", prog, refs[seed], t0)


if __name__ == "__main__":
    main()
