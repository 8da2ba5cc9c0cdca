"""Set a cell's correctness limits from its readings.

    python bench/set_limits.py qwen3-0.6b.train-2k

Reads ``bench/limits/<workload>.readings.jsonl`` (one JSON line per
reading, from ``readings.py`` and the harness's own runs: ``what`` is
``program``, ``control``, or a fault; a line counts for the numbers it
holds; ``gap_geomean`` is worked out where a line lacks it) and writes
``bench/limits/<workload>.json``.  For each compared number:

* the lower reading is the largest over the program's sound runs;
* the upper reading is the least of: the control's smallest reading where
  that is at least 3 x the lower; each fault's smallest where at least
  10 x the lower; and, for ``grad_gap`` and ``change_gap``, 1.0, which a
  step that leaves its state unchanged reads (3 x rule);
* the limit lies between, at ``lower^0.3 * upper^0.7`` (more room above the
  lower, since fresh seeds read higher than a dozen did), two digits.

A number with no upper reading gets the limit null: it is not compared.
The script fails where the control, or a fault, would pass every limit.
"""
from __future__ import annotations

import json
import math
import sys
from collections import defaultdict
from pathlib import Path

from compare import NUMBERS, geomean

LIMITS = Path(__file__).resolve().parent / "limits"
STATE_UNCHANGED = {"grad_gap": 1.0, "change_gap": 1.0}


def derive(records):
    by = defaultdict(list)
    for r in records:
        by[r["what"]].append(r)
    limits, notes = {}, {}
    for k in NUMBERS:
        lower = max(r[k] for r in by["program"] if k in r)
        uppers = {}
        for what, rs in by.items():
            if what == "program" or not any(k in r for r in rs):
                continue
            least = min(r[k] for r in rs if k in r)
            need = 3 if what == "control" else 10
            if least >= need * lower:
                uppers[what] = least
        if k in STATE_UNCHANGED and STATE_UNCHANGED[k] >= 3 * lower:
            uppers["state_unchanged"] = STATE_UNCHANGED[k]
        if uppers:
            upper = min(uppers.values())
            lim = float(f"{lower ** 0.3 * upper ** 0.7:.2g}")
        else:
            upper, lim = None, None
        limits[k] = lim
        notes[k] = {"lower": lower, "upper": upper, "uppers": uppers,
                    "program_runs": sum(k in r for r in by["program"])}
    for what, rs in by.items():
        if what == "program":
            continue
        for r in rs:
            if not any(limits[k] is not None and r.get(k, 0) > limits[k]
                       for k in NUMBERS):
                raise SystemExit(f"{what} seed {r['seed']} passes every "
                                 f"limit {limits}: no limit holds")
    return limits, notes


def main(workload):
    records = [json.loads(ln) for ln in
               (LIMITS / f"{workload}.readings.jsonl").read_text().splitlines()
               if ln.strip()]
    for r in records:
        r.setdefault("gap_geomean", geomean(r))
    limits, notes = derive(records)
    (LIMITS / f"{workload}.json").write_text(json.dumps(limits, indent=1) + "\n")
    for k in NUMBERS:
        n = notes[k]
        print(f"{workload} {k}: lower {n['lower']:.3e} upper "
              f"{n['upper'] if n['upper'] is None else format(n['upper'], '.3e')}"
              f" ({', '.join(n['uppers'])}) -> limit {limits[k]}")
    return limits


if __name__ == "__main__":
    for w in sys.argv[1:]:
        main(w)
