"""``python -m repro.analysis`` — the ``make lint-ir`` CLI.

Runs the static-audit rule matrix over every registered schedule ×
(use_kernel on/off), prints a per-cell summary, writes the machine-readable
findings JSON, and exits non-zero when any error-severity finding survives.

Environment is self-contained: this process forces CPU host devices BEFORE
jax initializes (the analyzer needs a real K-rank mesh to trace the ring
program; the pytest main process deliberately strips this forcing, so the
in-process tests stick to K=1).
"""
import argparse
import json
import os
import sys


def _force_devices(n: int) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
    os.environ["JAX_PLATFORMS"] = "cpu"      # never opens a chip


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static jaxpr/HLO audit over the schedule registry")
    ap.add_argument("--schedules", nargs="*", default=None,
                    help="schedule names (default: the whole registry)")
    ap.add_argument("--k", type=int, default=2,
                    help="pipeline ranks per cell (default 2)")
    ap.add_argument("--json", default="experiments/lint_ir.json",
                    help="findings JSON path (default %(default)s)")
    ap.add_argument("--no-donation", action="store_true",
                    help="skip the compiled donation audit (trace-only)")
    ap.add_argument("--no-growth", action="store_true",
                    help="skip the O(1)-in-M/D growth traces")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule registry and exit")
    args = ap.parse_args(argv)

    _force_devices(max(args.k, 2) * 2)
    from repro.analysis import audit, rules

    if args.list_rules:
        for rid, rule in sorted(rules.RULES.items()):
            print(f"{rid:28s} {rule.doc}")
        return 0

    cells = audit.default_cells(args.schedules, K=args.k)
    print(f"lint-ir: {len(cells)} cells "
          f"({len({c.schedule for c in cells})} schedules x kernel on/off, "
          f"K={args.k})", flush=True)
    report = audit.run_matrix(cells,
                              compile_donation=not args.no_donation,
                              growth=not args.no_growth,
                              log=lambda m: print(m, flush=True))

    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")

    n_err = sum(1 for cell in report["cells"] for f in cell["findings"]
                if f["severity"] == "error")
    if n_err:
        for cell in report["cells"]:
            for f in cell["findings"]:
                if f["severity"] == "error":
                    print(f"ERROR {cell['cell']} {f['rule']}: "
                          f"{f['message']}", file=sys.stderr)
        print(f"lint-ir: FAILED ({n_err} error findings)", file=sys.stderr)
        return 1
    print("lint-ir: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
