"""Spread of repeated runs of a cell, for setting a metric's bound.

    python chipbench/spreads.py SET1_DIR SET2_DIR

Each directory holds one file per run whose last line is the run's JSON
result (``*.out``).  For every metric, prints each set's median and spread
(first to third quartile over the median, ``statistics.quantiles``), the
wider spread, and five times it: the bound the benchmark's rule asks for.
"""
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.common import spread  # noqa: E402


def results(directory: str):
    out = []
    for path in sorted(Path(directory).glob("*.out")):
        lines = path.read_text().strip().splitlines()
        if lines and lines[-1].startswith("{"):
            out.append(json.loads(lines[-1]))
    return out


def main() -> None:
    sets = [results(d) for d in sys.argv[1:]]
    names = sorted({m for runs in sets for r in runs for m in r["metrics"]})
    for name in names:
        cols, widest = [], 0.0
        for runs in sets:
            values = [r["metrics"][name]["value"] for r in runs
                      if name in r["metrics"]]
            s = spread(values) if len(values) >= 2 else float("nan")
            widest = max(widest, s)
            cols.append(f"n={len(values)} median {statistics.median(values):.6g} "
                        f"spread {s:.4f}")
        print(f"{name}: {' | '.join(cols)} | widest {widest:.4f} "
              f"x5 {5 * widest:.4f}")


if __name__ == "__main__":
    main()
