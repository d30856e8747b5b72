"""Spread of repeated runs, as the driver reads it: for each end-to-end metric
the median of a set of runs and the distance between its quartiles over the
median. Takes files that each hold the outputs of one set (the last JSON line
of every run, one per line; other lines are skipped).

    python3 benchmarks/tools/spread.py set1.jsonl set2.jsonl
"""

from __future__ import annotations

import json
import sys

import numpy as np


def last_lines(path: str) -> list[dict]:
    out = []
    for line in open(path):
        line = line.strip()
        if line.startswith('{"correct"'):
            out.append(json.loads(line))
    return out


def main() -> int:
    sets = [last_lines(p) for p in sys.argv[1:]]
    names = sorted({m for runs in sets for r in runs for m in r["metrics"]})
    for name in names:
        row = []
        for runs in sets:
            v = np.array([r["metrics"][name]["value"] for r in runs
                          if name in r["metrics"]], float)
            q1, med, q3 = np.percentile(v, [25, 50, 75])
            row.append(f"n={len(v)} median={med:.6g} iqr/median="
                       f"{(q3 - q1) / med:.4%} min={v.min():.6g} max={v.max():.6g}")
        print(f"{name}: " + " | ".join(row))
    for i, runs in enumerate(sets):
        print(f"set {i + 1}: correct={[r['correct'] for r in runs]} "
              f"failed={[r['failed'] for r in runs]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
