"""Look at a profiler trace by hand before writing a pattern against it:
every plane and line of every ``*.xplane.pb`` under a directory, with event
counts, the names that took most time and the stats their events carry.

    python3 benchmarks/tools/trace_summary.py <trace_dir> [names_per_line]
"""

from __future__ import annotations

import os
import sys
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    from jax.profiler import ProfileData

    from benchmarks.trace.extract import xplane_files

    top = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    for path in xplane_files(sys.argv[1]):
        print(f"FILE {path} ({os.path.getsize(path)} bytes)")
        for plane in ProfileData.from_file(path).planes:
            print(f" PLANE {plane.name!r}")
            for line in plane.lines:
                seconds: Counter = Counter()
                count: Counter = Counter()
                sample = {}
                lo, hi, n = float("inf"), 0.0, 0
                for e in line.events:
                    n += 1
                    seconds[e.name] += e.duration_ns * 1e-9
                    count[e.name] += 1
                    lo = min(lo, e.start_ns)
                    hi = max(hi, e.start_ns + e.duration_ns)
                    if e.name not in sample:
                        sample[e.name] = {k: str(v)[:80] for k, v in e.stats}
                span = (hi - lo) * 1e-9 if n else 0.0
                print(f"  LINE {line.name!r}: {n} events over {span:.4f} s")
                for name, sec in seconds.most_common(top):
                    print(f"    {sec:.6f} s  x{count[name]:<6} {name[:90]!r} "
                          f"{sample[name]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
