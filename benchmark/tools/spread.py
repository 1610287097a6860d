"""Spread of each end-to-end metric in the sets `sets.sh` wrote: per set the
median and the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median.

    python benchmark/tools/spread.py chiprun_out/sets-<cell>-<tag>.jsonl
"""

import json
import statistics
import sys


def main() -> None:
    for path in sys.argv[1:]:
        rows = [json.loads(line) for line in open(path) if line.strip()]
        print(path)
        sets: dict[str, dict[str, list[float]]] = {}
        bad = 0
        for r in rows:
            line = r["line"]
            bad += not line["correct"]
            for name, m in line["metrics"].items():
                sets.setdefault(r["set"], {}).setdefault(name, []).append(m["value"])
        for s, metrics in sorted(sets.items()):
            for name, v in metrics.items():
                q1, _, q3 = statistics.quantiles(v, n=4)
                med = statistics.median(v)
                print(f"  set {s} {name}: n={len(v)} median {med:.6g} "
                      f"spread {(q3 - q1) / med:.4%} min {min(v):.6g} max {max(v):.6g}")
        print(f"  runs not correct: {bad} of {len(rows)}")
        nums: dict[str, list[float]] = {}
        for r in rows:
            for name, (value, _) in r["line"]["compared"].items():
                nums.setdefault(name, []).append(value)
        for name, v in nums.items():
            print(f"  compared {name}: max {max(v):.4g} median {statistics.median(v):.4g}")


if __name__ == "__main__":
    main()
