"""Spreads of a cell's end-to-end metrics over sets of runs, as the
builder's contract measures them: the distance between the first and the
third quartile (`statistics.quantiles(values, n=4)`) as a share of the
median; per metric the wider of the sets' spreads; a bound of about five
times that, never under 1 %.

    python3 benchmark/tools/spread.py chiprun_out/runs/setA-*.out -- chiprun_out/runs/setB-*.out

Each file's last line is a run's result line.
"""

import json
import statistics
import sys


def last_line(path):
    with open(path) as f:
        lines = [line for line in f.read().splitlines() if line.strip()]
    return json.loads(lines[-1])


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv):
    sets, current = [], []
    for arg in argv:
        if arg == "--":
            sets.append(current)
            current = []
        else:
            current.append(arg)
    sets.append(current)
    runs = [[last_line(p) for p in files] for files in sets if files]
    names = sorted({m for s in runs for r in s for m in r["metrics"]})
    for name in names:
        per_set = []
        for s in runs:
            values = [r["metrics"][name]["value"] for r in s if name in r["metrics"]]
            per_set.append({"n": len(values), "median": statistics.median(values),
                            "min": min(values), "max": max(values),
                            "spread": spread(values) if len(values) >= 2 else None})
        widest = max(p["spread"] for p in per_set if p["spread"] is not None)
        print(json.dumps({"metric": name, "sets": per_set, "widest_spread": widest,
                          "five_times": max(5 * widest, 0.01)}))
    wrong = [r for s in runs for r in s if not r["correct"]]
    print(json.dumps({"runs": sum(len(s) for s in runs), "not_correct": len(wrong)}))


if __name__ == "__main__":
    main(sys.argv[1:])
