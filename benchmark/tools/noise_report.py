#!/usr/bin/env python3
"""Where the spread between runs comes from, out of the report lines
``run.py`` prints (the line before the last): a few slow windows, a
level that holds for a whole process, or drift inside a run.

    python3 benchmark/tools/noise_report.py <run.out> [<run.out> ...]

For each run: the rate over the first 20, 30, 40 s of whole windows and
over all of them, the median window, how many windows ran 2% over it,
and the second half's median window over the first half's. Then, over
the runs, the spread the driver computes (distance between the
quartiles over the median) at each length.
"""

import json
import statistics
import sys

LENGTHS = (20, 30, 40, None)


def _rate(windows, tokens_per_window, chips, seconds):
    """As run.py computes it, over the first ``seconds`` of windows:
    the total over every window taken."""
    taken, total = 0, 0.0
    for w in windows:
        taken += 1
        total += w
        if seconds is not None and total >= seconds:
            break
    return taken * tokens_per_window / total / chips


def _spread(values):
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def main(paths):
    rates = {length: [] for length in LENGTHS}
    for path in paths:
        with open(path) as f:
            lines = [line for line in f.read().splitlines() if line.startswith("{")]
        report, result = json.loads(lines[-2]), json.loads(lines[-1])
        windows = report["window_seconds_each"]
        chips = result["device"]["count"]
        per_window = (
            report["window_steps"] / len(windows) * report["tokens_per_step"]
        )
        med = statistics.median(windows)
        half = len(windows) // 2
        row = []
        for length in LENGTHS:
            r = _rate(windows, per_window, chips, length)
            rates[length].append(r)
            row.append("%9.1f" % r)
        print(
            "%-28s %s  windows=%d median=%.4fs slow(>2%%)=%d max=%.3fs "
            "second/first half=%.4f setup=%.1fs prep=%.2fs teardown=%.1fs correct=%s"
            % (
                path[-28:], " ".join(row), len(windows), med,
                sum(w > 1.02 * med for w in windows), max(windows),
                statistics.median(windows[half:]) / statistics.median(windows[:half]),
                report["setup_s"], report["bench_prep_s"], report["teardown_s"],
                result["correct"],
            )
        )  # fmt: skip
    for length in LENGTHS:
        values = rates[length]
        print(
            "window %-4s median %9.1f  min %9.1f  max %9.1f  range/median %.4f  IQR/median %.4f"
            % (
                length or "all", statistics.median(values), min(values), max(values),
                (max(values) - min(values)) / statistics.median(values), _spread(values),
            )
        )  # fmt: skip
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
