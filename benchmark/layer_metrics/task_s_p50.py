"""Median seconds from a task's dispatch to the master's receipt of its
report, over the tasks reported during the measured window."""
import statistics

from _common import ev

LAYER = "master"
UNIT = "s"
SOURCE = "program_counter"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    took = [
        e["dispatch_to_report_s"]
        for e in ev.of_kind(run["events"], "task_done")
        if run["window_start"] <= e["ts"] and "dispatch_to_report_s" in e
    ]
    return statistics.median(took) if took else None
