"""The most loaded held expert's rows over the mean held expert's, per
measured window, averaged: 1.0 is even routing. Each (layer, held
expert) pair is one group of a grouped product, and the largest group
is what a tile schedule waits for. From the program's own counters
(``moe_rows_max_expert`` / ``moe_rows_mean_expert`` of the
``train_window`` events)."""
import statistics

LAYER = "expert layer"
UNIT = "ratio"
SOURCE = "program_counter"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    ratios = [
        w["moe_rows_max_expert"] / w["moe_rows_mean_expert"]
        for w in run["windows"]
        if w.get("moe_rows_mean_expert")
    ]
    return statistics.mean(ratios) if ratios else None
