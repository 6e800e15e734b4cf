"""The longest measured sync window: beside step_ms_p50 it says whether
one stall set the run's rate."""
LAYER = "worker loop"
UNIT = "s"
SOURCE = "program_counter"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return max(w["seconds"] for w in run["windows"])
