"""The most loaded held expert's rows over the mean held expert's, per
measured window, averaged, of a step that holds KDA layers:
``moe_load_max_over_mean``'s arithmetic (the program's routing
counters) under a name that lists the cell of ling-3.0-flash-vl-ep64,
whose router is limited to 4 of 8 groups and steered by a bias. None
where ``step_built`` names no ``kda_layers``."""
import _ling
import moe_load_max_over_mean

LAYER = "expert layer"
UNIT = "ratio"
SOURCE = "program_counter"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    if not _ling.built_with_kda(run):
        return None
    return moe_load_max_over_mean.read(run)
