"""The most loaded held expert's rows over the mean held expert's, per
measured window, averaged, of a step that holds a prediction module:
``moe_load_max_over_mean``'s arithmetic (the program's routing
counters, the module's router among them) under a name that lists the
cell of glm-4.7-flash-ep8. None where ``step_built`` names no
``mtp_layers``."""
import _glm
import moe_load_max_over_mean

LAYER = "expert layer"
UNIT = "ratio"
SOURCE = "program_counter"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    if _glm.built_with_a_prediction_module(run) is None:
        return None
    return moe_load_max_over_mean.read(run)
