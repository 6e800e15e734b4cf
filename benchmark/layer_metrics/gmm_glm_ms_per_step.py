"""Device time of the grouped-matmul kernels (edl_gmm*, edl_tgmm*) per
step, of a step that holds a prediction module: ``gmm_ms_per_step``'s
arithmetic under a name that lists the cell of glm-4.7-flash-ep8, whose
five expert layers (the module's among them) each expect 512 rows a
held expert a step (8,192 tokens x 4 assignments over 64 experts). None
where ``step_built`` names no ``mtp_layers`` (every other cell, and the
parent commit)."""
import _glm
import gmm_ms_per_step

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    if _glm.built_with_a_prediction_module(run) is None:
        return None
    return gmm_ms_per_step.read(run)
