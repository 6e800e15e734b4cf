"""Device time of the grouped-matmul kernels (edl_gmm*, edl_tgmm*) per
step, of a step that holds KDA layers: ``gmm_ms_per_step``'s arithmetic
under a name that lists the cell of ling-3.0-flash-vl-ep64, where a
held expert expects 128 rows a step (8,192 tokens x 8 assignments over
512 experts) and gets bursts of none to 8,192 (PERF.md, Open
questions). None where ``step_built`` names no ``kda_layers`` (every
other cell, and the parent commit)."""
import _ling
import gmm_ms_per_step

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return gmm_ms_per_step.read(run) if _ling.built_with_kda(run) else None
