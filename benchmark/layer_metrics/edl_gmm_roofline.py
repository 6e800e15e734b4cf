"""Roofline share of edl_gmm (forward and dlhs): least time by shapes
(benchmark/gmm_cost.py), over the rows the program counted as routed
to held experts and not over the padded buffer, over measured."""
import _gmm

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return _gmm.roofline(run, _gmm.GMM)
