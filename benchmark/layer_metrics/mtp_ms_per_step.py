"""Device time per step of the multi-token-prediction module (scope
edl/mtp: its two norms and projection, its layer, its pass through the
head and its cross entropy; forward, recomputed and backward): the ops
that lie under the scope whole. An op the compiler fused with work
from outside it (the trunk's last state and the head's gradient meet
the trunk's own there) is left out, never divided."""
import _glm

LAYER = "prediction module"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    if _glm.built_with_a_prediction_module(run) is None:
        return None
    seconds = _glm.scope_s(run, _glm.MTP_SCOPE)
    if seconds is None:
        return None
    return 1e3 * seconds[0] / run["trace"]["steps"]
