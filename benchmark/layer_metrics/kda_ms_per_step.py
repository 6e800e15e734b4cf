"""Device time per step of the Kimi-Delta-Attention recurrence
(elasticdl_tpu/ops/kda.py, scope edl/kda): its chunk loops, forward,
recomputed and backward."""
import _kda

LAYER = "delta-rule scan"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    seconds = _kda.state_loops_s(run)
    if seconds is None:
        return None
    return 1e3 * seconds / run["trace"]["steps"]
