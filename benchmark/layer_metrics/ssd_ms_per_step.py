"""Device time per step of the Mamba-2 state-space scan
(elasticdl_tpu/ops/ssd.py, scope edl/ssd): its chunk loops, forward,
recomputed and backward."""
import _ssd

LAYER = "state-space scan"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    seconds = _ssd.scan_loops_s(run)
    if seconds is None:
        return None
    return 1e3 * seconds / run["trace"]["steps"]
