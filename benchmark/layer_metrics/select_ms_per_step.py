"""Device time per step of the key selection in front of attention
(ops/sparse_select.py): the indexer's scores and the top-k, the loops
of scopes edl/sparse_select/scores and edl/sparse_select/topk."""
import _sel

LAYER = "key selection"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    seconds = _sel.select_loops_s(run)
    if seconds is None:
        return None
    return 1e3 * seconds / run["trace"]["steps"]
