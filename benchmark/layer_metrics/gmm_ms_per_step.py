"""Device time of the grouped-matmul kernels (edl_gmm*, edl_tgmm*) per
step: the expert layers' products over the rows of the experts held."""
import _gmm

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    ops = _gmm.ops(run, _gmm.GMM) + _gmm.ops(run, _gmm.TGMM)
    if not ops:
        return None
    return 1e3 * sum(s for _, s, _ in ops) / run["trace"]["steps"]
