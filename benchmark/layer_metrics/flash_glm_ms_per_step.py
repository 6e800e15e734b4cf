"""Device time per step of the three flash kernels (edl_flash_*) at the
one head size of a latent attention whose q, k and v are equally wide
(256 in glm-4.7-flash-ep8): the trunk's layers' calls and the
prediction module's, forward, recomputed and backward."""
import _glm

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    ops = [op for kernel in _glm.KERNELS for op in _glm.flash_ops(run, kernel)]
    if not ops:
        return None
    return 1e3 * sum(s for _, s, _ in ops) / run["trace"]["steps"]
