"""Device time of the three flash kernels at unequal head sizes
(edl_flash_mla_*: a latent attention's q and k at 192, its v at 128)
per step."""
import _mla

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    ops = [op for kernel in _mla.KERNELS for op in _mla.ops(run, kernel)]
    if not ops:
        return None
    return 1e3 * sum(s for _, s, _ in ops) / run["trace"]["steps"]
