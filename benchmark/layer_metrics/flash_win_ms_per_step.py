"""Device time of the three flash kernels under a window
(edl_flash_win_*) per step."""
import _win

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    ops = [op for kernel in _win.KERNELS for op in _win.ops(run, kernel)]
    if not ops:
        return None
    return 1e3 * sum(s for _, s, _ in ops) / run["trace"]["steps"]
