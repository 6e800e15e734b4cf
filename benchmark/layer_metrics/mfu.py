"""Model FLOP/s utilisation: the rate times the FLOPs forward and
backward require per token (no recompute), over the chip's bf16 peak."""
from _common import flops

LAYER = "step"
UNIT = "%"
SOURCE = "program_counter"
BETTER = "higher"
MOVES = "tokens_per_s_per_chip"


def read(run):
    return flops.mfu_percent(
        run["tokens_per_s_per_chip"],
        run["config"]["model_params"],
        run["traffic"]["seq_len"],
        run["device_kind"],
    )
