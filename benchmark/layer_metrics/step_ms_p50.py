"""Median over the measured windows of window seconds / window steps."""
import _common

LAYER = "step"
UNIT = "ms"
SOURCE = "program_counter"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"
read = _common.step_ms_p50
