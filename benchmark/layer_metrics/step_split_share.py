"""Share of the device's busy time per step that the five classes of
the compiled step's ops explain: ``fwd + bwd + remat + optimizer +
mixed`` over ``step_device_ms``. What is missing is ``reduce`` (the
psums), collectives, the tiny per-step programs, and ops the program's
map does not name. It guards the five as ``loop_accounted_share``
guards the phase clocks: under about 90% the split does not explain
the step, and the cause (instruction names that do not match, a class
that is missing) comes before any reading of the others. 0.0 on a
program that wrote no map of its step's ops (_split.py)."""
import _split

LAYER = "step"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = "tokens_per_s_per_chip"


def read(run):
    split = _split.split_s(run)
    if split is None:
        return None
    return 100.0 * sum(split.values()) / run["trace"]["busy_s"]
