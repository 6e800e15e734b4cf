"""Share of the measured seconds spent in stalled windows: windows over
1.5 x the run's median window, in which the worker (or the host under
it) stood still for seconds. The rate keeps them in, because a user
pays for them; this says how much of a low rate they were."""
import statistics

LAYER = "worker loop"
UNIT = "%"
SOURCE = "program_counter"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"

STALL_FACTOR = 1.5


def read(run):
    seconds = [w["seconds"] for w in run["windows"]]
    limit = STALL_FACTOR * statistics.median(seconds)
    return 100.0 * sum(s for s in seconds if s > limit) / sum(seconds)
