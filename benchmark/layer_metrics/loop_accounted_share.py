"""Share of the measured windows' seconds that the worker's own phase
clocks account for: the sum of the eight ``<phase>_s`` fields of the
measured ``train_window`` events over the sum of their ``seconds``.
It guards every per-phase number: below about 95% a piece of the step
loop that no phase covers is eating the step. A window with no phase
field (a program without the clocks) accounts for nothing, which is
true of it, so this reads 0.0 there and not None."""
LAYER = "worker loop"
UNIT = "%"
SOURCE = "program_span"
BETTER = "higher"
MOVES = "tokens_per_s_per_chip"

# profiling.STEP_PHASES, by name: the benchmark imports nothing of the
# program
PHASES = (
    "world_poll",
    "input_wait",
    "batch_place",
    "dispatch",
    "fetch",
    "report",
    "stage_next",
    "cadence",
)


def read(run):
    windows = run["windows"]
    accounted = sum(w.get(p + "_s", 0.0) for w in windows for p in PHASES)
    return 100.0 * accounted / sum(w["seconds"] for w in windows)
