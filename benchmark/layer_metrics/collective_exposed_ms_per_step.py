"""The part of collective time during which no other op ran on the
device: what the step really waits for."""
LAYER = "collectives"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    trace = run["trace"]
    if not trace or not trace["collective_s"]:
        return None
    return 1e3 * trace["collective_exposed_s"] / trace["steps"]
