"""Device time inside collective ops per step."""
LAYER = "collectives"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    trace = run["trace"]
    if not trace or not trace["collective_s"]:
        return None
    return 1e3 * trace["collective_s"] / trace["steps"]
