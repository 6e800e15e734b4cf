"""The yardstick's own share of set-up: from the start of run.py until
``edl train`` is started (records, native reader, spec)."""
LAYER = "harness"
UNIT = "s"
SOURCE = "host_clock"
BETTER = "lower"
MOVES = "setup_s"


def read(run):
    return run["bench_prep_s"]
