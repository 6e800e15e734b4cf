"""The first sync window: the step's trace, lowering and compile (or
cache load), then its first steps."""
from _common import ev

LAYER = "trainer"
UNIT = "s"
SOURCE = "program_span"
BETTER = "lower"
MOVES = "setup_s"


def read(run):
    windows = ev.of_kind(run["events"], "train_window")
    return windows[0]["seconds"] if windows else None
