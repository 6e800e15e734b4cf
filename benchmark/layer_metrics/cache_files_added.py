"""Compiled programs the job added to the persistent cache: 0 on every
run of a cell in a checkout but its first."""
LAYER = "compile cache"
UNIT = "files"
SOURCE = "program_counter"
BETTER = "lower"
MOVES = "setup_s"


def read(run):
    return run["cache_files_added"]
