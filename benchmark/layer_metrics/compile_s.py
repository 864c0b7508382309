"""Seconds of set-up that went into making programs runnable: JAX's own
timers for tracing, lowering, backend compilation and reading the persistent
cache, summed over every program first called before the window opened."""

LAYER = "entry points + utils/compile_cache.py"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(run):
    return run["setup"].get("compile_s")
