"""consensus + WAL: the event loop's busy seconds ([stats] loop.busy_s) over the window (%)."""
from benchmarks.harness import readers

read = readers.loop_busy_share
