"""consensus + WAL: seconds the event loop blocked fetching commit replies from the chip ([stats] loop.fetch_s delta) over the window (%)."""
from benchmarks.harness import window

read = window.loop_fetch_share
