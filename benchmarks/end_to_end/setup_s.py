"""Process start -> first request of the window: build, format, boot, compile, account load, warm-up (s)."""
from benchmarks.harness import readers

read = readers.setup_s
