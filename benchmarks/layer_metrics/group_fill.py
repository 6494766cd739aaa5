"""commit backend: batches carried over batch slots run, at the launch site ([stats] device.commit_batches / device.commit_slots deltas) (%)."""
from benchmarks.harness import window

read = window.group_fill
