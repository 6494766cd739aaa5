"""device: 1 - device seconds booked to commit launches over the window ([stats] device.commit_busy_s delta) (%)."""
from benchmarks.harness import window

read = window.device_idle_window
