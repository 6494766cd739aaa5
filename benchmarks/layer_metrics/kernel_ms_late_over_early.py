"""kernels: kernel_ms_window of the last quarter of the window over that of the first, from the flight recorder history of the window-end [stats] snapshot (ratio)."""
from benchmarks.harness import window

read = window.kernel_ms_late_over_early
