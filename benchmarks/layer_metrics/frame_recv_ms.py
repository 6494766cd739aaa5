"""wire: frames over 64 KiB, first byte read -> complete and handed on, window mean ([stats] bus.frame_recv_us) (ms)."""
from benchmarks.harness import window

read = window.frame_recv_ms
