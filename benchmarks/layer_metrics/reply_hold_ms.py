"""wire: a create's reply built -> the flush that writes it, window mean of latency.create.reply_egress_us (ms)."""
from benchmarks.harness import readers

read = readers.hist_ms("latency.create.reply_egress_us")
