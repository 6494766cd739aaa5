"""device: share of the traced span idle seconds whose gap is not named by one of the program own tb.* spans (reduced trace idle_gaps) (%)."""
from benchmarks.harness import window

read = window.idle_unnamed_share
