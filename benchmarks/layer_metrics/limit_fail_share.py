"""commit backend: window transfers answered exceeds_credits / exceeds_debits over the window transfers acknowledged, from the replies; above 0 = the traffic still crosses limits, so a program that ignored them would be caught (%)."""
from benchmarks.harness import roofline_limits

read = roofline_limits.limit_fail_share
