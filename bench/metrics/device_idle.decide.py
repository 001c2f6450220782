"""Share of the control cycles' drive spans (observe, decide, apply) with
no operation on the device, in %; the simulated world's ticks lie outside
them."""
from bench.metrics import common


def read(run):
    return common.idle_within(run, "drive")
