"""Device time of the fused decide program (fit + PGD solve) per control
cycle in the window, from the trace, in ms."""
from bench.metrics import common


def read(run):
    return common.decide_device_ms(run)
