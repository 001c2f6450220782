"""Share of the traced window with no operation on the device, in %."""
from bench.metrics import common


def read(run):
    return common.idle(run)
