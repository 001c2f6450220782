"""Model operations of the tokens the traced engine steps processed, over
the summed host time of those steps times the chip's peak, in %."""
from bench.metrics import common


def read(run):
    return common.step_mfu(run)
