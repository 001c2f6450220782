"""The decode steps' least time over the decode program's device time, in
%: the least time counts the weights and head once and the keys and values
of each active slot's real context (``bench.work``)."""
from bench.metrics import common


def read(run):
    return common.roofline(run, "decode", common.DECODE_PROGRAM)
