"""The admissions' least time at their true prompt lengths over the prefill
program's device time, in %: the head counts at the last position only
(``bench.work``)."""
from bench.metrics import common


def read(run):
    return common.roofline(run, "prefill", common.PREFILL_PROGRAM)
