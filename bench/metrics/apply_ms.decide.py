"""Host time of arbitrating and applying the plan (program span
``repro.mudap.apply``) per traced control cycle (``repro.env.drive``), in
ms."""
from bench import program_spans


def read(run):
    return program_spans.per_cycle_ms(run, ["repro.mudap.apply"])
