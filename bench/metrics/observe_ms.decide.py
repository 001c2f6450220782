"""Host time of the agent's observe (program span ``repro.rask.observe``:
the windowed telemetry query, the training-table appends, the SLO
accountant) per traced control cycle (``repro.env.drive``), in ms."""
from bench import program_spans


def read(run):
    return program_spans.per_cycle_ms(run, ["repro.rask.observe"])
