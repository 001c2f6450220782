"""Host time of packing the fused decide's inputs and enqueueing it
(program spans ``repro.rask.pack`` and ``repro.rask.dispatch``) per traced
control cycle (``repro.env.drive``), in ms."""
from bench import program_spans


def read(run):
    return program_spans.per_cycle_ms(
        run, ["repro.rask.pack", "repro.rask.dispatch"])
