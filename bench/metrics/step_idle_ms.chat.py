"""Mean time inside a traced engine step (program span
``repro.serve.step``: admissions, the decode dispatch, the token hand-out)
with no operation on the device, in ms."""
from bench import program_spans


def read(run):
    return program_spans.mean_idle_ms(run, "repro.serve.step")
