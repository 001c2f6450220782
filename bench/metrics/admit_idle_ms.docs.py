"""Mean time inside a traced admission (program span
``repro.serve.admit``: pad, upload, prefill dispatch, first-token sync)
with no operation on the device, in ms."""
from bench import program_spans


def read(run):
    return program_spans.mean_idle_ms(run, "repro.serve.admit")
