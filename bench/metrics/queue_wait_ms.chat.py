"""Mean time a traced admission's request waited in the engine's queue,
from ``submit`` to the dispatch of its prefill (attribute ``wait_us`` of the
program span ``repro.serve.admit``), in ms."""
from bench import program_spans


def read(run):
    wait_us = program_spans.mean_attr(run, "repro.serve.admit", "wait_us")
    return None if wait_us is None else wait_us * 1e-3
