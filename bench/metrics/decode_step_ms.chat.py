"""Traced decode time over traced decode steps (harness span ``decode``:
the one decode dispatch for all slots, to its tokens on the host), in ms."""
from bench.metrics import common


def read(run):
    return common.mean_span_ms(run, "decode")
