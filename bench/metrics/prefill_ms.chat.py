"""Mean host time of a traced admission: prefill dispatch to the first
token on the host (harness span ``prefill``), in ms."""
from bench.metrics import common


def read(run):
    return common.mean_span_ms(run, "prefill")
