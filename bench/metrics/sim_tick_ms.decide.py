"""Mean host time of one simulated second of the edge deployment (program
span ``repro.env.tick``: the load patterns, the container pool's step, the
telemetry scrape) in the traced window, in ms."""
from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, "repro.env.tick")
