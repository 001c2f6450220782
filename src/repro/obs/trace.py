"""The program's own host spans, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation``: while a profiler trace
runs (``jax.profiler.trace(dir)`` or ``start_trace``/``stop_trace``) it
lands in the trace's ``.xplane.pb`` beside the device's operations, with its
keyword attributes as event stats; with no trace running it costs about a
microsecond and records nothing. Attributes are scalars. An attribute known
only once the work is done is added on exit with ``set_metadata``::

    with trace.span(trace.MUDAP_APPLY) as s:
        receipt = platform.apply_plan(plan)
        s.set_metadata(changed=n)

Counters stay in ``repro.core.regression.TRACE_COUNTS``.
"""
from __future__ import annotations

import jax

PREFIX = "repro."

SERVE_STEP = PREFIX + "serve.step"        # ServingEngine.step, whole
SERVE_ADMIT = PREFIX + "serve.admit"      # one admission, to its first token
SERVE_DECODE = PREFIX + "serve.decode"    # the decode dispatch and its sync
ENV_TICK = PREFIX + "env.tick"            # one simulated second
ENV_DRIVE = PREFIX + "env.drive"          # one control cycle: observe..apply
ENV_RECORD = PREFIX + "env.record"        # the cycle's measured fulfillment
RASK_OBSERVE = PREFIX + "rask.observe"    # telemetry into the training table
RASK_DECIDE = PREFIX + "rask.decide"      # RASKAgent.decide, whole
RASK_PACK = PREFIX + "rask.pack"          # fit inputs to device arrays
RASK_DISPATCH = PREFIX + "rask.dispatch"  # enqueue of the fused decide
RASK_RESYNC = PREFIX + "rask.resync"      # the streaming fit's exact resync
RASK_COLLECT = PREFIX + "rask.collect"    # host blocked on the decide, + d2h
MUDAP_APPLY = PREFIX + "mudap.apply"      # plan arbitration and apply

SPANS = (SERVE_STEP, SERVE_ADMIT, SERVE_DECODE, ENV_TICK, ENV_DRIVE,
         ENV_RECORD, RASK_OBSERVE, RASK_DECIDE, RASK_PACK, RASK_DISPATCH,
         RASK_RESYNC, RASK_COLLECT, MUDAP_APPLY)


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (one of ``SPANS``) with scalar
    attributes, as a context manager."""
    return jax.profiler.TraceAnnotation(name, **attrs)
