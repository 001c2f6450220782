"""The TPU adaptation (DESIGN.md §2): MUDAP + RASK autoscaling three
co-located LM *serving* services sharing one pod's chip budget.

Elasticity dimensions per service: chips (resource), context budget
(data-quality analog), model rung (model-size analog). Throughput surfaces
are the analytic roofline rates of ``repro.env.profiles.lm_profile``.

    PYTHONPATH=src python examples/autoscale_lm_services.py
"""
from repro.launch.autoscale import main

history = main(["--minutes", "10", "--chips", "16", "--pattern", "diurnal"])
