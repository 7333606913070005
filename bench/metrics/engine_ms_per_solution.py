"""Milliseconds of the traced window inside ``repro.engine.run`` spans
(each engine dispatch through the overflow read that waits for it) per
certified solution. Less ``screen_ms_per_solution`` and
``cm_ms_per_solution``, it is the engine's time outside the two
kernels."""
from bench import spans


def read(r):
    return spans.ms_per_solution(r, "repro.engine.run")
