"""Milliseconds of the traced window inside ``repro.session.prepare``
spans (per-request preparation: c0 statistics, h, capacity, cold start)
per certified solution."""
from bench import spans


def read(r):
    return spans.ms_per_solution(r, "repro.session.prepare")
