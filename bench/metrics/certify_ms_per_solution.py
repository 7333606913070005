"""Milliseconds of the traced window inside ``repro.serving.certify``
spans (the full-width KKT certificate and the host reads of the result
it checks) per certified solution."""
from bench import spans


def read(r):
    return spans.ms_per_solution(r, "repro.serving.certify")
