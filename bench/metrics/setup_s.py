"""Seconds from process start to the start of the window: making the data,
admitting the problems, opening the server, warming every program the
mix uses and, in a run that compiles, compilation."""


def read(r):
    return r.setup_s
