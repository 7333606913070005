"""The design protocol's own responses: the design generator's
``responses`` (``bench/designs/<generator>.py``)."""


def make(key, X, design: dict, spec: dict, design_mod):
    return design_mod.responses(key, X, design, int(spec["count"]))
