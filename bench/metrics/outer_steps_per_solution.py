"""Mean outer steps of the engine (``SaifResult.n_outer``) over the
solutions completed in the window: one per Scalar, one per grid point of
a path (each point's own ``SaifResult`` in ``results``)."""
import numpy as np


def steps(value) -> list:
    """The ``n_outer`` of each solution a served value holds."""
    points = getattr(value, "results", None)
    if points is None:
        return [int(np.asarray(value.n_outer))]
    return [int(np.asarray(res.n_outer)) for res in points]


def read(r):
    out = [s for v in r.results for s in steps(v.value)]
    return float(np.mean(out)) if out else None
