"""Mean outer steps of the engine (``SaifResult.n_outer``) over the
solutions completed in the window."""
import numpy as np


def read(r):
    steps = [int(np.asarray(v.value.n_outer)) for v in r.results]
    return float(np.mean(steps)) if steps else None
