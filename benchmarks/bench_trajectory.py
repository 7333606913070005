"""Fig. 3 reproduction: the active-set size trajectory of SAIF — |A_t|
must grow from a small seed to ~|support| (Theorem 1/3)."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import simulation_data
from repro.core import SaifConfig, saif, solve_lasso_cm, get_loss
from repro.core.duality import lambda_max


def run(full: bool = False):
    X, y, _ = simulation_data(n=100, p=3000 if full else 800)
    loss = get_loss("least_squares")
    lmax = float(lambda_max(loss, jnp.asarray(X), jnp.asarray(y)))
    rows = []
    for frac in (0.1, 0.02):
        res = saif(X, y, frac * lmax, SaifConfig(eps=1e-8))
        tr_n = np.asarray(res.trace_n_active)
        tr_n = tr_n[tr_n >= 0]
        beta_ref = solve_lasso_cm(loss, jnp.asarray(X), jnp.asarray(y),
                                  frac * lmax, tol=1e-10)
        sup = int(np.sum(np.abs(np.asarray(beta_ref)) > 1e-9))
        rows.append({"lam_frac": frac, "start_size": int(tr_n[0]),
                     "peak_size": int(tr_n.max()), "opt_support": sup,
                     "n_outer": int(res.n_outer)})
        print(f"[fig3] lam={frac}*lmax start={tr_n[0]:.0f} "
              f"peak={tr_n.max():.0f} support={sup}")
    return rows


if __name__ == "__main__":
    run(full=True)
