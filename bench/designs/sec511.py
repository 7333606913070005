"""Design of arXiv:1806.05817 Sec 5.1.1: X ~ U[low, high]; a truth with a
share of nonzero coefficients drawn from U[beta_low, beta_high]; the
response X beta + N(0, noise_std^2), scaled to unit rms.

Traceable: ``design`` and ``responses`` run inside the one jitted call
that makes a run's data on the device (``bench.data``)."""
import jax
import jax.numpy as jnp


def design(key, d):
    return jax.random.uniform(key, (d["n"], d["p"]), jnp.float32,
                              d["x_low"], d["x_high"])


def responses(key, X, d, count):
    """``count`` responses, each from its own truth and noise."""
    n, p = X.shape
    k = int(round(d["active_share"] * p))

    def truth(kk):
        ki, kv = jax.random.split(kk)
        idx = jax.random.permutation(ki, p)[:k]
        vals = jax.random.uniform(kv, (k,), jnp.float32, d["beta_low"],
                                  d["beta_high"])
        return jnp.zeros((p,), jnp.float32).at[idx].set(vals)

    kb, ke = jax.random.split(key)
    B = jax.vmap(truth)(jax.random.split(kb, count))            # (count, p)
    Y = jnp.dot(B, X.T, precision="highest")
    Y = Y + d["noise_std"] * jax.random.normal(ke, Y.shape, jnp.float32)
    return Y / jnp.sqrt(jnp.mean(Y * Y, axis=1, keepdims=True))
