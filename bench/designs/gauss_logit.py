"""Design of arXiv:1806.05817 Sec 5.2 (logistic): X ~ N(0, 1); a truth
with ``nnz`` coefficients drawn from U[w_low, w_high]; labels
sign(X w + label_noise * eps), eps ~ N(0, 1), a zero mapped to +1.

Traceable: ``design`` and ``responses`` run inside the one jitted call
that makes a run's data on the device (``bench.data``)."""
import jax
import jax.numpy as jnp


def design(key, d):
    return jax.random.normal(key, (d["n"], d["p"]), jnp.float32)


def responses(key, X, d, count):
    """``count`` label sets, each from its own truth and noise."""
    n, p = X.shape
    k = d["nnz"]

    def truth(kk):
        ki, kv = jax.random.split(kk)
        idx = jax.random.permutation(ki, p)[:k]
        vals = jax.random.uniform(kv, (k,), jnp.float32, d["w_low"],
                                  d["w_high"])
        return jnp.zeros((p,), jnp.float32).at[idx].set(vals)

    kw, ke = jax.random.split(key)
    W = jax.vmap(truth)(jax.random.split(kw, count))            # (count, p)
    Z = jnp.dot(W, X.T, precision="highest")
    Z = Z + d["label_noise"] * jax.random.normal(ke, Z.shape, jnp.float32)
    return jnp.where(Z >= 0, 1.0, -1.0).astype(jnp.float32)
