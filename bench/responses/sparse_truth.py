"""Responses of one sparse truth each, the same for every least-squares
design: ``nnz`` coefficients from U[-1, 1] at random columns, plus noise
of the signal's own standard deviation times ``noise``, scaled to unit
rms."""
import jax
import jax.numpy as jnp


def make(key, X, design: dict, spec: dict, design_mod):
    n, p = X.shape
    count, nnz = int(spec["count"]), int(spec["nnz"])
    noise = float(spec.get("noise", 1.0))

    def truth(kk):
        ki, kv = jax.random.split(kk)
        idx = jax.random.permutation(ki, p)[:nnz]
        vals = jax.random.uniform(kv, (nnz,), jnp.float32, -1.0, 1.0)
        return jnp.zeros((p,), jnp.float32).at[idx].set(vals)

    kb, ke = jax.random.split(key)
    S = jnp.dot(jax.vmap(truth)(jax.random.split(kb, count)), X.T,
                precision="highest")                          # (count, n)
    Y = S + noise * jnp.std(S, axis=1, keepdims=True) * jax.random.normal(
        ke, S.shape, jnp.float32)
    return Y / jnp.sqrt(jnp.mean(Y * Y, axis=1, keepdims=True))
