"""Gaussian samples z = g S^(1/2) of a covariance with the configuration's
top eigenvalue, eigengap and power-law (or linear) tail under a random
rotation, drawn on the device in one jitted call and fetched to the host
once.

Configuration keys: `dim`, `lambda1`, `eigengap`, `spectrum`. Traffic keys:
`pool_samples` (rows in the replayed pool)."""
import numpy as np

from bench.generator import Pool, jax_key


def spectrum(config: dict) -> np.ndarray:
    d, lam1 = int(config["dim"]), float(config["lambda1"])
    lam2 = lam1 - float(config["eigengap"])
    if config["spectrum"] == "power":
        rest = lam2 * np.arange(1, d, dtype=np.float64) ** -0.7
    else:
        rest = np.linspace(lam2, 0.01 * lam1, d - 1)
    return np.concatenate([[lam1], rest]).astype(np.float32)


def samples(config: dict, seed: int, rows: int, device) -> np.ndarray:
    """[rows, dim] float32 samples, made on `device` in one jitted call."""
    import jax
    import jax.numpy as jnp

    d = int(config["dim"])
    evals = jnp.asarray(spectrum(config))

    def draw(key):
        kq, kz = jax.random.split(key)
        with jax.default_matmul_precision("highest"):
            q, _ = jnp.linalg.qr(jax.random.normal(kq, (d, d), jnp.float32))
            sqrt_cov = (q * jnp.sqrt(evals)) @ q.T
            return jax.random.normal(kz, (rows, d), jnp.float32) @ sqrt_cov

    key = jax.device_put(jax_key(seed, salt=1), device)
    return np.asarray(jax.jit(draw)(key))


def build(traffic: dict, config: dict, seed: int, device) -> Pool:
    return Pool({"z": samples(config, seed, int(traffic["pool_samples"]), device)})
