"""The replay pool that hands traffic to the system, and the lookup of a
traffic mix's generator.

A traffic mix is a data file, `traffic/<traffic>.json`, of parameters; its
`generator` key names a module `generators/<generator>.py` whose
`build(traffic, config, seed, device)` makes the pool from the run's seed
during set-up. A new mix adds a data file; a new kind of traffic adds a
generator file beside the others, and no existing file changes.

Generation is the benchmark's work and happens before the window opens;
splitting, stacking and staging the handed-over arrays is the system's. Each
hand-over through the driver's `sample_fn(rng, n)` is stamped with the host
clock, so the update lag of every sample can be read once the superstep that
learned it has returned.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))


def seed_words(seed: int) -> Tuple[int, int]:
    """Two 31-bit words from a seed of any size (JAX keys take 32 bits)."""
    w = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return int(w[0] >> 1), int(w[1] >> 1)


def jax_key(seed: int, salt: int = 0):
    import jax

    a, b = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(a), (b + salt) & 0x7FFFFFFF)


class Pool:
    """Rows replayed in order, `n` per call, wrapping round the pool. Every
    call is logged as (samples handed over so far, host time)."""

    def __init__(self, arrays: Dict[str, np.ndarray]):
        self.arrays = arrays
        self.rows = len(next(iter(arrays.values())))
        self.log: List[Tuple[int, float]] = []
        self._next = 0
        self._lock = threading.Lock()

    def rows_at(self, start: int, n: int) -> Dict[str, np.ndarray]:
        idx = (start + np.arange(n)) % self.rows
        return {k: v[idx] for k, v in self.arrays.items()}

    def sample_fn(self, _rng, n: int) -> Dict[str, np.ndarray]:
        with self._lock:
            start = self._next
            self._next += n
        if start % self.rows + n <= self.rows:
            s = start % self.rows
            out = {k: v[s:s + n] for k, v in self.arrays.items()}
        else:
            out = self.rows_at(start, n)
        self.log.append((start + n, time.perf_counter()))
        return out


def build_pool(traffic: dict, config: dict, seed: int, device,
               bench_dir: str = BENCH) -> Pool:
    from bench.harness import load_module

    gen = load_module(bench_dir, "generators", traffic["generator"])
    return gen.build(traffic, config, seed, device)
