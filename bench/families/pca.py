"""Streaming 1-PCA cells: the program's D-Krasulina superstep on its
streaming driver, built as `chip_smoke.pca_phase` builds it
(`krasulina_superstep_builder`, `fuse_xi` left to the program, the default
`EngineConfig`), with no in-scan metric oracle: a deployment has no
covariance to evaluate against.

Set-up drives the first supersteps through `StreamingDriver.run(1)` and keeps
the per-node iterates after the first; after the window the plain reference
repeats those rounds from the same start on the same rows."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare
from bench.generator import jax_key
from bench.reference import krasulina as ref

from repro.configs.base import AveragingConfig
from repro.configs.paper_pca import PCAConfig, PCARunConfig
from repro.core import krasulina
from repro.train.driver import EngineConfig, StreamingDriver


class Cell:
    def __init__(self, spec, devices, seed: int, pool):
        self.spec, self.devices, self.pool = spec, devices, pool
        cfg, traffic = spec.config, spec.traffic
        self.nodes = int(cfg["nodes"])
        self.batch = int(traffic["batch"])
        self.c = float(cfg["stepsize_c"])
        self.rounds_r = int(cfg["averaging"]["rounds"])
        self.tokens_per_sample = None
        averaging = AveragingConfig(**cfg["averaging"])
        pca = PCAConfig(dim=int(cfg["dim"]), eigengap=float(cfg["eigengap"]),
                        lambda1=float(cfg["lambda1"]), spectrum=cfg["spectrum"])
        w0 = jax.device_put(jax.random.normal(jax_key(seed, salt=2), (pca.dim,)),
                            devices[0])
        self.w0 = np.asarray(w0 / jnp.linalg.norm(w0))
        c = self.c
        builder = krasulina.krasulina_superstep_builder(
            averaging, self.nodes, lambda t: c / t)
        state = krasulina.init_krasulina_state(
            jax.device_put(self.w0, devices[0]), averaging, self.nodes)
        self.driver = StreamingDriver(
            PCARunConfig(pca=pca, averaging=averaging), None, state,
            pool.sample_fn, superstep_builder=builder, n_nodes=self.nodes,
            batch=self.batch, seed=seed % 2**32, engine=EngineConfig())
        self.k_rounds = self.driver.engine.superstep
        self.readings: Dict = {}

    def prime(self) -> None:
        self.driver.run(1)
        self.readings = {"w": np.asarray(self.driver.state.w)}

    def close(self) -> None:
        if self.driver is not None:
            self.driver.close()
            self.driver.state = None
            self.driver = None

    def reference(self, precision: str = "highest", exchange: bool = True,
                  half: bool = False) -> dict:
        """The reference's readings; `precision="high"` is the control,
        `exchange=False` leaves the gossip out and `half=True` drops half of
        each node's rows (faults)."""
        rows = self.pool.rows_at(0, self.k_rounds * self.batch)["z"]
        batches = rows.reshape(self.k_rounds, self.nodes, self.batch // self.nodes, -1)
        if half:
            batches = batches[:, :, : batches.shape[2] // 2]
        w0 = np.tile(self.w0[None], (self.nodes, 1))
        with jax.default_device(self.devices[0]):
            w = ref.run(w0, batches, rounds=self.rounds_r if exchange else 0,
                        c=self.c, precision=precision)
        return {"w": w}

    def explain(self, r: dict, prog: dict = None) -> Dict[str, list]:
        return {}

    def variants(self) -> Dict[str, dict]:
        return {"control": {"precision": "high"}, "no_exchange": {"exchange": False},
                "half_batch": {"half": True}}

    def gaps(self, r: dict, prog: dict = None) -> Dict[str, float]:
        prog = prog if prog is not None else self.readings
        w0 = self.w0[None].astype(np.float64)
        dp = np.linalg.norm(prog["w"].astype(np.float64) - w0, axis=1)
        dr = np.linalg.norm(r["w"].astype(np.float64) - w0, axis=1)
        scale = np.max(np.abs(r["w"]))
        return {
            "update": compare.norm_gap({"w": dp}, {"w": dr}),
            "iterate": float(np.max(np.abs(prog["w"] - r["w"])) / scale),
        }
