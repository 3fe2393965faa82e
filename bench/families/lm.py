"""LM training cells: the program's transformer trainer on its streaming
driver, built as `repro.launch.train.main` builds it (host mesh, activation
rules, `init_placed_state` from the seed, the trainer's superstep builder,
the default `EngineConfig`; no publisher and no snapshotter).

Set-up drives the first supersteps through `StreamingDriver.run(1)`, the
window's own call, and reads what they produced: the loss of the first
superstep's last round, the norms of Adam's first moment (the gradients as
the optimizer got them) and the norms of the float32 master weights' change
over the superstep.
After the window the plain reference repeats those rounds from the seed on
the same rows."""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import compare
from bench.generator import jax_key
from bench.reference import granite as ref

from repro.configs.base import SHAPES, AveragingConfig, ModelConfig, RunConfig
from repro.launch.mesh import make_host_mesh, n_data_nodes
from repro.launch.sharding import activation_rules
from repro.models.common import mesh_rules
from repro.train.driver import EngineConfig, StreamingDriver
from repro.train.trainer import init_placed_state, superstep_builder

MODEL_FIELDS = {f.name for f in dataclasses.fields(ModelConfig)}


def model_config(cfg: dict) -> ModelConfig:
    return ModelConfig(**{k: v for k, v in cfg.items() if k in MODEL_FIELDS})


def _paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [jax.tree_util.keystr(k) for k, _ in flat], [x for _, x in flat]


class Cell:
    def __init__(self, spec, devices, seed: int, pool):
        self.spec, self.devices, self.pool = spec, devices, pool
        cfg, traffic = spec.config, spec.traffic
        self.mesh = make_host_mesh(n_devices=len(devices))
        self.nodes = n_data_nodes(self.mesh)
        self.averaging = dict(cfg["averaging"])
        tr = cfg["training"]
        self.run_cfg = RunConfig(
            model=model_config(cfg), shape=SHAPES["train_4k"],
            averaging=AveragingConfig(**self.averaging), optimizer=tr["optimizer"],
            learning_rate=tr["learning_rate"], param_dtype=tr["param_dtype"],
            master_weights=tr["master_weights"], remat=tr["remat"])
        self.decentralized = self.run_cfg.averaging.mode != "exact"
        self.batch = self.nodes * int(traffic["sequences_per_node"])
        self.tokens_per_sample = int(traffic["seq_len"])
        self._rules = mesh_rules(self.mesh, activation_rules(
            self.mesh, self.run_cfg.shape, node_axis=self.decentralized))
        self._rules.__enter__()
        self.key = jax_key(seed)
        state = init_placed_state(self.run_cfg, self.mesh, self.key)
        self.driver = StreamingDriver(
            self.run_cfg, self.mesh, state, pool.sample_fn, engine=EngineConfig(),
            superstep_builder=superstep_builder(self.run_cfg, self.mesh,
                                                n_nodes=self.nodes),
            batch=self.batch, seed=seed % 2**32)
        self.k_rounds = self.driver.engine.superstep
        self.readings: Dict = {}

    # ---------------------------------------------------------------- set-up

    def _node_leading(self, x):
        return x if self.decentralized else x[None]

    def prime(self) -> None:
        """Drive the first superstep (it compiles) and read what it made: the
        loss of its last round, the norms of Adam's first moment, and the
        norms of the change of the weights the optimizer updates (the float32
        masters, where the configuration keeps them) from a host copy taken
        before the superstep. The change is taken from the masters and not
        from the bfloat16 parameters: the masters may start from the
        unrounded initial weights (XLA may drop the float32 -> bfloat16 ->
        float32 round trip that makes them), and a change measured from the
        rounded parameters would count that rounding as an update."""
        state = self.driver.state
        names, start = _paths(jax.device_get(
            state.opt.master if state.opt.master != () else state.params))
        self.driver.run(1)
        rec = self.driver.history[-1]
        opt = self.driver.state.opt
        lead = self._node_leading

        def node_norms(a):
            a = lead(a).astype(jnp.float32)
            return jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim))))

        m_norms = jax.device_get(jax.jit(lambda m: jax.tree.map(node_norms, m))(opt.m))
        change = jax.jit(lambda a, b: node_norms(a.astype(jnp.float32) - b))
        after = _paths(opt.master if opt.master != () else self.driver.state.params)[1]
        d_leaves = [np.asarray(change(a, jax.device_put(b.astype(np.float32), a.sharding)))
                    for a, b in zip(after, start)]
        del start
        self.readings = {
            "loss": float(rec["metrics"]["loss"]),
            "m": dict(zip(names, map(np.asarray, _paths(m_norms)[1]))),
            "delta": dict(zip(names, d_leaves)),
        }

    # ----------------------------------------------------------------- check

    def close(self) -> None:
        """Stop the driver and drop the program's state."""
        if self.driver is not None:
            self.driver.close()
            self.driver.state = None
            self.driver = None
        if self._rules is not None:
            self._rules.__exit__(None, None, None)
            self._rules = None

    def _rounds(self):
        """(tokens, labels) per round of the first superstep, [N, b, S]."""
        out = []
        for r in range(self.k_rounds):
            rows = self.pool.rows_at(r * self.batch, self.batch)
            out.append(tuple(rows[k].reshape(self.nodes, -1, rows[k].shape[-1])
                             for k in ("tokens", "labels")))
        return out

    def reference(self, numerics: str = "f32", exchange: bool = True,
                  frozen: bool = False) -> dict:
        """The reference's readings; `numerics="fp8"` is the control,
        `exchange=False` leaves the gossip out and `frozen=True` keeps the
        weights where they start (faults)."""
        averaging = dict(self.averaging, rounds=self.averaging["rounds"] if exchange else 0)
        train = dict(self.spec.config["training"])
        if frozen:
            train["learning_rate"] = 0.0
        sharding = None
        if self.nodes > 1:
            mesh = Mesh(np.asarray(self.devices[:self.nodes]), ("nodes",))
            sharding = NamedSharding(mesh, P("nodes"))
        return ref.run(self.spec.config, train, averaging,
                       self.key, self._rounds(), nodes=self.nodes,
                       numerics=numerics, sharding=sharding)

    def gaps(self, r: dict, prog: dict = None) -> Dict[str, float]:
        """The compared numbers of `prog` (default: the program's readings)
        against the reference readings `r`."""
        prog = prog if prog is not None else self.readings
        prog_loss = prog["loss"] if np.ndim(prog["loss"]) == 0 else float(
            np.mean(prog["loss"][-1]))
        return {
            "loss": compare.rel_gap(prog_loss, float(np.mean(r["loss"][-1]))),
            "grad": compare.norm_gap(prog["m"], r["m"], grad_ref=r["m"]),
            "update": compare.norm_gap(prog["delta"], r["delta"], grad_ref=r["m"]),
        }

    def explain(self, r: dict, prog: dict = None) -> Dict[str, list]:
        """The three leaves that read worst in each norm gap."""
        prog = prog if prog is not None else self.readings
        out = {}
        for key in ("m", "delta"):
            leaf = compare.leaf_gaps(prog[key], r[key], grad_ref=r["m"])
            out[key] = sorted(leaf.items(), key=lambda kv: -kv[1])[:3]
        return out

    def variants(self) -> Dict[str, dict]:
        """The control and the faults the reference can stand in for."""
        out = {"control": {"numerics": "fp8"}, "unchanged": {"frozen": True}}
        if self.decentralized:
            out["no_exchange"] = {"exchange": False}
        return out
