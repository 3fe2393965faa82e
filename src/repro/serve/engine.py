"""Batched serving: prefill + greedy/temperature decode over the sharded KV
cache. `serve_step` is the unit the decode-shape dry-runs lower: ONE new token
against a cache of seq_len.

`ContinuousBatchingEngine` is the production decode loop on top of the same
model API (docs/DESIGN.md §Train-to-serve publication): a fixed pool of KV
slots, requests admitted (prefill-on-admit) and retired per decode step, and
hot weight swaps between steps — params is a traced argument of the one
compiled decode step, so a newly published version changes neither shapes nor
the executable, and in-flight requests continue bit-exactly on the new
weights with zero loss.
"""
from __future__ import annotations

from collections import deque
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import registry

Tree = Any


class ServeState(NamedTuple):
    cache: Tree
    last_tokens: jax.Array  # [B, 1]
    index: jax.Array  # scalar int32: number of valid cache positions


def init_serve(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               window_override: int = 0) -> ServeState:
    cache = registry.init_cache(cfg, batch, max_len, dtype,
                                window_override=window_override)
    return ServeState(cache, jnp.zeros((batch, 1), jnp.int32),
                      jnp.zeros((), jnp.int32))


def prefill(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            state: ServeState, *, window_override: int = 0) -> ServeState:
    logits, cache = registry.prefill(params, cfg, batch, state.cache,
                                     window_override=window_override)
    nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    return ServeState(cache, nxt, jnp.asarray(batch["tokens"].shape[1], jnp.int32))


def serve_step(params, cfg: ModelConfig, state: ServeState, *,
               window_override: int = 0, temperature: float = 0.0,
               key: Optional[jax.Array] = None) -> Tuple[ServeState, jax.Array]:
    """Decode ONE token for the whole batch. Returns (state, token [B, 1])."""
    logits, cache = registry.decode_step(params, cfg, state.last_tokens,
                                         state.cache, state.index,
                                         window_override=window_override)
    lf = logits[:, -1].astype(jnp.float32)
    if temperature > 0.0 and key is not None:
        nxt = jax.random.categorical(key, lf / temperature, axis=-1)[:, None]
    else:
        nxt = jnp.argmax(lf, axis=-1)[:, None]
    nxt = nxt.astype(jnp.int32)
    return ServeState(cache, nxt, state.index + 1), nxt


def generate(params, cfg: ModelConfig, prompt: Dict[str, jax.Array], max_len: int,
             steps: int, *, dtype=jnp.bfloat16, window_override: int = 0) -> jax.Array:
    """Simple static-batch greedy generate loop (examples / tests): one
    prefill, then `steps - 1` serve_steps. Params are jit arguments, not
    closed-over constants: folded into the program, a model's weights make
    the compile take minutes and the executable as large as the model."""
    B = prompt["tokens"].shape[0]
    st = init_serve(cfg, B, max_len, dtype, window_override=window_override)
    st = jax.jit(lambda p, b, s: prefill(p, cfg, b, s,
                                         window_override=window_override)
                 )(params, prompt, st)
    toks = [st.last_tokens]
    step = jax.jit(lambda p, s: serve_step(p, cfg, s,
                                           window_override=window_override))
    for _ in range(steps - 1):
        st, t = step(params, st)
        toks.append(t)
    return jnp.concatenate(toks, axis=1)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


class Request:
    """Host-side bookkeeping for one in-flight generation request."""

    __slots__ = ("rid", "prompt", "max_new", "tokens", "versions", "slot",
                 "submitted_step", "finished_step")

    def __init__(self, rid: int, prompt: np.ndarray, max_new: int):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.tokens: List[int] = []  # generated token ids
        self.versions: List[int] = []  # param version each token was decoded under
        self.slot: Optional[int] = None
        self.submitted_step: Optional[int] = None
        self.finished_step: Optional[int] = None


class StepEvents(NamedTuple):
    """What one `ContinuousBatchingEngine.step` did."""

    admitted: Tuple[int, ...]  # request ids that entered a slot (prefilled)
    retired: Tuple[int, ...]  # request ids completed this step
    tokens: Dict[int, int]  # rid -> token decoded this step
    version: int  # param version the decode ran under
    active: int  # slots occupied after the step


def _decode_fn(cfg: ModelConfig, window_override: int, params, last, cache,
               index, max_len: int):
    """One batched decode step over all slots; `index` is the per-slot [S]
    position vector. Idle slots decode garbage safely (their row is fully
    overwritten on the next admission) and their index is clamped so a long
    idle stretch can never scatter out of bounds."""
    logits, new_cache = registry.decode_step(params, cfg, last, cache, index,
                                             window_override=window_override)
    nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
    nxt = nxt[:, None].astype(jnp.int32)
    return new_cache, nxt, jnp.minimum(index + 1, max_len - 1)


def _insert_fn(cache, pcache, slot):
    """Scatter a batch=1 prefilled cache into slot `slot` of the pooled
    cache. "layers" leaves are super-block-stacked [n_rep, B, ...] (batch at
    axis 1); "tail" leaves are [B, ...] (axis 0). `slot` is a traced scalar,
    so every slot shares one executable."""

    def put(axis):
        def f(dst, src):
            return jax.lax.dynamic_update_index_in_dim(
                dst, jnp.squeeze(src, axis).astype(dst.dtype), slot, axis)
        return f

    return {"layers": jax.tree.map(put(1), cache["layers"], pcache["layers"]),
            "tail": jax.tree.map(put(0), cache["tail"], pcache["tail"])}


class ContinuousBatchingEngine:
    """Slot-based continuous-batching decode loop with hot weight swaps.

    * A fixed pool of `slots` KV-cache rows; `submit` enqueues a request and
      `step` admits queued requests into free slots (prefill-on-admit: a
      batch=1 prefill compiled per prompt length, its cache row scattered
      into the slot), decodes ONE token for every occupied slot in a single
      batched call, and retires requests that hit `max_new`.
    * `swap_params` installs a newly published param version BETWEEN decode
      steps. The decode step takes params as a traced jit argument, so a
      swap is a host-side reference assignment: zero retrace, zero in-flight
      request loss — slots keep their cache rows and continue under the new
      weights at the next step.
    * Greedy decode only (the benchmark/contract path); recurrent families
      (rglru/ssm) ride the same cache plumbing since their state is
      positionless. Encoder-decoder families are not supported.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 128, dtype=jnp.float32,
                 window_override: int = 0, version: int = 0):
        if cfg.is_encdec:
            raise NotImplementedError(
                "continuous batching is decoder-only; encoder-decoder "
                "families still use the static `generate` path")
        if slots < 1 or max_len < 2:
            raise ValueError(f"bad pool: slots={slots} max_len={max_len}")
        self.cfg = cfg
        self.params = params
        self.version = int(version)
        self.slots = slots
        self.max_len = max_len
        self._dtype = dtype
        self._wo = window_override
        self.cache = registry.init_cache(cfg, slots, max_len, dtype,
                                         window_override=window_override)
        self.index = jnp.zeros((slots,), jnp.int32)
        self.last = jnp.zeros((slots, 1), jnp.int32)
        self._free: List[int] = list(range(slots))[::-1]
        self._active: Dict[int, Request] = {}  # slot -> request
        self._queue: deque = deque()
        self._done: Dict[int, Request] = {}
        self._next_rid = 0
        self.decode_steps = 0
        self.swaps = 0
        self._decode = jax.jit(partial(_decode_fn, cfg, window_override),
                               static_argnames=("max_len",))
        self._insert = jax.jit(_insert_fn)
        self._prefills: Dict[int, Any] = {}  # prompt_len -> jitted prefill

    # ------------------------------------------------------------- interface

    def submit(self, prompt, max_new: int) -> int:
        """Enqueue a generation request. `prompt`: [L] int token ids with
        0 < L, L + max_new <= max_len. Returns the request id."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new < 1 or prompt.size + max_new > self.max_len:
            raise ValueError(f"prompt_len={prompt.size} + max_new={max_new} "
                             f"exceeds max_len={self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, max_new)
        req.submitted_step = self.decode_steps
        self._queue.append(req)
        return rid

    def swap_params(self, params, version: Optional[int] = None) -> int:
        """Install new weights between decode steps (never mid-step: `step`
        reads `self.params` exactly once). Versions must be monotone."""
        new_v = self.version + 1 if version is None else int(version)
        if new_v <= self.version:
            raise ValueError(f"non-monotone param version: "
                             f"{self.version} -> {new_v}")
        self.params = params
        self.version = new_v
        self.swaps += 1
        return new_v

    def poll(self, publisher) -> bool:
        """Adopt the publisher's current snapshot if it is newer than the
        engine's installed version. Returns True on a swap."""
        snap = publisher.snapshot()
        if snap is None or snap.version <= self.version:
            return False
        self.swap_params(snap.params, snap.version)
        return True

    @property
    def n_active(self) -> int:
        return len(self._active)

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    def result(self, rid: int) -> Optional[Request]:
        """The completed request (None while queued or in flight)."""
        return self._done.get(rid)

    # ----------------------------------------------------------- decode loop

    def _prefill_fn(self, L: int):
        fn = self._prefills.get(L)
        if fn is None:
            cfg, wo, dtype, max_len = self.cfg, self._wo, self._dtype, self.max_len

            def f(params, tokens):
                c = registry.init_cache(cfg, 1, max_len, dtype,
                                        window_override=wo)
                logits, cache = registry.prefill(params, cfg,
                                                 {"tokens": tokens}, c,
                                                 window_override=wo)
                nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
                return cache, nxt.astype(jnp.int32)

            fn = jax.jit(f)
            self._prefills[L] = fn
        return fn

    def _admit(self) -> List[int]:
        admitted = []
        while self._free and self._queue:
            req = self._queue.popleft()
            slot = self._free.pop()
            L = int(req.prompt.size)
            pcache, nxt = self._prefill_fn(L)(self.params,
                                              jnp.asarray(req.prompt)[None])
            self.cache = self._insert(self.cache, pcache,
                                      jnp.asarray(slot, jnp.int32))
            self.index = self.index.at[slot].set(L)
            self.last = self.last.at[slot].set(nxt)
            req.slot = slot
            # prefill emits the first generated token
            req.tokens.append(int(nxt[0]))
            req.versions.append(self.version)
            self._active[slot] = req
            admitted.append(req.rid)
        return admitted

    def _retire(self) -> List[int]:
        retired = []
        for slot, req in list(self._active.items()):
            if len(req.tokens) >= req.max_new:
                req.finished_step = self.decode_steps
                req.slot = None
                self._done[req.rid] = req
                del self._active[slot]
                self._free.append(slot)
                # park the freed slot at position 0; its row is garbage until
                # the next admission fully overwrites it
                self.index = self.index.at[slot].set(0)
                self.last = self.last.at[slot].set(0)
                retired.append(req.rid)
        return retired

    def step(self) -> StepEvents:
        """One engine iteration: retire finished requests, admit from the
        queue, then decode one token for every occupied slot (a single
        batched call under the currently installed params)."""
        retired = self._retire()
        admitted = self._admit()
        # a request whose max_new == 1 completes on its prefill token
        retired += self._retire()
        toks: Dict[int, int] = {}
        if self._active:
            self.cache, self.last, self.index = self._decode(
                self.params, self.last, self.cache, self.index,
                max_len=self.max_len)
            self.decode_steps += 1
            out = np.asarray(self.last)  # the per-step host sync point
            for slot, req in self._active.items():
                tok = int(out[slot, 0])
                req.tokens.append(tok)
                req.versions.append(self.version)
                toks[req.rid] = tok
        return StepEvents(tuple(admitted), tuple(retired), toks,
                          self.version, len(self._active))

    def drain(self, max_steps: int = 10_000) -> None:
        """Step until queue and slots are empty (tests / end-of-benchmark)."""
        for _ in range(max_steps):
            if not self._active and not self._queue:
                return
            self.step()
        raise RuntimeError("drain did not converge")
