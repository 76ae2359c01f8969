"""Batched serving engine: continuous batching over a fixed-slot KV cache.

Slots hold independent sequences; ``step`` decodes one token for every
active slot with a single jit'd serve_step. Finished slots are refilled from the request queue via per-slot
prefill; greedy or temperature sampling.

Sparse side-channel workloads (retrieval adapters, graph features, MoE
routing tables) go through :meth:`ServeEngine.spmm`, which resolves the
schedule from the persistent tuner cache (``repro.tune``) — tuning
happens ahead of time via :meth:`ServeEngine.prepare_sparse` (or
``repro.tune.tune_schedule``); the request path itself *never* runs a
measurement.
"""
from __future__ import annotations

import dataclasses
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 16


class ServeEngine:
    def __init__(self, api, params, *, slots: int = 4, max_len: int = 128,
                 temperature: float = 0.0, seed: int = 0,
                 tuner_cache=None):
        self.api = api
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self.queue: deque[Request] = deque()
        self.active: dict[int, dict] = {}  # slot -> {rid, remaining, out}
        self.cache = api.init_cache(slots, max_len)
        self._decode = jax.jit(api.decode_step)
        self.results: dict[int, list[int]] = {}
        self._next_tokens = np.zeros((slots,), np.int32)
        # repro.tune.ScheduleCache (None -> the process default cache);
        # consulted by the sparse side-channel path below.  The memo maps
        # fingerprint cache keys -> tuned Schedule, so it survives operand
        # re-creation and never aliases two different matrices (ids can be
        # reused after GC; fingerprints cannot collide that way).
        self.tuner_cache = tuner_cache
        self._sched_memo: dict[str, object] = {}

    def submit(self, req: Request):
        self.queue.append(req)

    # -- tuned sparse side-channel ----------------------------------------

    def prepare_sparse(self, csr, n_dense_cols: int, *,
                       value_dtypes=None, error_budget=None):
        """Ahead-of-time tuning for a sparse operand this engine will
        serve with: measures (or replays the fingerprint cache) and
        persists the winner, so :meth:`spmm` replays it for free.

        ``value_dtypes`` / ``error_budget`` forward to
        :func:`~repro.tune.tune_schedule`'s dtype axis (DESIGN.md §13):
        pass ``value_dtypes=()`` to pin f32 storage for a
        parity-critical serving path, or a tighter ``error_budget``
        than the tuner's 5% default."""
        from ..tune import cache_key, tune_schedule

        kw = {}
        if value_dtypes is not None:
            kw["value_dtypes"] = value_dtypes
        if error_budget is not None:
            kw["error_budget"] = error_budget
        sched = tune_schedule(csr, n_dense_cols,
                              cache=self.tuner_cache, **kw).schedule
        self._sched_memo[cache_key(csr, n_dense_cols)] = sched
        return sched

    def prepare_dist(self, csr, n_dense_cols: int, *, mesh, axis: str,
                     value_dtypes=None):
        """Ahead-of-time tuning for a *sharded* sparse operand: one
        joint search over local tiling × collective mode × value dtype
        (:func:`~repro.tune.tune_dist_spmm` on the §14 driver), persisted
        under the mesh-extent-suffixed key so
        ``dist_spmm(..., schedule="tune")`` replays it for free on the
        serving path.  ``value_dtypes=()`` pins f32 storage."""
        from ..tune import cache_key, tune_dist_spmm

        kw = {}
        if value_dtypes is not None:
            kw["value_dtypes"] = value_dtypes
        res = tune_dist_spmm(csr, n_dense_cols, mesh=mesh, axis=axis,
                             cache=self.tuner_cache, **kw)
        axis_size = int(mesh.shape[axis])
        self._sched_memo[
            f"dist:{cache_key(csr, n_dense_cols)}|mesh:{axis_size}"
        ] = res.schedule
        return res.schedule

    def prepare_moe(self, cfg, t_tokens: int, expert_lengths=None):
        """Ahead-of-time tuning of the MoE dispatch this engine will run:
        measures (or replays the per-backend cache) the token-tile ×
        capacity × (f_tile, d_tile) space for this config's expert
        histogram, so :meth:`moe_dispatch_schedule` replays it for free."""
        from ..models.moe import moe_tune_dispatch

        res = moe_tune_dispatch(cfg, t_tokens,
                                expert_lengths=expert_lengths,
                                cache=self.tuner_cache)
        self._sched_memo[res.key] = res.schedule
        return res.schedule

    def moe_dispatch_schedule(self, cfg, t_tokens: int,
                              expert_lengths=None):
        """Serving-path resolver for ``apply_moe(..., dispatch=...)``:
        per-engine memo, then the persistent per-backend cache, else the
        config's static default — never an inline measurement."""
        import numpy as np

        from ..models.moe import balanced_expert_lengths, moe_dispatch_schedule
        from ..tune.moe import moe_cache_key

        observed = expert_lengths is not None
        lengths = np.asarray(expert_lengths if observed
                             else balanced_expert_lengths(cfg, t_tokens))
        # same keying as moe_tune_dispatch: assumed histograms resolve
        # the no-shrink record only
        key = moe_cache_key(lengths, cfg.d_model, cfg.moe_d_ff,
                            str(cfg.param_dtype), shrink=observed,
                            max_tokens=t_tokens)
        sched = self._sched_memo.get(key)
        if sched is None:
            sched = moe_dispatch_schedule(cfg, t_tokens,
                                          expert_lengths=expert_lengths,
                                          cache=self.tuner_cache)
        return sched

    def spmm(self, a, b):
        """Serving-path SpMM: schedule comes from the per-engine memo,
        then the persistent tuner cache, else the static selector —
        never from an inline measurement (requests must not stall on a
        tuning run).  Cache misses are not memoized, so tuning done
        later (``tune_schedule``, another engine's ``prepare_sparse``)
        is picked up on the next call.  Non-CSR operands have no
        fingerprint; they fall through to the library default, matching
        ``repro.sparse.spmm(..., schedule="auto")``."""
        from ..sparse import spmm as _spmm
        from ..sparse.formats import CSR
        from ..tune import cache_key, cached_or_auto

        if not isinstance(a, CSR):
            return _spmm(a, b, schedule="auto")
        key = cache_key(a, int(b.shape[1]))  # memoized on the CSR
        sched = self._sched_memo.get(key)
        if sched is None:
            sched = cached_or_auto(a, int(b.shape[1]),
                                   cache=self.tuner_cache, key=key)
        return _spmm(a, b, schedule=sched)

    def _slot_prefill(self, slot: int, req: Request):
        """Prefill one slot: run the prompt batched-by-1 and splice the
        per-slot KV into the shared cache."""
        batch = {"tokens": jnp.asarray(req.prompt[None, :], jnp.int32)}
        logits, cache1 = self.api.prefill(self.params, batch, self.max_len)

        def splice(full, one):
            if one.ndim >= 2 and one.shape[1] == 1:  # (L, 1, ...) slot axis
                return jax.lax.dynamic_update_slice_in_dim(
                    full, one.astype(full.dtype), slot, axis=1)
            return full

        self.cache = jax.tree.map(splice, self.cache, cache1)
        # NOTE: per-slot positions require a vector 'pos'; this engine uses
        # synchronized-length prompts per wave (documented limitation).
        self.cache["pos"] = cache1["pos"]
        tok = int(jnp.argmax(logits[0]))
        self.active[slot] = {"rid": req.rid,
                             "remaining": req.max_new_tokens - 1,
                             "out": [tok]}
        self._next_tokens[slot] = tok

    def _fill_slots(self):
        for slot in range(self.slots):
            if slot not in self.active and self.queue:
                self._slot_prefill(slot, self.queue.popleft())

    def _sample(self, logits):
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.key, sub = jax.random.split(self.key)
        return jax.random.categorical(
            sub, logits / self.temperature, axis=-1).astype(jnp.int32)

    def step(self):
        """One decode wave across all active slots."""
        self._fill_slots()
        if not self.active:
            return False
        toks = jnp.asarray(self._next_tokens)
        logits, self.cache = self._decode(self.params, self.cache, toks)
        nxt = np.asarray(self._sample(logits))
        for slot, st in list(self.active.items()):
            tok = int(nxt[slot])
            st["out"].append(tok)
            st["remaining"] -= 1
            self._next_tokens[slot] = tok
            if st["remaining"] <= 0:
                self.results[st["rid"]] = st["out"]
                del self.active[slot]
        return True

    def run_to_completion(self, max_steps: int = 1000):
        steps = 0
        while (self.queue or self.active) and steps < max_steps:
            self.step()
            steps += 1
        return self.results
