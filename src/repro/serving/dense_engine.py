"""Dense-cache serving engine (the pre-paged seed design), kept as

  * the fallback for recurrent architectures (mamba/xlstm blocks carry O(1)
    state, not paged KV — chunked prefill of padded prompts would push pad
    tokens through their state update);
  * the baseline the paged engine's parity tests and BENCH_serving.json
    benchmarks compare against.

Design (seed): `slots` decode lanes over a dense `(slots, max_len)` cache;
finished lanes are refilled from the queue by re-running whole-prompt
prefill (one jit trace PER DISTINCT PROMPT LENGTH) and decode runs one call
per distinct lane position — both the bursty anti-patterns the paged engine
(`serving.engine.ServingEngine`) removes.

Per-step token counts are recorded into `self.metrics` so the serving
benchmark can report this engine's burstiness (tokens/step CoV) next to the
paged engine's flat schedule.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.schedule import tokens_per_step_cov
from repro.models import transformer as tf
from repro.obs import make_telemetry
from repro.obs.ledger import BandwidthLedger
from repro.obs.trace import (PID_REQUESTS, PID_SERVING, TID_ENGINE,
                             TID_LANE0, annotate_serving_tracks, phase)
from repro.serving.engine import DRAWN_ID_BYTES, ServeConfig, sample_token

Pytree = Any


@dataclasses.dataclass
class _Lane:
    request_id: int | None = None
    pos: int = 0
    remaining: int = 0
    tokens: list = dataclasses.field(default_factory=list)


class DenseServingEngine:
    def __init__(self, cfg: ModelConfig, params: Pytree, serve: ServeConfig):
        if serve.dense_kernel is not None:
            cfg = cfg.with_(dense_kernel=serve.dense_kernel)
        self.cfg = cfg
        self.params = params
        self.serve = serve
        self.lanes = [_Lane() for _ in range(serve.slots)]
        self._queue: list[tuple[int, np.ndarray, int]] = []
        self._results: dict[int, list[int]] = {}
        self._next_id = 0
        # same telemetry handle + typed step ledger as the paged engine, so
        # both engines emit the one shared schema (obs.ledger.STEP_SCHEMA)
        obs_on = serve.obs if serve.obs is not None else cfg.obs
        self.obs = make_telemetry(
            obs_on,
            trace_capacity=serve.trace_capacity or cfg.obs_trace_capacity)
        annotate_serving_tracks(self.obs.trace, serve.slots)
        self.metrics = BandwidthLedger(retention=(
            serve.metrics_retention if serve.metrics_retention is not None
            else cfg.metrics_retention))
        self._param_bytes = cfg.active_params() * cfg.jdtype.itemsize
        self._programs = 0             # model programs launched this step
        self._d2h = 0                  # device -> host bytes this step
        self._kv_token_bytes = 0       # measured from the first prefill's
        #                                materialized cache (recurrent state
        #                                amortized over max_len)
        self.trace_counts = {"prefill": 0, "decode": 0}

        def _prefill_one(params, tokens):
            self.trace_counts["prefill"] += 1
            batch = {"tokens": tokens}
            return tf.prefill(params, cfg, batch, max_len=serve.max_len)

        def _decode(params, toks, caches, pos_scalar):
            self.trace_counts["decode"] += 1
            return tf.decode_step(params, cfg, toks, caches, pos_scalar)

        self._prefill = jax.jit(_prefill_one)
        self._decode = jax.jit(_decode)
        self.caches = None

    # ---------------------------------------------------------------- API
    def submit(self, prompt: list[int], max_new_tokens: int = 32) -> int:
        rid = self._next_id
        self._next_id += 1
        self._queue.append((rid, np.asarray(prompt, np.int32), max_new_tokens))
        self.obs.requests.on_submit(rid)
        if self.obs.enabled:
            self.obs.trace.async_begin(
                f"req {rid}", rid, pid=PID_REQUESTS,
                args={"prompt_tokens": len(prompt),
                      "max_new": max_new_tokens})
        return rid

    def _finish(self, rid: int, tokens: "list[int]") -> None:
        self._results[rid] = tokens
        self.obs.requests.on_finish(rid, len(tokens))
        if self.obs.enabled:
            self.obs.trace.async_end(f"req {rid}", rid, pid=PID_REQUESTS,
                                     args={"tokens": len(tokens)})

    def result(self, rid: int) -> list[int] | None:
        return self._results.get(rid)

    @property
    def pending(self) -> int:
        return len(self._queue) + sum(1 for l in self.lanes if l.request_id is not None)

    def flatness_cov(self) -> float:
        """Coefficient of variation of tokens/step (prefill bursts make the
        dense engine's high; the paged engine's is the flat comparison)."""
        return tokens_per_step_cov([m["tokens"] for m in self.metrics])

    # ------------------------------------------------------------ engine
    def _sample(self, rid: int, token_idx: int, logits_row) -> int:
        """Read one lane's logits back (counted into the step's
        `d2h_bytes`) and sample the next token from them."""
        self._d2h += logits_row.nbytes
        row = np.asarray(logits_row, np.float32)
        if self.serve.temperature > 0.0:
            self._d2h += DRAWN_ID_BYTES
        return sample_token(self.serve, rid, token_idx, row)

    def _admit(self) -> int:
        """Fill idle lanes from the queue (continuous batching).  Returns
        prefill tokens processed (whole prompts — the bursty phase)."""
        prefill_tokens = 0
        for i, lane in enumerate(self.lanes):
            if lane.request_id is not None or not self._queue:
                continue
            rid, prompt, max_new = self._queue.pop(0)
            with phase(self.obs.trace, "serve.prefill.launch",
                       tid=TID_LANE0 + i, rid=rid, rows=len(prompt)):
                logits, caches = self._prefill(self.params, prompt[None, :])
            self._programs += 1
            prefill_tokens += len(prompt)
            first = self._sample(rid, 0, logits[0, -1])
            self.obs.requests.on_first_token(rid)
            if max_new <= 1 or (self.serve.eos_token is not None
                                and first == self.serve.eos_token):
                # finished on the prefill-sampled token: never occupies a
                # lane (matches the paged engine's _maybe_finish semantics)
                self._finish(rid, [first])
                continue
            # batch dim is 1 for stacked ("blocks") cache leaves, 0 otherwise
            def bdim(path):
                return 1 if tf.is_stacked_cache_path(path) else 0
            if self.caches is None:
                # materialize an empty slot-pool cache from this prototype
                def pool(path, c):
                    d = bdim(path)
                    shape = list(c.shape)
                    shape[d] = self.serve.slots
                    return jnp.zeros(shape, c.dtype)
                self.caches = jax.tree_util.tree_map_with_path(pool, caches)
                # per-token cache bytes for the ledger, measured from the
                # one-lane prototype (length-independent recurrent state is
                # amortized over the max_len the cache was sized for)
                self._kv_token_bytes = sum(
                    int(np.prod(c.shape)) * c.dtype.itemsize
                    for c in jax.tree_util.tree_leaves(caches)
                ) // self.serve.max_len
            # write this lane's cache slice
            def write(path, pool, c):
                return jax.lax.dynamic_update_slice_in_dim(pool, c, i, bdim(path))
            self.caches = jax.tree_util.tree_map_with_path(
                write, self.caches, caches)
            lane.request_id = rid
            lane.pos = len(prompt)
            lane.remaining = max_new - 1
            lane.tokens = [first]
        return prefill_tokens

    def _record_step(self, step_t0: float, prefill_tokens: int,
                     decode_tokens: int, read_tokens: int) -> None:
        """One shared-schema ledger row (obs.ledger.STEP_SCHEMA — identical
        keys to the paged engine; paged-only fields stay at the schema's
        zero defaults: this engine never shares KV, never speculates, and
        has no block pool).  Byte components are real, not parity zeros:
        weights stream once per model program (each prefill, each decode
        call of a position group), processed tokens write cache state,
        reads cover each participant's visible context."""
        tokens = prefill_tokens + decode_tokens
        programs, self._programs = self._programs, 0
        d2h, self._d2h = self._d2h, 0
        row = self.metrics.record(
            tokens=tokens,
            prefill_tokens=prefill_tokens,
            # dense prefill is never padded: real == scheduled
            prefill_real_tokens=prefill_tokens,
            decode_tokens=decode_tokens,
            queue_depth=len(self._queue),
            param_bytes=programs * self._param_bytes,
            kv_write_bytes=tokens * self._kv_token_bytes,
            kv_read_bytes=read_tokens * self._kv_token_bytes,
            d2h_bytes=d2h,
            step_wall_us=(self.obs.now_us() - step_t0
                          if self.obs.enabled else 0.0),
        )
        if self.obs.enabled:
            self.obs.trace.complete(
                "step", step_t0, row["step_wall_us"], pid=PID_SERVING,
                tid=TID_ENGINE, cat="step",
                args={"step": row["step"], "tokens": tokens,
                      "hbm_bytes": row["hbm_bytes"]})

    def step(self):
        """One batched decode step across all active lanes."""
        obs = self.obs
        step_t0 = obs.now_us() if obs.enabled else 0.0
        prefill_tokens = self._admit()
        active = [l for l in self.lanes if l.request_id is not None]
        if not active:
            if prefill_tokens:
                # every admitted request finished on its prefill-sampled
                # token (max_new=1 / instant eos): still record the burst,
                # or flatness_cov() under-reports exactly the spikes this
                # engine is the baseline for
                self._record_step(step_t0, prefill_tokens, 0,
                                  prefill_tokens)
                return True
            return False
        toks = np.zeros((self.serve.slots, 1), np.int32)
        for i, lane in enumerate(self.lanes):
            if lane.request_id is not None and lane.tokens:
                toks[i, 0] = lane.tokens[-1]
        # single shared pos isn't valid for heterogeneous lanes, so we run
        # one decode call per unique pos group — and merge back ONLY the
        # group's cache rows: decode_step writes KV at `pos` (and advances
        # recurrent state) for EVERY batch row, which would clobber
        # out-of-group lanes' history at that position.  (The paged engine
        # avoids all of this with per-lane position vectors.)
        pos_groups: dict[int, list[int]] = {}
        for i, lane in enumerate(self.lanes):
            if lane.request_id is not None:
                pos_groups.setdefault(lane.pos, []).append(i)
        decode_tokens = 0
        read_tokens = prefill_tokens   # prefill self-attends its context
        for pos, lanes_at in pos_groups.items():
            with phase(obs.trace, "serve.decode.launch", pos=pos,
                       lanes=len(lanes_at)):
                logits, new_caches = self._decode(
                    self.params, jnp.asarray(toks), self.caches, pos)
            self._programs += 1
            in_group = np.zeros((self.serve.slots,), bool)
            in_group[lanes_at] = True

            def merge(path, old, new):
                d = 1 if tf.is_stacked_cache_path(path) else 0
                mask = jnp.asarray(in_group).reshape(
                    (1,) * d + (-1,) + (1,) * (old.ndim - d - 1))
                return jnp.where(mask, new, old)

            self.caches = jax.tree_util.tree_map_with_path(
                merge, self.caches, new_caches)
            for i in lanes_at:
                lane = self.lanes[i]
                nxt = self._sample(lane.request_id, len(lane.tokens),
                                   logits[i, -1])
                lane.tokens.append(nxt)
                lane.pos += 1
                lane.remaining -= 1
                decode_tokens += 1
                read_tokens += lane.pos
                done = lane.remaining <= 0 or (
                    self.serve.eos_token is not None and nxt == self.serve.eos_token)
                if done:
                    self._finish(lane.request_id, lane.tokens)
                    self.lanes[i] = _Lane()
        self._record_step(step_t0, prefill_tokens, decode_tokens,
                          read_tokens)
        return True

    def run(self, max_steps: int = 10_000):
        steps = 0
        while self.pending and steps < max_steps:
            self.step()
            steps += 1
        return self._results
