"""Paged-KV serving engine: chunked prefill + continuous batching decode.

Composes `serving.cache.GroupedPagedCache` (fixed-size KV blocks shared
across lanes, one block table per layer group — global vs sliding-window
reach) with `serving.scheduler.ChunkedPrefillScheduler` (FCFS + preemption-
by-block-pressure, prefill split into fixed chunks and interleaved with
decode — the generalized-ping-pong schedule applied to the request stream,
so per-step token count and HBM traffic stay flat), and optionally
`serving.prefix.PrefixCache` (radix-tree shared-prefix KV reuse: admission
maps previously computed prompt-prefix blocks straight into the lane's
tables and prefill skips those chunks entirely — the redundant re-prefill
bytes never cross HBM).

Exactly THREE step shapes are jit-compiled, independent of prompt lengths,
draft lengths, and acceptance patterns:

  * `prefill_chunk`: (1, chunk) tokens — one chunk of one lane's (padded)
    prompt, scattering per-token KV writes through the lane's block tables
    (per-token because a prefix-cache hit may start a chunk mid-block);
  * `decode_step_paged`: (slots, 1) tokens with PER-LANE position vectors —
    heterogeneous lanes decode in one call (the seed engine ran one call per
    distinct position and re-traced per prompt length);
  * `verify_step_paged`: (slots, draft_len+1) tokens — the speculative-
    decoding verify burst (ServeConfig.speculation): drafts mined from the
    request's own history / the prefix radix tree (`ngram_propose` /
    `PrefixCache.suffix_lookup` — no weights streamed to draft) or from an
    optional small draft model are scored in ONE forward pass, so the
    streamed weight working set is amortized over up to draft_len+1 tokens
    per lane instead of 1.  Per-lane shorter drafts are masked by an
    `nvalid` vector (spare rows write null block 0), so one shape covers
    every acceptance pattern; steps where no lane drafted use the plain
    decode shape.  Rejected drafts roll back by block-table truncation
    (`GroupedPagedCache.truncate_blocks`) — stale pool rows are hidden by
    the position-exact masks and overwritten in place, the prefill-pad
    argument again.  Accepted-token bursts stay under the scheduler's flat
    token budget (`core.schedule.plan_verify_budget`), and the output
    stream is token-for-token identical with speculation on or off.

Sampling is deterministic: greedy by default; with temperature > 0 every
token draw uses a key folded from (ServeConfig.seed, request id, token
index), so identical request streams reproduce identical outputs regardless
of lane assignment, step interleaving, preemption/resume, or prefix-cache
hits.

Per-step metrics (tokens, blocks in use/shared, prefix hit tokens, queue
depth, projected HBM bytes, device -> host bytes) accumulate in
`engine.metrics`; `benchmarks/run.py` records them into BENCH_serving.json.
Each step runs inside a `serve.step` profiler span numbered like its ledger
row, with a child span per stage (`serve.schedule`, `serve.copy`,
`serve.{prefill,decode,verify}.{launch,fetch}`, `serve.sample`,
`serve.account`; a `*.dispatch` span inside each launch covers the jitted
call alone), so a `jax.profiler` trace puts the device's idle time inside
a step down to what the host was doing.

Recurrent architectures (mamba/xlstm blocks: O(1) state, no paged KV) are
served by `serving.dense_engine.DenseServingEngine` — see `make_engine`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.schedule import plan_serve_chunk, round_up, tokens_per_step_cov
from repro.models import transformer as tf
from repro.obs import make_telemetry
from repro.obs.ledger import BandwidthLedger
from repro.obs.trace import (PID_REQUESTS, PID_SERVING, TID_ENGINE,
                             TID_LANE0, annotate_serving_tracks, phase)
from repro.serving.cache import GroupedPagedCache, PagedKVCache
from repro.serving.prefix import PrefixCache, ngram_propose
from repro.serving.scheduler import ChunkedPrefillScheduler, Request

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    slots: int = 4                 # concurrent decode lanes
    max_len: int = 256             # max tokens per sequence (table capacity)
    temperature: float = 0.0       # 0 => greedy
    eos_token: int | None = None
    dense_kernel: str | None = None  # override cfg.dense_kernel at serve time;
                                     # threads through prefill AND decode, so
                                     # "kernel" streams every projection
                                     # through the GPP Pallas matmul
    paged_attn_kernel: str | None = None  # override cfg.paged_attn_kernel:
                                     # auto | pallas | interpret | ref — the
                                     # paged READ path ("pallas" streams KV
                                     # blocks through the VMEM-ring kernel
                                     # instead of gathering pools)
    seed: int = 0                  # PRNG root for temperature sampling;
                                   # per-token keys fold in (rid, token_idx)
    # paged-KV knobs (0 = derive from the ModelConfig serving defaults)
    block_size: int = 0            # tokens per KV block
    num_blocks: int = 0            # pool size PER LAYER GROUP incl. reserved
                                   # null block 0; 0 = slots*max_len worth
                                   # (the dense engine's footprint, now
                                   # SHARED across lanes)
    prefill_chunk: int = 0         # tokens per prefill chunk; 0 = planned by
                                   # core.schedule.plan_serve_chunk
    token_budget: int = 0          # flat per-step token target; 0 = cfg /
                                   # slots + 2 blocks
    # shared-prefix KV reuse (serving/prefix.py); None = cfg.prefix_cache /
    # cfg.prefix_cache_blocks
    prefix_cache: "bool | None" = None
    prefix_cache_blocks: "int | None" = None
    # speculative decoding (paged engine only); None/0 = cfg.speculation /
    # cfg.draft_len.  draft_source picks the proposal mechanism: "self" =
    # prompt-lookup n-grams over the lane's own history with a fallback to
    # the prefix radix tree's stored sequences (no extra weights streamed);
    # "model" = greedy rollout of a small draft model passed to the engine
    # (make_engine / ServingEngine `draft_model=(cfg, params)`), falling
    # back to "self" when none was provided.
    speculation: "bool | None" = None
    draft_len: int = 0
    draft_source: str = "self"
    # observability (repro.obs): request/phase trace spans + TTFT/TPOT
    # histograms + per-step wall times in the ledger.  None = cfg.obs;
    # trace_capacity 0 = cfg.obs_trace_capacity; metrics_retention None =
    # cfg.metrics_retention (ledger rows kept; 0 = unbounded).
    obs: "bool | None" = None
    trace_capacity: int = 0
    metrics_retention: "int | None" = None


@functools.partial(jax.jit, donate_argnums=(0,))
def _pool_copy(pool, src, dst):
    """One COW block copy in a flat (nb, bs, ...) pool.  src/dst are traced
    scalars, so every pool shape compiles exactly once per process; the
    pool buffer is DONATED (the engine rebinds self.caches immediately), so
    on accelerators this lowers to an in-place one-block update instead of
    materializing a whole new pool per copy."""
    return pool.at[dst].set(pool[src])


@functools.partial(jax.jit, donate_argnums=(0,))
def _pool_copy_stacked(pool, src, dst):
    """Same for stacked (S, nb, bs, ...) superblock pools."""
    return pool.at[:, dst].set(pool[:, src])


# `sample_token` reads one drawn int32 id back per token above temperature 0
DRAWN_ID_BYTES = 4


def sample_token(serve: ServeConfig, rid: int, token_idx: int,
                 logits_row) -> int:
    """Deterministic sampling shared by both engines: greedy at
    temperature 0, otherwise a categorical draw keyed on
    (serve.seed, rid, token_idx) — no shared/implicit PRNG state, so
    identical request streams reproduce identical outputs regardless of
    lane assignment, batching, or preemption/resume."""
    if serve.temperature <= 0.0:
        return int(np.argmax(np.asarray(logits_row, np.float32)))
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(serve.seed), rid), token_idx)
    scaled = jnp.asarray(logits_row, jnp.float32) / serve.temperature
    return int(jax.random.categorical(key, scaled))


class ServingEngine:
    """Paged-KV continuous-batching engine (see module docstring)."""

    def __init__(self, cfg: ModelConfig, params: Pytree, serve: ServeConfig,
                 draft_model: "tuple[ModelConfig, Pytree] | None" = None):
        if serve.dense_kernel is not None:
            cfg = cfg.with_(dense_kernel=serve.dense_kernel)
        if serve.paged_attn_kernel is not None:
            cfg = cfg.with_(paged_attn_kernel=serve.paged_attn_kernel)
        if not tf.supports_paged(cfg):
            raise ValueError(
                f"{cfg.name} has recurrent/cross blocks; paged serving "
                "covers attention-cache models — use DenseServingEngine "
                "(serving.make_engine picks automatically)")
        self.cfg = cfg
        self.params = params
        self.serve = serve

        bs = serve.block_size or cfg.serve_block_size
        max_len = round_up(serve.max_len, bs)
        mb = max_len // bs
        budget = serve.token_budget or cfg.serve_token_budget \
            or (serve.slots + 2 * bs)
        chunk = serve.prefill_chunk or plan_serve_chunk(
            token_budget=budget, decode_lanes=serve.slots, block_size=bs)
        num_blocks = serve.num_blocks or serve.slots * mb + 1
        self.block_size = bs
        self.chunk = chunk
        self.token_budget = budget

        # layers bucketed by attention reach: one block table + block-id
        # space per group, so `release_expired` frees a windowed group's
        # blocks while a global group keeps full history
        self.group_keys = tf.layer_group_keys(cfg)
        self.group_horizons = tf.group_horizons(cfg)
        self.kv = GroupedPagedCache(
            slots=serve.slots, num_blocks=num_blocks, block_size=bs,
            max_blocks_per_seq=mb, horizons=self.group_horizons)

        prefix_on = (serve.prefix_cache if serve.prefix_cache is not None
                     else cfg.prefix_cache)
        prefix_blocks = (serve.prefix_cache_blocks
                         if serve.prefix_cache_blocks is not None
                         else cfg.prefix_cache_blocks)
        self.prefix = (PrefixCache(self.kv, max_blocks=prefix_blocks)
                       if prefix_on else None)

        # speculative decoding: drafts mined host-side (or by a small draft
        # model), verified in one batched (slots, draft_len+1) call
        spec_on = (serve.speculation if serve.speculation is not None
                   else cfg.speculation)
        self.draft_len = (serve.draft_len or cfg.draft_len) if spec_on else 0
        self.draft_source = serve.draft_source
        self._draft_cfg = self._draft_params = None
        if draft_model is not None and spec_on \
                and serve.draft_source == "model":
            self._draft_cfg, self._draft_params = draft_model
            self._draft_window = 16      # fixed (1, W) rollout shape: one
            #                              compile regardless of context len
            dcfg = self._draft_cfg

            def _draft_fwd(params, toks):
                return tf.forward(params, dcfg, {"tokens": toks})

            self._draft_fwd = jax.jit(_draft_fwd)

        # telemetry (repro.obs): disabled handle = one attribute check per
        # instrumentation site; enabled = trace spans + TTFT/TPOT histograms
        obs_on = serve.obs if serve.obs is not None else cfg.obs
        self.obs = make_telemetry(
            obs_on,
            trace_capacity=serve.trace_capacity or cfg.obs_trace_capacity)
        annotate_serving_tracks(self.obs.trace, serve.slots)
        self._d2h = 0            # device -> host bytes of the current step

        self.scheduler = ChunkedPrefillScheduler(
            self.kv, slots=serve.slots, chunk=chunk, prefix=self.prefix,
            draft_len=self.draft_len,
            draft_fn=self._draft_for if self.draft_len else None,
            token_budget=budget, trace=self.obs.trace)
        specs = tf.paged_cache_specs(cfg, num_blocks, bs)
        self.caches = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), specs)
        self._kv_token_bytes = self._kv_bytes_per_token(specs)
        # weight bytes each step program copies into padded kernel operands
        self._gpp_pad_bytes = {
            "prefill": tf.served_gpp_pad_bytes(cfg, chunk),
            "decode": tf.served_gpp_pad_bytes(cfg, serve.slots),
            "verify": (tf.served_gpp_pad_bytes(
                cfg, serve.slots * (self.draft_len + 1))
                if self.draft_len else 0)}
        self._group_token_bytes = self._kv_bytes_by_group(cfg, specs)
        self._param_bytes = cfg.active_params() * cfg.jdtype.itemsize
        # resolved paged-attention read path ("ref" gathers pools, else the
        # streaming kernel) — recorded so benchmarks can attribute bytes
        from repro.kernels.ops import resolve_paged_attn_mode
        self.paged_attn_mode = resolve_paged_attn_mode(cfg.paged_attn_kernel)
        # whole-model reclamation horizon kept for the all-window case and
        # back-compat introspection; per-group reclamation supersedes it
        self.window_horizon = tf.window_horizon(cfg)
        self._reclaims = any(h is not None for h in self.group_horizons)

        # trace_counts increments when jax TRACES (= compiles) a step fn —
        # the re-jit regression tests assert it stays at {1, 1, 1} across
        # arbitrary prompt-length / draft-length / acceptance mixes
        # ("verify" stays 0 with speculation off).
        self.trace_counts = {"prefill_chunk": 0, "decode": 0, "verify": 0}

        def _prefill(params, caches, toks, table_rows, start_pos, last_idx):
            self.trace_counts["prefill_chunk"] += 1
            return tf.prefill_chunk(params, cfg, toks, caches, table_rows,
                                    start_pos, last_idx)

        def _decode(params, caches, toks, tables, positions, active):
            self.trace_counts["decode"] += 1
            return tf.decode_step_paged(params, cfg, toks, caches, tables,
                                        positions, active)

        def _verify(params, caches, toks, tables, positions, active, nvalid):
            self.trace_counts["verify"] += 1
            return tf.verify_step_paged(params, cfg, toks, caches, tables,
                                        positions, active, nvalid)

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode)
        self._verify = jax.jit(_verify)

        self._results: dict[int, list[int]] = {}
        self._next_id = 0
        # the typed step ledger IS `metrics` (list-compatible: len / iter /
        # int+slice indexing), with optional bounded retention
        self.metrics = BandwidthLedger(retention=(
            serve.metrics_retention if serve.metrics_retention is not None
            else cfg.metrics_retention))

    @staticmethod
    def _kv_bytes_per_token(specs) -> int:
        """Per-token KV bytes across every attention layer (stacked block
        leaves carry a leading superblock dim before (nb, bs, ...))."""
        total = 0

        def leaf(path, s):
            nonlocal total
            stacked = tf.is_stacked_cache_path(path)
            per_slot = int(np.prod(s.shape[3:] if stacked else s.shape[2:]))
            layers = s.shape[0] if stacked else 1
            total += layers * per_slot * jnp.dtype(s.dtype).itemsize

        jax.tree_util.tree_map_with_path(leaf, specs)
        return total

    @staticmethod
    def _kv_bytes_by_group(cfg, specs) -> "list[int]":
        """Per-token KV bytes split by layer group (for per-group read
        accounting: a window group's live blocks differ from a global
        group's)."""
        out = [0] * len(tf.layer_group_keys(cfg))

        def leaf(path, s):
            stacked = tf.is_stacked_cache_path(path)
            per_slot = int(np.prod(s.shape[3:] if stacked else s.shape[2:]))
            layers = s.shape[0] if stacked else 1
            out[tf.cache_path_group(cfg, path)] += \
                layers * per_slot * jnp.dtype(s.dtype).itemsize

        jax.tree_util.tree_map_with_path(leaf, specs)
        return out

    # ---------------------------------------------------------------- API
    def submit(self, prompt: "list[int]", max_new_tokens: int = 32) -> int:
        rid = self._next_id
        self._next_id += 1
        self.scheduler.submit(Request(
            rid=rid, prompt=np.asarray(prompt, np.int32),
            max_new=max_new_tokens))
        self.obs.requests.on_submit(rid)
        if self.obs.enabled:
            self.obs.trace.async_begin(
                f"req {rid}", rid, pid=PID_REQUESTS,
                args={"prompt_tokens": len(prompt),
                      "max_new": max_new_tokens})
        return rid

    def result(self, rid: int) -> "list[int] | None":
        return self._results.get(rid)

    @property
    def pending(self) -> int:
        return self.scheduler.pending

    def flatness_cov(self) -> float:
        """Coefficient of variation of tokens/step (lower = flatter)."""
        return tokens_per_step_cov([m["tokens"] for m in self.metrics])

    def prefix_hit_rate(self) -> float:
        return self.prefix.hit_rate() if self.prefix else 0.0

    def acceptance_rate(self) -> float:
        """Accepted / drafted tokens over the engine's lifetime (0.0 with
        speculation off or nothing drafted yet).  Ledger totals, so the
        rate stays lifetime-exact under bounded metrics retention."""
        drafted = self.metrics.total("drafted_tokens")
        accepted = self.metrics.total("accepted_tokens")
        return accepted / drafted if drafted else 0.0

    # ------------------------------------------------------------ engine
    def _sample(self, logits_row, req: Request) -> int:
        if self.serve.temperature > 0.0:
            self._d2h += DRAWN_ID_BYTES
        return sample_token(self.serve, req.rid, len(req.produced), logits_row)

    # ---------------------------------------------------------- drafting
    def _draft_for(self, req: Request, cap: int) -> np.ndarray:
        """Scheduler hook: propose up to `cap` draft tokens for `req`.

        "self" drafting is pure token statistics over tokens the system has
        already seen — the lane's own prompt+produced history first
        (`ngram_propose`), then the prefix radix tree's stored sequences
        (`PrefixCache.suffix_lookup`, cross-request repetition) — so no
        weights are streamed to produce the guess.  "model" drafting rolls
        out the small draft model greedily.  Wrong drafts only cost their
        share of one verify pass; the acceptance loop keeps the emitted
        stream exact either way."""
        if cap < 1:
            return np.zeros((0,), np.int32)
        hist = np.concatenate(
            [req.prompt, np.asarray(req.produced, np.int32)])
        if self._draft_params is not None:
            d = self._draft_with_model(hist, cap)
        else:
            d = ngram_propose(hist, cap)
            if len(d) == 0 and self.prefix is not None:
                d = self.prefix.suffix_lookup(hist, cap)
        d = np.asarray(d, np.int32)[:cap]
        if len(d):
            # a draft model with a different vocab may propose ids the
            # target can't embed; clamp — a wrong guess is just rejected
            d = np.clip(d, 0, self.cfg.vocab_size - 1)
        return d

    def _draft_with_model(self, hist: np.ndarray, cap: int) -> np.ndarray:
        """Greedy draft-model rollout over a fixed (1, W) token window —
        one compiled shape regardless of context length; the rollout cost
        is the draft model's (small) weight stream, repaid when accepted
        tokens amortize the TARGET model's stream."""
        W = self._draft_window
        seq = [int(t) for t in hist[-W:]]
        out: "list[int]" = []
        for _ in range(cap):
            window = seq[-W:]
            n = len(window)
            toks = np.zeros((1, W), np.int32)
            toks[0, :n] = window
            logits = self._draft_fwd(self._draft_params, jnp.asarray(toks))
            t = int(np.argmax(np.asarray(logits[0, n - 1], np.float32)))
            out.append(t)
            seq.append(t)
        return np.asarray(out, np.int32)

    def _tables_jnp(self, lane: "int | None" = None):
        """Per-group block tables as a jit-stable tuple: the whole (slots,
        MB) table per group for decode, or one lane's (1, MB) row per group
        for a prefill chunk."""
        if lane is None:
            return tuple(jnp.asarray(g.tables) for g in self.kv.groups)
        return tuple(jnp.asarray(g.tables[lane][None])
                     for g in self.kv.groups)

    def _apply_pending_copies(self) -> None:
        """Drain queued copy-on-write block copies into the device pools —
        BEFORE any model call of this step, so forked blocks carry their
        source rows before the lane appends (and before a freed source id
        can be overwritten by this step's writes)."""
        if not self.kv.pending_copies:
            return
        per_group: "dict[int, list[tuple[int, int]]]" = {}
        for gi, src, dst in self.kv.pending_copies:
            per_group.setdefault(gi, []).append((src, dst))
        self.kv.pending_copies = []

        def apply(path, pool):
            copies = per_group.get(tf.cache_path_group(self.cfg, path))
            if not copies:
                return pool
            op = (_pool_copy_stacked if tf.is_stacked_cache_path(path)
                  else _pool_copy)
            for src, dst in copies:
                pool = op(pool, np.int32(src), np.int32(dst))
            return pool

        self.caches = jax.tree_util.tree_map_with_path(apply, self.caches)

    def _prefix_insert(self, lane: int, tokens: np.ndarray) -> None:
        """Index `tokens` (every position's KV is written for this lane)
        into the radix tree, adopting the lane's novel blocks.  Called when
        a lane's prefill completes (its context becomes shareable while it
        still decodes) and again at finish (context + generated tokens,
        for multi-turn reuse)."""
        if self.prefix is None or len(tokens) == 0:
            return
        n = -(-len(tokens) // self.block_size)
        self.prefix.insert(np.asarray(tokens, np.int32),
                           self.kv.table_snapshot(lane, n))

    def _maybe_finish(self, lane: int, tok: int) -> None:
        req = self.scheduler.request_at(lane)
        done = req.remaining <= 0 or (
            self.serve.eos_token is not None and tok == self.serve.eos_token)
        if done:
            if self.prefix is not None:
                # KV exists for every fed token: prompt + produced[:-1]
                # (the final sampled token was never fed back)
                fed = np.concatenate(
                    [req.prompt, np.asarray(req.produced[:-1], np.int32)])
                self._prefix_insert(lane, fed)
            self._results[req.rid] = list(req.produced)
            self.obs.requests.on_finish(req.rid, len(req.produced))
            if self.obs.enabled:
                self.obs.trace.async_end(
                    f"req {req.rid}", req.rid, pid=PID_REQUESTS,
                    args={"tokens": len(req.produced),
                          "preemptions": req.preemptions})
            self.scheduler.finish(lane)
            if self.prefix is not None:
                # the lane's refs just dropped: the block cap can now bite
                self.prefix.enforce_cap()

    def _live_blocks(self) -> np.ndarray:
        """(slots, groups): the physical blocks each lane maps in each
        layer group (`blocks_for`, counted for every lane at once) — what
        the streaming kernel reads for that lane."""
        mb = np.arange(self.kv.cfg.max_blocks_per_seq)
        return np.stack(
            [np.count_nonzero((g.tables != 0) & (mb < g.num_mapped[:, None]),
                              axis=1) for g in self.kv.groups], axis=1)

    def _fetch(self, x) -> np.ndarray:
        """Read `x` back to the host as float32, counting its bytes into
        the step's `d2h_bytes`."""
        self._d2h += x.nbytes
        return np.asarray(x, np.float32)

    def step(self) -> bool:
        """One engine step: at most one prefill chunk + one batched decode
        call over every decode-phase lane.  The step runs inside a
        `serve.step` profiler span numbered like its ledger row, with one
        child span per stage (`obs.trace.phase`)."""
        with jax.profiler.StepTraceAnnotation("serve.step",
                                              step_num=self.metrics.steps):
            return self._step()

    def _step(self) -> bool:
        obs = self.obs
        trace = obs.trace
        step_t0 = obs.now_us() if obs.enabled else 0.0
        with phase(trace, "serve.schedule"):
            plan = self.scheduler.schedule()
        if plan is None:
            if self.scheduler.pending:
                raise RuntimeError(
                    "paged pool cannot back even the oldest request "
                    f"({self.kv.cfg.num_blocks} blocks of {self.block_size}); "
                    "raise ServeConfig.num_blocks")
            return False
        # copy-on-write forks queued by admission: copy pool rows before
        # any write this step
        if self.kv.pending_copies:
            with phase(trace, "serve.copy"):
                self._apply_pending_copies()
        prefill_tokens = decode_tokens = 0
        verify_tokens = drafted_tokens = accepted_tokens = 0
        read_tokens = 0
        programs = 0            # model programs launched: weight streams
        gathered = 0            # lanes whose logical sequence a gather reads
        live = []               # live-block counts of each call's lanes
        moe_routed = moe_computed = 0
        gpp_pad = 0             # weight bytes padded for the kernels
        self._d2h = 0

        if plan.prefill:
            w = plan.prefill
            req = self.scheduler.request_at(w.lane)
            lane_tid = TID_LANE0 + w.lane
            with phase(trace, "serve.prefill.launch", tid=lane_tid,
                       rid=req.rid, rows=len(w.tokens)):
                # shared blocks arrive via the tables READ-ONLY; the write
                # span must be exclusively owned (fork_block upholds this
                # at admission — assert it before every write)
                self.kv.assert_writable(w.lane, w.start_pos,
                                        w.start_pos + len(w.tokens))
                inputs = (jnp.asarray(w.tokens[None]),
                          self._tables_jnp(w.lane))
                with phase(trace, "serve.prefill.dispatch", tid=lane_tid):
                    logits, self.caches = self._prefill(
                        self.params, self.caches, *inputs,
                        w.start_pos, w.last_idx)
                live.append(self._live_blocks()[w.lane])
            programs += 1
            gathered += 1
            prefill_tokens = len(w.tokens)
            moe_routed, moe_computed = tf.served_moe_rows(
                self.cfg, len(w.tokens), w.real_tokens)
            gpp_pad += self._gpp_pad_bytes["prefill"]
            read_tokens += w.start_pos + len(w.tokens)
            if w.final:
                with phase(trace, "serve.prefill.fetch", tid=lane_tid):
                    first = self._fetch(logits[0])
            if w.final or self._reclaims:
                with phase(trace, "serve.sample"):
                    if self._reclaims and w.real_tokens:
                        self.kv.release_expired(
                            w.lane, w.start_pos + w.real_tokens - 1)
                    if w.final:
                        tok = self._sample(first, req)
                        req.produced.append(tok)
                        obs.requests.on_first_token(req.rid)
                        # the lane's full context KV is now written: publish
                        # it for sharing while the lane keeps decoding
                        self._prefix_insert(w.lane, req.context)
                        self.scheduler.to_decode(w.lane)
                        self._maybe_finish(w.lane, tok)

        if plan.decode_lanes:
            slots = self.serve.slots
            with phase(trace, "serve.decode.launch",
                       lanes=len(plan.decode_lanes)):
                toks = np.zeros((slots, 1), np.int32)
                positions = np.zeros((slots,), np.int32)
                active = np.zeros((slots,), bool)
                for lane in plan.decode_lanes:
                    req = self.scheduler.request_at(lane)
                    toks[lane, 0] = req.produced[-1]
                    positions[lane] = req.decode_pos
                    active[lane] = True
                    read_tokens += req.decode_pos + 1
                    self.kv.assert_writable(lane, req.decode_pos,
                                            req.decode_pos + 1)
                inputs = (jnp.asarray(toks), self._tables_jnp(),
                          jnp.asarray(positions), jnp.asarray(active))
                with phase(trace, "serve.decode.dispatch"):
                    logits, self.caches = self._decode(
                        self.params, self.caches, *inputs)
                live.append(self._live_blocks())
            programs += 1
            gathered += slots
            r, c = tf.served_moe_rows(self.cfg, slots,
                                      len(plan.decode_lanes))
            moe_routed, moe_computed = moe_routed + r, moe_computed + c
            gpp_pad += self._gpp_pad_bytes["decode"]
            with phase(trace, "serve.decode.fetch"):
                logits_np = self._fetch(logits)
            with phase(trace, "serve.sample"):
                for lane in plan.decode_lanes:
                    req = self.scheduler.request_at(lane)
                    req.decode_pos += 1
                    tok = self._sample(logits_np[lane, 0], req)
                    req.produced.append(tok)
                    if self._reclaims:
                        self.kv.release_expired(lane, req.decode_pos)
                    self._maybe_finish(lane, tok)
            decode_tokens = len(plan.decode_lanes)

        if plan.verify:
            v = plan.verify
            slots = self.serve.slots
            S = self.draft_len + 1
            with phase(trace, "serve.verify.launch", lanes=len(v.lanes)):
                toks = np.zeros((slots, S), np.int32)
                positions = np.zeros((slots,), np.int32)
                active = np.zeros((slots,), bool)
                nvalid = np.zeros((slots,), np.int32)
                for lane, draft in zip(v.lanes, v.drafts):
                    req = self.scheduler.request_at(lane)
                    toks[lane, 0] = req.produced[-1]
                    toks[lane, 1 : 1 + len(draft)] = draft
                    positions[lane] = req.decode_pos
                    active[lane] = True
                    nvalid[lane] = 1 + len(draft)
                    read_tokens += req.decode_pos + 1 + len(draft)
                    # the whole write span [decode_pos, decode_pos + 1 +
                    # len(draft)) must be exclusively owned: shared prefix
                    # blocks all sit below decode_pos (tail forked at
                    # admission) and draft blocks were freshly ensured —
                    # assert, never mutate shares
                    self.kv.assert_writable(lane, req.decode_pos,
                                            req.decode_pos + 1 + len(draft))
                inputs = (jnp.asarray(toks), self._tables_jnp(),
                          jnp.asarray(positions), jnp.asarray(active),
                          jnp.asarray(nvalid))
                with phase(trace, "serve.verify.dispatch"):
                    logits, self.caches = self._verify(
                        self.params, self.caches, *inputs)
                live.append(self._live_blocks())
            programs += 1
            gathered += slots
            r, c = tf.served_moe_rows(self.cfg, slots * S,
                                      int(nvalid.sum()))
            moe_routed, moe_computed = moe_routed + r, moe_computed + c
            gpp_pad += self._gpp_pad_bytes["verify"]
            with phase(trace, "serve.verify.fetch"):
                logits_np = self._fetch(logits)
            with phase(trace, "serve.sample"):
                for lane, draft in zip(v.lanes, v.drafts):
                    req = self.scheduler.request_at(lane)
                    nd = len(draft)
                    drafted_tokens += nd
                    verify_tokens += nd + 1
                    # greedy-exact acceptance: every emitted token is sampled
                    # from the TARGET logits at its logical token index (the
                    # same key plain decode would use), so the stream is
                    # token-for-token identical with speculation off; draft
                    # d_{i+1} survives only if it EQUALS the sampled token
                    tok = -1
                    for i in range(nd + 1):
                        tok = self._sample(logits_np[lane, i], req)
                        req.decode_pos += 1
                        req.produced.append(tok)
                        done = req.remaining <= 0 or (
                            self.serve.eos_token is not None
                            and tok == self.serve.eos_token)
                        matched = i < nd and tok == draft[i]
                        if matched:
                            accepted_tokens += 1
                        if done or not matched:
                            break
                    # rollback: drop table entries mapped past the accepted
                    # point (blocks ensured for rejected drafts go back to
                    # the pool); stale rows inside kept blocks are
                    # masked/overwritten
                    self.kv.truncate_blocks(
                        lane, -(-req.decode_pos // self.block_size))
                    if self._reclaims:
                        self.kv.release_expired(lane, req.decode_pos)
                    self._maybe_finish(lane, tok)

        tokens = prefill_tokens + decode_tokens + verify_tokens
        with phase(trace, "serve.account"):
            # one typed ledger row per step (schema: obs.ledger.STEP_SCHEMA).
            # The ledger derives hbm_bytes = param_bytes + kv_write_bytes +
            # kv_read_bytes: weights stream once per model program launched,
            # every processed token writes its KV, reads cover each
            # participant's live prefix.  attn_bytes_gather is the bytes
            # `_paged_gather` would MATERIALIZE (every participant's full
            # MB*bs logical sequence per layer); attn_bytes_stream is what
            # the Pallas kernel DMAs (each participant's LIVE blocks per
            # group, counted when its call was launched).
            group_bytes = np.asarray(self._group_token_bytes, np.int64)
            mb_rows = self.kv.cfg.max_blocks_per_seq * self.block_size
            row = self.metrics.record(
                tokens=tokens,
                prefill_tokens=prefill_tokens,
                # non-pad prompt tokens in the chunk (<= prefill_tokens; the
                # padded count is the flatness/traffic quantity)
                prefill_real_tokens=(plan.prefill.real_tokens
                                     if plan.prefill else 0),
                decode_tokens=decode_tokens,
                verify_tokens=verify_tokens,
                drafted_tokens=drafted_tokens,
                accepted_tokens=accepted_tokens,
                blocks_in_use=self.kv.blocks_in_use,
                free_blocks=self.kv.num_free,
                queue_depth=self.scheduler.queue_depth,
                preempted=len(plan.preempted),
                prefix_hit_tokens=plan.prefix_hit_tokens,
                blocks_shared=self.kv.blocks_shared,
                param_bytes=programs * self._param_bytes,
                kv_write_bytes=tokens * self._kv_token_bytes,
                kv_read_bytes=read_tokens * self._kv_token_bytes,
                # KV the radix index served this step: re-prefill bytes that
                # never crossed HBM
                prefix_saved_bytes=(plan.prefix_hit_tokens
                                    * self._kv_token_bytes),
                # each accepted draft token is one decode call's weight
                # stream avoided
                spec_saved_bytes=accepted_tokens * self._param_bytes,
                attn_bytes_gather=gathered * mb_rows * self._kv_token_bytes,
                attn_bytes_stream=self.block_size * int(
                    sum((n * group_bytes).sum() for n in live)),
                d2h_bytes=self._d2h,
                moe_rows_routed=moe_routed,
                moe_rows_computed=moe_computed,
                gpp_pad_bytes=gpp_pad,
                step_wall_us=(obs.now_us() - step_t0) if obs.enabled else 0.0,
            )
            if obs.enabled:
                trace.complete(
                    "step", step_t0, row["step_wall_us"], pid=PID_SERVING,
                    tid=TID_ENGINE, cat="step",
                    args={"step": row["step"], "tokens": tokens,
                          "hbm_bytes": row["hbm_bytes"]})
                trace.counter(
                    "hbm bytes/step",
                    {"total": row["hbm_bytes"],
                     "stream": row["attn_bytes_stream"]},
                    ts_us=step_t0, pid=PID_SERVING)
        return True

    def defragment(self) -> None:
        """Compact each group's physical pool (gathers then touch one dense
        prefix); pools are permuted in lockstep with the tables, and every
        holder of a moved shared block — other lanes' tables AND the prefix
        index — is rewritten through the same old->new map."""
        self._apply_pending_copies()      # copies reference pre-perm ids
        perms = self.kv.defragment()
        jperms = tuple(jnp.asarray(p) for p in perms)

        def apply(path, pool):
            jperm = jperms[tf.cache_path_group(self.cfg, path)]
            return (pool[:, jperm] if tf.is_stacked_cache_path(path)
                    else pool[jperm])

        self.caches = jax.tree_util.tree_map_with_path(apply, self.caches)
        if self.prefix is not None:
            self.prefix.remap(tuple(PagedKVCache.old_to_new(p)
                                    for p in perms))

    def run(self, max_steps: int = 10_000):
        steps = 0
        while self.pending and steps < max_steps:
            self.step()
            steps += 1
        return self._results


def make_engine(cfg: ModelConfig, params: Pytree, serve: ServeConfig,
                draft_model: "tuple[ModelConfig, Pytree] | None" = None):
    """Paged engine when the architecture supports it, dense-cache fallback
    (recurrent/cross blocks — no speculation there) otherwise."""
    if tf.supports_paged(cfg if serve.dense_kernel is None
                         else cfg.with_(dense_kernel=serve.dense_kernel)):
        return ServingEngine(cfg, params, serve, draft_model=draft_model)
    from repro.serving.dense_engine import DenseServingEngine
    return DenseServingEngine(cfg, params, serve)
