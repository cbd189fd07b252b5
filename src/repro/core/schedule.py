"""Schedule IR and builders for the three concurrent write/compute strategies.

A schedule is a list of `ScheduleOp`s — (macro, kind, start, dur, bytes) — the
TPU-idiomatic equivalent of the paper's PUMA-derived assembly: the simulator
executes it, tests assert its properties (flat bandwidth, zero idle), and the
JAX streamer (`core/streamer.py`) consumes the same planner to set its ring
depth / chunking.

Builders are *idealized* (no bandwidth arbiter): they place ops where the
strategy intends them.  `repro.core.simulator` plays the same strategies
against a real shared-bus arbiter and reports what actually happens.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Iterator

from repro.core.analytical import PimConfig

KIND_REWRITE = "rewrite"
KIND_COMPUTE = "compute"


@dataclasses.dataclass(frozen=True)
class ScheduleOp:
    macro: int
    kind: str          # "rewrite" | "compute"
    start: float       # cycles
    dur: float         # cycles
    nbytes: float      # off-chip bytes moved (0 for compute)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass(frozen=True)
class Schedule:
    ops: tuple[ScheduleOp, ...]
    num_macros: int
    cfg: PimConfig
    strategy: str

    @property
    def makespan(self) -> float:
        return max((op.end for op in self.ops), default=0.0)

    def bandwidth_profile(self, resolution: int = 2048) -> "list[float]":
        """Off-chip bandwidth demand sampled over the makespan [B/cycle]."""
        span = self.makespan
        if span <= 0:
            return []
        out = [0.0] * resolution
        dt = span / resolution
        for op in self.ops:
            if op.kind != KIND_REWRITE or op.dur <= 0:
                continue
            rate = op.nbytes / op.dur
            i0 = int(op.start / dt)
            i1 = min(resolution - 1, int((op.end - 1e-9) / dt))
            for i in range(i0, i1 + 1):
                lo = max(op.start, i * dt)
                hi = min(op.end, (i + 1) * dt)
                out[i] += rate * max(0.0, hi - lo) / dt
        return out

    def peak_bandwidth(self) -> float:
        """Exact peak instantaneous bandwidth demand [B/cycle]."""
        events: list[tuple[float, float]] = []
        for op in self.ops:
            if op.kind != KIND_REWRITE or op.dur <= 0:
                continue
            rate = op.nbytes / op.dur
            events.append((op.start, rate))
            events.append((op.end, -rate))
        events.sort()
        cur = peak = 0.0
        for _, delta in events:
            cur += delta
            peak = max(peak, cur)
        return peak

    def avg_bandwidth(self) -> float:
        total = sum(op.nbytes for op in self.ops if op.kind == KIND_REWRITE)
        return total / self.makespan if self.makespan else 0.0

    def bandwidth_idle_fraction(self) -> float:
        """Fraction of the makespan with zero rewrite traffic in flight."""
        span = self.makespan
        if span <= 0:
            return 0.0
        ivals = sorted(
            (op.start, op.end) for op in self.ops if op.kind == KIND_REWRITE
        )
        busy = 0.0
        cur_s = cur_e = None
        for s, e in ivals:
            if cur_s is None:
                cur_s, cur_e = s, e
            elif s <= cur_e:
                cur_e = max(cur_e, e)
            else:
                busy += cur_e - cur_s
                cur_s, cur_e = s, e
        if cur_s is not None:
            busy += cur_e - cur_s
        return 1.0 - busy / span

    def macro_utilization(self) -> float:
        """Mean fraction of the makespan each macro spends busy (either op)."""
        span = self.makespan
        if span <= 0:
            return 0.0
        busy = sum(op.dur for op in self.ops)
        return busy / (span * self.num_macros)


# ---------------------------------------------------------------------------
# Builders
# ---------------------------------------------------------------------------

def gpp_group_count(cfg: PimConfig) -> int:
    """Number of stagger groups G = round((t_pim + t_rw) / t_rw), >= 2.

    With G groups, group k starts its rewrite at k*(t_pim+t_rw)/G; exactly
    num/G macros rewrite at any instant when the ratio divides evenly.
    """
    tp, tr = cfg.time_pim, cfg.time_rewrite
    return max(2, round((tp + tr) / tr))


def gpp_concurrent_rewriters(cfg: PimConfig, num_macros: int) -> float:
    """Average number of simultaneously-rewriting macros under GPP."""
    tp, tr = cfg.time_pim, cfg.time_rewrite
    return num_macros * tr / (tp + tr)


def build_insitu(cfg: PimConfig, num_macros: int, rounds: int) -> Schedule:
    """All macros rewrite together, then all compute together."""
    tp, tr = cfg.time_pim, cfg.time_rewrite
    ops = []
    for r in range(rounds):
        t0 = r * (tp + tr)
        for m in range(num_macros):
            ops.append(ScheduleOp(m, KIND_REWRITE, t0, tr, cfg.size_macro))
            ops.append(ScheduleOp(m, KIND_COMPUTE, t0 + tr, tp, 0.0))
    return Schedule(tuple(ops), num_macros, cfg, "insitu")


def build_naive_pp(cfg: PimConfig, num_macros: int, rounds: int) -> Schedule:
    """Two synchronized banks: one computes GeMM n while the other rewrites
    weights for GeMM n+1; banks swap when BOTH finish (paper Fig 3b)."""
    tp, tr = cfg.time_pim, cfg.time_rewrite
    period = max(tp, tr)
    half = num_macros // 2
    bank = [0] * half + [1] * (num_macros - half)
    ops = []
    # phase p: bank (p % 2) computes round p, bank ((p+1) % 2) rewrites
    # weights for round p+1.  Warm-up: bank0 rewrites round 0 first.
    for m in range(num_macros):
        if bank[m] == 0:
            ops.append(ScheduleOp(m, KIND_REWRITE, 0.0, tr, cfg.size_macro))
    t0 = tr  # steady phases start after warm-up fill
    for p in range(rounds):
        comp_bank = p % 2
        for m in range(num_macros):
            if bank[m] == comp_bank:
                ops.append(ScheduleOp(m, KIND_COMPUTE, t0, tp, 0.0))
            elif p + 1 < rounds:
                ops.append(ScheduleOp(m, KIND_REWRITE, t0, tr, cfg.size_macro))
        t0 += period
    return Schedule(tuple(ops), num_macros, cfg, "naive_pp")


def build_gpp(cfg: PimConfig, num_macros: int, rounds: int) -> Schedule:
    """Generalized ping-pong: macro groups stagger rewrite starts so that
    off-chip traffic is flat and no macro ever idles (paper Fig 3c)."""
    tp, tr = cfg.time_pim, cfg.time_rewrite
    period = tp + tr
    groups = gpp_group_count(cfg)
    ops = []
    for m in range(num_macros):
        g = m % groups
        offset = g * period / groups
        for r in range(rounds):
            t0 = offset + r * period
            ops.append(ScheduleOp(m, KIND_REWRITE, t0, tr, cfg.size_macro))
            ops.append(ScheduleOp(m, KIND_COMPUTE, t0 + tr, tp, 0.0))
    return Schedule(tuple(ops), num_macros, cfg, "gpp")


def build(strategy: str, cfg: PimConfig, num_macros: int, rounds: int) -> Schedule:
    return {
        "insitu": build_insitu,
        "naive_pp": build_naive_pp,
        "gpp": build_gpp,
    }[strategy](cfg, num_macros, rounds)


# ---------------------------------------------------------------------------
# Planner interface consumed by the JAX streamer (core/streamer.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """GPP plan for streaming L weight blocks through a compute pipeline.

    ring_depth   number of weight buffers held concurrently (paper: groups
                 rewriting + the one computing).
    chunks       chunks each block's transfer is split into, issued one per
                 compute slot, so link demand is flat.
    t_compute    per-block compute time estimate [s]
    t_transfer   per-block transfer time estimate [s]
    """

    ring_depth: int
    chunks: int
    t_compute: float
    t_transfer: float

    @property
    def ratio(self) -> float:
        return self.t_compute / self.t_transfer if self.t_transfer else math.inf


def plan_stream(
    *,
    block_bytes: float,
    compute_flops: float,
    flops_per_s: float,
    transfer_bytes_per_s: float,
    max_ring: int = 8,
) -> StreamPlan:
    """Plan ring depth & chunking for streaming weight blocks.

    TPU mapping of Eq 4: a block is a "macro", transfer is the "rewrite",
    the per-block matmul is the "compute".  ring = ceil(t_tr/t_cmp)+1 buffers
    keep compute from ever waiting; `chunks` splits each transfer so each
    compute slot carries ~1/ratio of a block (flat bandwidth).
    """
    t_cmp = compute_flops / flops_per_s
    t_tr = block_bytes / transfer_bytes_per_s
    if t_cmp <= 0:
        return StreamPlan(2, 1, t_cmp, t_tr)
    ring = min(max_ring, max(2, math.ceil(t_tr / t_cmp) + 1))
    chunks = max(1, round(t_cmp / t_tr)) if t_tr > 0 else 1
    return StreamPlan(ring, chunks, t_cmp, t_tr)


# ---------------------------------------------------------------------------
# Serving chunk planner: GPP flatness applied to prefill
# ---------------------------------------------------------------------------

def plan_serve_chunk(*, token_budget: int, decode_lanes: int,
                     block_size: int, cached_tokens: int = 0) -> int:
    """Prefill chunk size for the paged serving engine (serving/scheduler.py).

    Same math as `plan_stream`'s chunking, one level up: a prompt's prefill
    is the bursty "rewrite" (its KV-write and weight-read traffic), decode
    steps are the compute slots, and the flat-bandwidth condition is that
    every step moves the same token count.  A step carries up to
    `decode_lanes` decode tokens plus one prefill chunk, so the chunk is the
    largest KV-block multiple that keeps the step at or under the flat
    `token_budget` target — the per-step analogue of "each compute slot
    carries ~1/ratio of a block".

    cached_tokens: expected per-admission prefix-cache hit depth
    (serving/prefix.py).  Cached tokens enter a request's context without
    compute or HBM writes — a prompt's real prefill burst shrinks by that
    much, so the budget they would have burned is handed back to the chunk:
    a deployment with a known steady hit depth can carry a larger chunk at
    the same real per-step traffic, finishing cold prompts sooner without
    un-flattening the stream.
    """
    if block_size < 1:
        raise ValueError("block_size >= 1")
    if decode_lanes < 0:
        raise ValueError("decode_lanes >= 0")
    if cached_tokens < 0:
        raise ValueError("cached_tokens >= 0")
    spare = max(block_size, token_budget + cached_tokens - decode_lanes)
    return max(block_size, (spare // block_size) * block_size)


def plan_verify_budget(*, token_budget: int, prefill_tokens: int,
                       decode_lanes: int) -> int:
    """Draft tokens a speculative-verify step may add on top of the step's
    prefill chunk and decode lanes — the GPP flatness math extended to
    accepted-token bursts.

    The flat target is `token_budget` tokens per step.  A prefill chunk
    plus the decode lanes already claim `prefill_tokens + decode_lanes` of
    it; the SLACK is what drafting may fill.  On prefill-carrying steps
    the slack is ~0 (the chunk was sized to reach the budget), so drafts
    ride the decode-only steps that would otherwise under-fill the link —
    per-step token count (and hence weight-stream amortization) stays flat
    instead of decode trickling one token per lane per weight pass.
    """
    if token_budget < 0:
        raise ValueError("token_budget >= 0")
    if prefill_tokens < 0:
        raise ValueError("prefill_tokens >= 0")
    if decode_lanes < 0:
        raise ValueError("decode_lanes >= 0")
    return max(0, token_budget - prefill_tokens - decode_lanes)


def tokens_per_step_cov(counts: "list[int] | list[float]") -> float:
    """Coefficient of variation of per-step token counts — the serving
    flatness metric (0 = perfectly flat traffic, the GPP ideal; the seed
    engine's prefill bursts push it >> 1)."""
    counts = [float(c) for c in counts]
    if not counts:
        return 0.0
    mean = sum(counts) / len(counts)
    if mean == 0:
        return 0.0
    return statistics.pstdev(counts) / mean


# ---------------------------------------------------------------------------
# Measured-timing feedback: TimingCache
# ---------------------------------------------------------------------------

TIMING_PROVENANCES = ("host", "compiled")


@dataclasses.dataclass(frozen=True)
class TimingSample:
    """One measured (transfer, compute) pair for a weight tile.

    block_bytes / compute_flops describe the tile the measurement was taken
    on; t_dma / t_compute are the measured wall-times [s] to move and to
    matmul that tile.  Rates (bytes/s, flop/s) are what the planner consumes,
    so samples at any tile size inform plans at every tile size.

    measured_on records provenance: "host" samples come from eager/CPU timing
    loops (dispatch overhead, no real HBM), "compiled" samples from a
    compiled run on the accelerator the plan will execute on.  Consumers
    (`TimingCache.effective_rates`) prefer compiled samples when any exist —
    a host-measured rate is a stand-in, not ground truth.
    """

    block_bytes: float
    compute_flops: float
    t_dma: float
    t_compute: float
    measured_on: str = "host"

    @property
    def bytes_per_s(self) -> float:
        return self.block_bytes / self.t_dma if self.t_dma > 0 else math.inf

    @property
    def flops_per_s(self) -> float:
        return self.compute_flops / self.t_compute if self.t_compute > 0 else math.inf


class TimingCache:
    """Measured per-tile t_dma/t_compute samples feeding `plan_matmul_tiles`.

    The analytic model (a `ChipSpec`'s peaks) is a datasheet ideal;
    real kernels see fused-epilogue overheads, DMA contention, and clock
    throttling.  `benchmarks/run.py` records what one tile *actually* costs
    on this host and the planner then sizes the ring against median measured
    rates instead of the ideal — the paper's runtime-adaptation loop
    (Fig 7) applied to the TPU mapping.
    """

    def __init__(self, samples: "list[TimingSample] | tuple[TimingSample, ...]" = ()):
        self._samples: list[TimingSample] = list(samples)

    def __len__(self) -> int:
        return len(self._samples)

    @property
    def samples(self) -> "tuple[TimingSample, ...]":
        return tuple(self._samples)

    def record(self, *, block_bytes: float, compute_flops: float,
               t_dma: float, t_compute: float,
               measured_on: str = "host") -> None:
        if block_bytes <= 0 or compute_flops <= 0:
            raise ValueError("block_bytes and compute_flops must be positive")
        if t_dma < 0 or t_compute < 0:
            raise ValueError("measured times must be non-negative")
        if measured_on not in TIMING_PROVENANCES:
            raise ValueError(
                f"measured_on must be one of {TIMING_PROVENANCES}, "
                f"got {measured_on!r}")
        self._samples.append(TimingSample(block_bytes, compute_flops,
                                          t_dma, t_compute, measured_on))

    def effective_rates(self) -> "tuple[float, float]":
        """(flops_per_s, transfer_bytes_per_s) — median of per-sample rates.

        Median (not mean): one cold-cache or preempted sample must not drag
        the plan; the planner wants the steady-state rate.  When any
        compiled-run samples exist they are used exclusively — host-measured
        rates (eager dispatch, no real HBM link) only stand in until a
        compiled path has been profiled.
        """
        if not self._samples:
            raise ValueError("TimingCache has no samples")
        pool = [s for s in self._samples if s.measured_on == "compiled"] \
            or self._samples
        fps = statistics.median(s.flops_per_s for s in pool)
        bps = statistics.median(s.bytes_per_s for s in pool)
        return fps, bps

    # ---- persistence (benchmarks/run.py emits, sessions consume) ----
    def to_json(self) -> "list[dict]":
        return [dataclasses.asdict(s) for s in self._samples]

    @classmethod
    def from_json(cls, entries: "list[dict]") -> "TimingCache":
        return cls([TimingSample(**e) for e in entries])

    @classmethod
    def from_bench_json(cls, path: str,
                        key: str = "dense_timing_samples") -> "TimingCache":
        """Load the samples `benchmarks/run.py` mirrors into
        BENCH_kernels.json (entry `key`, field "samples")."""
        import json
        with open(path) as f:
            bench = json.load(f)
        entry = bench.get(key) or {}
        return cls.from_json(entry.get("samples", []))


_DEFAULT_TIMING: "TimingCache | None" = None


def set_default_timing_cache(cache: "TimingCache | None") -> None:
    """Install measurements for every subsequent `plan_matmul_tiles` call
    that doesn't pass its own `timing` (None clears)."""
    global _DEFAULT_TIMING
    _DEFAULT_TIMING = cache


def get_default_timing_cache() -> "TimingCache | None":
    return _DEFAULT_TIMING


# ---------------------------------------------------------------------------
# M/K/N tile planner for the streaming matmul kernel (kernels/gpp_matmul.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """One accelerator's published peaks plus the VMEM a kernel may claim.

    `vmem_limit_bytes` is passed to every `pallas_call` as its scoped-VMEM
    limit AND is the budget the tile/ring planners fill, so one number sets
    both."""

    peak_flops: float           # bf16 FLOP/s
    hbm_bytes_per_s: float
    vmem_limit_bytes: int


# Keyed by `jax.Device.device_kind`.  v5e: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 128 MiB VMEM per core — 100 MiB
# claimed, the rest left to Mosaic's internal scratch).
CHIPS = {
    "TPU v5 lite": ChipSpec(peak_flops=197e12, hbm_bytes_per_s=819e9,
                            vmem_limit_bytes=100 * 1024 * 1024),
}
V5E = CHIPS["TPU v5 lite"]     # analytic default; CPU interpret runs' target


def chip_spec(device_kind: str) -> ChipSpec:
    """The `CHIPS` entry for a device kind; an unknown kind is an error,
    never a silent v5e default."""
    spec = CHIPS.get(device_kind)
    if spec is None:
        raise ValueError(
            f"no ChipSpec for device kind {device_kind!r}: add its published "
            f"peaks to repro.core.schedule.CHIPS (known: {sorted(CHIPS)})")
    return spec


_LANE = 128     # TPU lane width: block_n granularity
_SUBLANE = 8    # f32 sublane: block_m / block_k granularity


def round_up(x: int, mult: int) -> int:
    """Smallest multiple of `mult` >= x (tile, block, and chunk sizing)."""
    return ((x + mult - 1) // mult) * mult


def _resolve_rates(flops_per_s, transfer_bytes_per_s,
                   timing: "TimingCache | None",
                   chip: ChipSpec) -> "tuple[float, float]":
    """Shared rate resolution for the tile/ring planners: an explicit
    `timing` cache (or, when nothing was passed, the ambient default cache)
    replaces the chip's datasheet peaks with median measured rates;
    explicitly passed rate kwargs win over the ambient default."""
    if timing is None and flops_per_s is None and transfer_bytes_per_s is None:
        timing = _DEFAULT_TIMING
    if timing is not None and len(timing):
        flops_per_s, transfer_bytes_per_s = timing.effective_rates()
    if flops_per_s is None:
        flops_per_s = chip.peak_flops
    if transfer_bytes_per_s is None:
        transfer_bytes_per_s = chip.hbm_bytes_per_s
    return flops_per_s, transfer_bytes_per_s


@dataclasses.dataclass(frozen=True)
class MatmulTilePlan:
    """Tile sizes + ring depth for the 3-D-grid GPP streaming matmul.

    The working set held on-chip is
      ring:   num_bufs * block_k * block_n * w_itemsize   (weight ring)
      x:      2 * block_m * block_k * x_itemsize          (pipelined in-block)
      y:      2 * block_m * block_n * out_itemsize        (pipelined out-block)
      acc:    block_m * block_n * 4                       (f32 accumulator)
    """

    block_m: int
    block_n: int
    block_k: int
    num_bufs: int
    vmem_bytes: int

    def grid(self, M: int, N: int, K: int) -> "tuple[int, int, int]":
        return (
            -(-M // self.block_m),
            -(-N // self.block_n),
            -(-K // self.block_k),
        )


def matmul_vmem_bytes(block_m: int, block_n: int, block_k: int, num_bufs: int,
                      *, x_itemsize: int, w_itemsize: int,
                      out_itemsize: int) -> int:
    return (
        num_bufs * block_k * block_n * w_itemsize
        + 2 * block_m * block_k * x_itemsize
        + 2 * block_m * block_n * out_itemsize
        + block_m * block_n * 4
    )


# A free (block_k, block_n) pair aims at a weight tile of this many bytes,
# no tile above _TILE_MAX_BYTES where a smaller one divides, and rows at
# least _WIDE_N lanes long where N allows (scripts/gpp_tile_sweep.py).
_TILE_BYTES = 4 * 1024 * 1024
_TILE_MAX_BYTES = 6 * 1024 * 1024
_WIDE_N = 512


def _lane_divisors(n: int) -> "list[int]":
    """The multiples of the 128-lane tile that divide n, ascending."""
    return [t for t in range(_LANE, n + 1, _LANE) if n % t == 0]


def _tile_options(dim: int, pinned: "int | None",
                  whole: bool) -> "list[tuple[int, int]]":
    """(tile, padded dim) choices for one dim of the weight.  A pinned tile
    pads the dim to its next multiple.  Free, the tiles are the 128-lane
    divisors of the dim, or of the dim padded to whole lanes when it has
    none; `whole` (block_k) also offers the whole dim as one tile, rounded
    up to the sublane tile (a block equal to the operand's dim)."""
    if pinned is not None:
        return [(pinned, round_up(dim, pinned))]
    padded = round_up(dim, _LANE)
    opts = [(t, padded) for t in _lane_divisors(padded)]
    if whole and dim % _LANE:
        opts.append((round_up(dim, _SUBLANE), round_up(dim, _SUBLANE)))
    return opts


def plan_matmul_tiles(
    M: int,
    K: int,
    N: int,
    *,
    x_itemsize: int = 4,
    w_itemsize: int = 4,
    out_itemsize: int = 4,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    num_bufs: int | None = None,
    vmem_budget: "int | None" = None,
    max_ring: int = 8,
    flops_per_s: "float | None" = None,
    transfer_bytes_per_s: "float | None" = None,
    timing: "TimingCache | None" = None,
    chip: ChipSpec = V5E,
) -> MatmulTilePlan:
    """Pick (block_m, block_n, block_k, num_bufs) under the VMEM budget.

    Caller-pinned dims are honored as-is.  Free block_k and block_n are
    chosen together among tiles that divide K and N, so the kernel streams
    the weight as it lies in HBM: a tile of the weight's own dims (128-lane
    divisors, or the whole of a K that has none) is taken over one that
    pads it, and among those the pair whose weight tile is nearest
    `_TILE_BYTES`, no larger than `_TILE_MAX_BYTES` where a smaller one
    exists, with rows at least `_WIDE_N` lanes long where N allows.  Only
    a lane dim N with no 128-lane divisor (Mosaic slices HBM in whole
    lanes) or a pinned tile makes the wrapper zero-pad the weight.

    When the working set does not fit, the planner moves to a smaller
    divisor pair, then halves block_m, then shrinks the ring depth, instead
    of erroring.  It raises only if the *pinned* configuration cannot fit
    at minimum sizes of every free dim.  `vmem_budget` defaults to
    `chip.vmem_limit_bytes`, the scoped limit the kernel is compiled with.

    `timing` (or, when omitted, the cache installed via
    `set_default_timing_cache`) replaces the analytic flops_per_s /
    transfer_bytes_per_s constants (the None-defaults of the rate kwargs)
    with median *measured* rates, so the ring depth tracks what one tile
    actually costs on this host rather than the datasheet ideal.  An
    explicitly passed rate kwarg wins over the ambient default cache (but
    not over an explicitly passed `timing`).
    """
    flops_per_s, transfer_bytes_per_s = _resolve_rates(
        flops_per_s, transfer_bytes_per_s, timing, chip)
    if vmem_budget is None:
        vmem_budget = chip.vmem_limit_bytes
    if M < 1 or K < 1 or N < 1:
        raise ValueError(f"bad matmul shape M={M} K={K} N={N}")
    if num_bufs is not None and num_bufs < 1:
        raise ValueError("num_bufs >= 1")
    bm = block_m if block_m is not None else min(round_up(M, _SUBLANE), 512)
    k_opts = _tile_options(K, block_k, whole=True)
    n_opts = _tile_options(N, block_n, whole=False)
    wide = min(_WIDE_N, max(bn for bn, _ in n_opts))

    def ring_for(bm_, bk_, bn_):
        if num_bufs is not None:
            return num_bufs
        plan = plan_stream(
            block_bytes=bk_ * bn_ * w_itemsize,
            compute_flops=2.0 * bm_ * bk_ * bn_,
            flops_per_s=flops_per_s,
            transfer_bytes_per_s=transfer_bytes_per_s,
            max_ring=max_ring,
        )
        return plan.ring_depth

    def fits(bm_, bk_, bn_, g_):
        return matmul_vmem_bytes(
            bm_, bn_, bk_, g_, x_itemsize=x_itemsize, w_itemsize=w_itemsize,
            out_itemsize=out_itemsize) <= vmem_budget

    def rank(bk_, kp, bn_, np_):
        tile = bk_ * bn_ * w_itemsize
        return (kp * np_, tile > _TILE_MAX_BYTES, bn_ < wide,
                abs(math.log2(tile / _TILE_BYTES)), -bn_)

    def best_pair(bm_, shrink_ring):
        """The best-ranked (bk, bn, ring) that fits at block_m `bm_`."""
        best = None
        for bk_, kp in k_opts:
            for bn_, np_ in n_opts:
                g_ = ring_for(bm_, bk_, bn_)
                while shrink_ring and g_ > 1 and not fits(bm_, bk_, bn_, g_):
                    g_ -= 1     # last resort ends at in-situ (G=1)
                if fits(bm_, bk_, bn_, g_):
                    key = rank(bk_, kp, bn_, np_)
                    if best is None or key < best[0]:
                        best = (key, bk_, bn_, g_)
        return None if best is None else best[1:]

    pick = best_pair(bm, False)
    while pick is None and block_m is None and bm > _SUBLANE:
        bm = max(_SUBLANE, round_up(bm // 2, _SUBLANE))
        pick = best_pair(bm, False)
    if pick is None and num_bufs is None:
        pick = best_pair(bm, True)
    if pick is None:
        bk, bn = k_opts[0][0], n_opts[0][0]
        used = matmul_vmem_bytes(
            bm, bn, bk, num_bufs or 1, x_itemsize=x_itemsize,
            w_itemsize=w_itemsize, out_itemsize=out_itemsize)
        raise ValueError(
            f"matmul working set {used / 2**20:.1f} MiB exceeds the "
            f"{vmem_budget / 2**20:.0f} MiB VMEM budget even at minimum "
            f"free-tile sizes (pinned: block_m={block_m} block_n={block_n} "
            f"block_k={block_k} num_bufs={num_bufs})"
        )
    bk, bn, g = pick

    # grow an unpinned block_m back toward M while the budget allows: every
    # extra m-pass re-streams the whole weight matrix from HBM, which is
    # exactly the traffic this kernel exists to minimize.
    if block_m is None:
        M_full = round_up(M, _SUBLANE)
        while bm < M_full:
            bm_try = min(M_full, bm * 2)
            g_try = ring_for(bm_try, bk, bn)
            if not fits(bm_try, bk, bn, g_try):
                break
            bm, g = bm_try, g_try

    used = matmul_vmem_bytes(
        bm, bn, bk, g, x_itemsize=x_itemsize, w_itemsize=w_itemsize,
        out_itemsize=out_itemsize)
    return MatmulTilePlan(block_m=bm, block_n=bn, block_k=bk, num_bufs=g,
                          vmem_bytes=used)


# ---------------------------------------------------------------------------
# KV-block ring planner for the paged-attention kernel
# (kernels/paged_attention.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PagedAttnPlan:
    """Ring depth + chunking for streaming KV blocks through VMEM.

    The paged-attention kernel is the GPP schedule applied to the attention
    read path: a physical KV block is the "macro", its HBM->VMEM DMA the
    "rewrite", the per-block online-softmax flash step the "compute".
    num_bufs is the KV-block ring depth G (1 in-situ, 2 naive ping-pong,
    >= 3 generalized ping-pong with C = G-1 chunks per block).
    """

    num_bufs: int
    chunks: int
    vmem_bytes: int


def plan_paged_attn(
    *,
    block_bytes: int,
    compute_flops: float,
    fixed_bytes: int = 0,
    vmem_budget: "int | None" = None,
    max_ring: int = 8,
    num_bufs: "int | None" = None,
    flops_per_s: "float | None" = None,
    transfer_bytes_per_s: "float | None" = None,
    timing: "TimingCache | None" = None,
    chip: ChipSpec = V5E,
) -> PagedAttnPlan:
    """Pick the KV-block ring depth for the paged-attention kernel.

    block_bytes    bytes one logical KV block moves HBM->VMEM per grid step
                   (both pools: k+v, or c_kv+k_rope)
    compute_flops  flops of one per-block flash step (QK^T + PV)
    fixed_bytes    non-ring VMEM working set (queries, accumulator, output)

    Rates come from the same measured-feedback path as `plan_matmul_tiles`:
    an explicit/ambient `TimingCache` overrides the analytic constants, with
    compiled-run samples preferred over host ones.  The ring shrinks (never
    errors) until fixed + G*block_bytes fits the VMEM budget, which defaults
    to `chip.vmem_limit_bytes` (the kernel's scoped limit).
    """
    if block_bytes <= 0:
        raise ValueError("block_bytes must be positive")
    flops_per_s, transfer_bytes_per_s = _resolve_rates(
        flops_per_s, transfer_bytes_per_s, timing, chip)
    if vmem_budget is None:
        vmem_budget = chip.vmem_limit_bytes
    if num_bufs is not None:
        if num_bufs < 1:
            raise ValueError("num_bufs >= 1")
        g = num_bufs
    else:
        g = plan_stream(
            block_bytes=block_bytes,
            compute_flops=compute_flops,
            flops_per_s=flops_per_s,
            transfer_bytes_per_s=transfer_bytes_per_s,
            max_ring=max_ring,
        ).ring_depth
        while g > 1 and fixed_bytes + g * block_bytes > vmem_budget:
            g -= 1      # shrink toward in-situ instead of erroring
    used = fixed_bytes + g * block_bytes
    if used > vmem_budget and num_bufs is None:
        raise ValueError(
            f"paged-attention working set {used / 2**20:.1f} MiB exceeds the "
            f"{vmem_budget / 2**20:.0f} MiB VMEM budget even at ring depth 1")
    return PagedAttnPlan(num_bufs=g, chunks=max(1, g - 1), vmem_bytes=used)
