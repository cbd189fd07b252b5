"""Generalized ping-pong streaming matmul — the paper's GeMM engine on TPU.

y[M, N] = epilogue(x[M, K] @ W[K, N]) where W is too large to be VMEM-resident
and streams from HBM ("off-chip") while the MXU computes — the PIM
concurrent-write/compute problem mapped to the TPU memory hierarchy
(DESIGN.md §2.1), now on a 3-D (num_m, num_n, num_k) grid so arbitrary M/K/N
fit in VMEM:

  PIM macro           ->  one (block_k, block_n) weight tile resident in VMEM
  weight rewrite      ->  async HBM->VMEM DMA into a ring slot
  n_in input vectors  ->  the block_m rows matmul'd against the resident tile
  off-chip bandwidth  ->  HBM DMA bandwidth
  consecutive GeMMs   ->  the flattened sequence of grid steps: k innermost
                          (f32 accumulator carried across), then n, then m

Strategies (selected by `num_bufs`):
  num_bufs == 1   in-situ: DMA tile for step s, wait, compute (bursty, stalls)
  num_bufs == 2   naive ping-pong: classic double buffering — whole-tile DMA
                  for step s+1 issued while computing step s
  num_bufs >= 3   generalized ping-pong: ring of G buffers; while computing
                  step s, issue ONE CHUNK (1/C of a tile, C = G-1) for each of
                  the C upcoming steps, so DMA traffic is flat at exactly one
                  tile per compute step and the MXU never waits even when
                  t_dma > t_compute.

The chunk schedule is the seed 1-D schedule re-derived over *global grid
steps* instead of N-tiles: with S = num_m*num_n*num_k sequential steps, the
weight tile needed at step s is tile(s) = s mod (num_n*num_k) (column tile
n = tile//num_k, K-tile k = tile mod num_k), its ring slot is s mod G, and
chunk c of step s's tile is issued at step s-C+c (steps < 0 fold into the
step-0 pipeline-fill prologue).  Because the schedule is phrased in steps,
the one-tile-per-step flat-bandwidth invariant holds across k-loop, n-loop
and m-loop boundaries alike — including the ragged final tiles, which the
wrapper zero-pads to full blocks.  Coverage proof:
tests/test_kernels.py::TestSchedule.

Grid steps on TPU run sequentially on one core ("arbitrary" dimension
semantics), so DMA state (semaphore signals) persists across steps — the
standard Pallas manual-multibuffering pattern.  Chunks split the block_k
(sublane) dimension so each DMA keeps full 128-lane rows.

Epilogue (fused into the last K step, before the output store, all in f32):
  optional per-column dequant scale (int8/bf16 weights are DMA'd raw and
  widened in-kernel), optional bias add, optional activation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import V5E, ChipSpec, chip_spec, plan_matmul_tiles

_ACTIVATIONS = {
    None: lambda x: x,
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
}


def target_chip(interpret: bool) -> ChipSpec:
    """The chip a kernel call is planned and compiled for: the v5e entry for
    CPU interpret runs, else the attached device's own `CHIPS` entry (an
    unknown kind raises)."""
    return V5E if interpret else chip_spec(jax.devices()[0].device_kind)


def sublane_tile(itemsize: int) -> int:
    """Rows of one native (sublane, 128-lane) VMEM tile: 8 for 32-bit, 16
    for bf16, 32 for int8 — the granularity a DMA may slice rows at."""
    return 8 * max(1, 4 // itemsize)


def ring_chunks(G: int, rows: int, tile: int) -> int:
    """Chunks C per ring slot: G-1 (the GPP schedule), capped so every chunk
    is a non-empty whole number of `tile`-row tiles.  A block whose rows are
    not a tile multiple moves whole (C = 1)."""
    if G <= 1 or rows % tile:
        return 1
    return max(1, min(G - 1, rows // tile))


def _chunk_bounds(rows: int, chunks: int, c: int,
                  tile: int = 1) -> tuple[int, int]:
    """Rows [lo, hi) of chunk c: the rows//tile whole tiles split as evenly
    as possible (sizes differ by at most one tile), the last chunk taking
    any remainder."""
    units = rows // tile
    lo = (c * units // chunks) * tile
    hi = rows if c == chunks - 1 else ((c + 1) * units // chunks) * tile
    return lo, hi


def chunk_issue_schedule(num_steps: int, G: int,
                         C: int) -> "dict[tuple[int, int], list[int]]":
    """Pure-Python replay of the kernel's DMA issue schedule.

    Returns {(step, chunk): [issue_steps]} — the steps at which chunk `chunk`
    of the weight tile consumed at `step` is DMA'd.  Mirrors `_gpp_kernel`'s
    loops one-for-one; the schedule tests assert every (step, chunk) appears
    exactly once, at or before its consuming step.
    """
    issued: dict[tuple[int, int], list[int]] = {}
    for s in range(num_steps):
        if G == 1:
            issued.setdefault((s, 0), []).append(s)
            continue
        if s == 0:
            for c in range(C):                       # step 0: all chunks now
                issued.setdefault((0, c), []).append(0)
            for d in range(1, C):                    # ramp: folded chunks
                if d < num_steps:
                    for c in range(0, C - d):
                        issued.setdefault((d, c), []).append(0)
        for d in range(1, G):                        # steady state
            c = C - d
            if c >= 0 and s + d < num_steps:
                issued.setdefault((s + d, c), []).append(s)
    return issued


def _make_chunk_ops(ring, sems, G: int, C: int, bk: int, tile_slice):
    """(start_chunk, wait_chunk) pair for the ring's chunk DMAs, shared by
    the flat and grouped kernels.  `tile_slice(step, lo, hi)` returns the
    HBM sub-ref holding rows [lo, hi) of the weight tile consumed at
    `step` — the step->tile mapping is the only per-grid difference.  Chunk
    bounds are whole sublane tiles of the ring's dtype (Mosaic refuses a
    DMA slice that is not tile-aligned)."""
    tile = sublane_tile(ring.dtype.itemsize)

    def _copy(step, c: int):
        slot = jax.lax.rem(step, G)
        lo, hi = _chunk_bounds(bk, C, c, tile)
        return pltpu.make_async_copy(
            tile_slice(step, lo, hi),
            ring.at[slot, pl.ds(lo, hi - lo), :],
            sems.at[slot],
        )

    return (lambda step, c: _copy(step, c).start(),
            lambda step, c: _copy(step, c).wait())


def _run_chunk_schedule(s, S: int, G: int, C: int, start_chunk, wait_chunk):
    """The GPP chunk-issue DMA schedule, shared by the flat and grouped
    kernel bodies (their step->tile mappings live in start/wait_chunk).

    G == 1 is in-situ (fetch-then-compute, nothing in flight).  Otherwise:
    step s's chunk c is issued at step s-C+c; steps < 0 fold into the step-0
    pipeline-fill prologue; at steady state, step s issues chunk C-d of step
    s+d for d = 1..G-1, then waits for all chunks of its own tile.  Mirrored
    by `chunk_issue_schedule` above — keep the two in lockstep.
    """
    if G == 1:
        start_chunk(s, 0)
        wait_chunk(s, 0)
        return

    @pl.when(s == 0)
    def _prologue():
        for c in range(C):                   # step 0 computes immediately
            start_chunk(0, c)
        for d in range(1, C):                # steps 1..C-1: folded chunks
            if d < S:                        # S is static
                for c in range(0, C - d):
                    start_chunk(d, c)

    for d in range(1, G):
        c = C - d
        if c < 0:
            continue

        @pl.when(s + d < S)
        def _(d=d, c=c):
            start_chunk(s + d, c)

    for c in range(C):
        wait_chunk(s, c)


def _gpp_kernel(*refs, grid_mnk: tuple, num_bufs: int, bm: int, bn: int,
                bk: int, C: int, has_scale: bool, has_bias: bool, activation,
                out_dtype, w_dtype, x_dtype):
    """Pallas kernel body; grid = (num_m, num_n, num_k), k innermost."""
    x_ref = refs[0]
    w_hbm = refs[1]
    i = 2
    scale_ref = bias_ref = None
    if has_scale:
        scale_ref = refs[i]; i += 1
    if has_bias:
        bias_ref = refs[i]; i += 1
    y_ref = refs[i]
    acc_ref, ring, sems = refs[i + 1], refs[i + 2], refs[i + 3]

    m, n, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    num_m, nn, nk = grid_mnk
    S = num_m * nn * nk                    # total sequential grid steps
    T = nn * nk                            # weight tiles per m-pass
    G = num_bufs
    s = (m * nn + n) * nk + k              # global step

    def tile_slice(step, lo: int, hi: int):
        """Rows [lo, hi) of the weight tile consumed at grid step `step`."""
        t = jax.lax.rem(step, T)
        n_idx, k_idx = t // nk, jax.lax.rem(t, nk)
        return w_hbm.at[pl.ds(k_idx * bk + lo, hi - lo), pl.ds(n_idx * bn, bn)]

    start_chunk, wait_chunk = _make_chunk_ops(ring, sems, G, C, bk,
                                              tile_slice)

    _run_chunk_schedule(s, S, G, C, start_chunk, wait_chunk)
    slot = jax.lax.rem(s, G)
    w_tile = ring[slot]
    x_tile = x_ref[...]
    if w_dtype != x_dtype or w_dtype == jnp.int8:
        # dtype-aware streaming: the tile was DMA'd raw (bf16/int8 bytes);
        # widen to f32 right before the MXU, accumulate in f32.
        w_tile = w_tile.astype(jnp.float32)
        x_tile = x_tile.astype(jnp.float32)
    contrib = jax.lax.dot_general(
        x_tile, w_tile,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = contrib

    @pl.when(k != 0)
    def _accum():
        acc_ref[...] = acc_ref[...] + contrib

    # fused epilogue on the last K step, before the output store.
    @pl.when(k == nk - 1)
    def _epilogue():
        out = acc_ref[...]
        if has_scale:
            out = out * scale_ref[...]           # (1, bn) dequant broadcast
        if has_bias:
            out = out + bias_ref[...]
        out = _ACTIVATIONS[activation](out)
        y_ref[...] = out.astype(out_dtype)


def _pad2(a: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    if a.shape == (rows, cols):
        return a
    return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))


def plan_call(M: int, K: int, N: int, x_dtype, w_dtype, *,
              block_m: int | None = None, block_n: int | None = None,
              block_k: int | None = None, num_bufs: int | None = None,
              vmem_budget: int | None = None, chip: ChipSpec = V5E):
    """The tile plan of one `gpp_matmul` call of x (M, K) @ w (K, N) (or
    one expert's of a `gpp_matmul_grouped` call) and the padded
    (Mp, Kp, Np) its operands take: whole tiles of the plan."""
    plan = plan_matmul_tiles(
        M, K, N,
        x_itemsize=jnp.dtype(x_dtype).itemsize,
        w_itemsize=jnp.dtype(w_dtype).itemsize,
        out_itemsize=jnp.dtype(x_dtype).itemsize,
        block_m=block_m, block_n=block_n, block_k=block_k,
        num_bufs=num_bufs, vmem_budget=vmem_budget or chip.vmem_limit_bytes,
        chip=chip,
    )
    num_m, num_n, num_k = plan.grid(M, N, K)
    return plan, (num_m * plan.block_m, num_k * plan.block_k,
                  num_n * plan.block_n)


def weight_pad_bytes(M: int, K: int, N: int, x_dtype, w_dtype, *,
                     experts: int = 1, chip: ChipSpec = V5E) -> int:
    """Bytes of the zero-padded weight a free-tiled call copies before it
    streams: `experts` (1 for `gpp_matmul`) padded (Kp, Np) weights, or 0
    when the planned tiles divide K and N (`plan_call`, the wrappers'
    own rule)."""
    _, (_, Kp, Np) = plan_call(M, K, N, x_dtype, w_dtype, chip=chip)
    if (Kp, Np) == (K, N):
        return 0
    return experts * Kp * Np * jnp.dtype(w_dtype).itemsize


def gpp_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bias: jnp.ndarray | None = None,
    w_scale: jnp.ndarray | None = None,
    activation: str | None = None,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    num_bufs: int | None = None,
    vmem_budget: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Streaming matmul with the generalized ping-pong DMA schedule.

    Args:
      x: (M, K) activations (streamed through VMEM in (block_m, block_k)
         tiles; block_m is the paper's n_in).
      w: (K, N) weights in HBM, streamed in (block_k, block_n) tiles.  May be
         a narrower dtype than x (bf16/int8): tiles are DMA'd raw and widened
         in-kernel against f32 accumulation.
      bias: optional (N,) bias fused into the last-K-step epilogue.
      w_scale: optional per-column dequant scale — scalar or (N,) — applied
         to the f32 accumulator in the epilogue (int8 streaming).
      activation: optional fused activation: relu | gelu | silu | tanh.
      block_m/block_n/block_k: tile sizes; any left None is planned against
         the VMEM budget (`core.schedule.plan_matmul_tiles`), dividing K and
         N where it can.  Ragged edges are zero-padded, not errors.
      num_bufs: ring depth G — 1: in-situ, 2: naive ping-pong, >=3: GPP.
         None: planned from the DMA:compute ratio of one tile.
      vmem_budget: scoped-VMEM limit in bytes the kernel is compiled with and
         the tiles are planned against (default: the target chip's
         `vmem_limit_bytes`, 100 MiB on v5e).
      interpret: run the kernel body in interpret mode (CPU validation).
    """
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
    if num_bufs is not None and num_bufs < 1:
        raise ValueError("num_bufs >= 1")
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    out_dtype = x.dtype

    chip = target_chip(interpret)
    vmem_limit = vmem_budget or chip.vmem_limit_bytes
    plan, (Mp, Kp, Np) = plan_call(
        M, K, N, x.dtype, w.dtype, block_m=block_m, block_n=block_n,
        block_k=block_k, num_bufs=num_bufs, vmem_budget=vmem_limit, chip=chip)
    bm, bn, bk = plan.block_m, plan.block_n, plan.block_k
    num_m, num_n, num_k = plan.grid(M, N, K)
    G = min(plan.num_bufs, max(1, num_m * num_n * num_k))
    # chunks per tile: C = G-1 splits of the block_k sublanes, capped to
    # whole sublane tiles of w's dtype
    C = ring_chunks(G, bk, sublane_tile(w.dtype.itemsize))

    # zero-pad ragged edges to full tiles (K-padding is correctness-neutral;
    # M/N padding is sliced off the output).  Free tiles divide K and N
    # where the planner can, so the weight is copied only for a pinned tile
    # or a lane dim that is no multiple of 128.
    xp = _pad2(x, Mp, Kp)
    wp = _pad2(w, Kp, Np)

    operands = [xp, wp]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),      # x tile
        pl.BlockSpec(memory_space=pl.ANY),                   # w: stays in HBM
    ]
    has_scale = w_scale is not None
    has_bias = bias is not None
    if has_scale:
        sc = jnp.broadcast_to(
            jnp.asarray(w_scale, jnp.float32).reshape(1, -1), (1, N))
        operands.append(_pad2(sc, 1, Np))
        in_specs.append(pl.BlockSpec((1, bn), lambda m, n, k: (0, n)))
    if has_bias:
        b = jnp.asarray(bias, jnp.float32).reshape(1, N)
        operands.append(_pad2(b, 1, Np))
        in_specs.append(pl.BlockSpec((1, bn), lambda m, n, k: (0, n)))

    kernel = functools.partial(
        _gpp_kernel, grid_mnk=(num_m, num_n, num_k), num_bufs=G,
        bm=bm, bn=bn, bk=bk, C=C,
        has_scale=has_scale, has_bias=has_bias, activation=activation,
        out_dtype=out_dtype, w_dtype=w.dtype, x_dtype=x.dtype,
    )
    y = pl.pallas_call(
        kernel,
        name="gpp_matmul",
        grid=(num_m, num_n, num_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),               # f32 accumulator
            pltpu.VMEM((G, bk, bn), w.dtype),                # weight ring
            pltpu.SemaphoreType.DMA((G,)),                   # per-slot DMA sems
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,          # sequential grid
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
    )(*operands)
    if (Mp, Np) != (M, N):
        y = y[:M, :N]
    return y


# ---------------------------------------------------------------------------
# Grouped (batched-expert) variant: y[e] = epilogue(x[e] @ w[e])
# ---------------------------------------------------------------------------

def _gpp_grouped_kernel(*refs, grid_emnk: tuple, num_bufs: int, bm: int,
                        bn: int, bk: int, C: int, has_scale: bool,
                        has_bias: bool, activation,
                        out_dtype, w_dtype, x_dtype):
    """Pallas kernel body; grid = (E, num_m, num_n, num_k), k innermost.

    The expert axis is the *outermost ring dimension*: the global step
    sequence runs all of expert e's tiles, then expert e+1's, and the chunk
    schedule is phrased over global steps — so while the MXU finishes expert
    e's last tiles the ring is already streaming expert e+1's first weight
    tiles from HBM.  Each expert's weights cross the HBM link exactly once
    per (m-pass, n, k) visit, the PIM-DRAM batched-workload schedule
    (arXiv 2105.03736) mapped onto the TPU ring.
    """
    x_ref = refs[0]
    w_hbm = refs[1]
    i = 2
    scale_ref = bias_ref = None
    if has_scale:
        scale_ref = refs[i]; i += 1
    if has_bias:
        bias_ref = refs[i]; i += 1
    y_ref = refs[i]
    acc_ref, ring, sems = refs[i + 1], refs[i + 2], refs[i + 3]

    e, m, n, k = (pl.program_id(d) for d in range(4))
    E, num_m, nn, nk = grid_emnk
    SM = num_m * nn * nk                   # sequential steps per expert
    S = E * SM                             # total sequential grid steps
    T = nn * nk                            # weight tiles per m-pass
    G = num_bufs
    s = ((e * num_m + m) * nn + n) * nk + k   # global step

    def tile_coords(step):
        """Weight-tile coords (expert, k-tile, n-tile) consumed at `step`."""
        e_idx = step // SM
        t = jax.lax.rem(jax.lax.rem(step, SM), T)
        return e_idx, t // nk, jax.lax.rem(t, nk)

    def tile_slice(step, lo: int, hi: int):
        e_idx, n_idx, k_idx = tile_coords(step)
        return w_hbm.at[e_idx, pl.ds(k_idx * bk + lo, hi - lo),
                        pl.ds(n_idx * bn, bn)]

    start_chunk, wait_chunk = _make_chunk_ops(ring, sems, G, C, bk,
                                              tile_slice)

    # same chunk schedule as `_gpp_kernel`, over E*SM global steps: the
    # step->tile mapping is the only difference, so the flat one-tile-
    # per-step DMA invariant holds across expert boundaries too.
    _run_chunk_schedule(s, S, G, C, start_chunk, wait_chunk)
    slot = jax.lax.rem(s, G)
    w_tile = ring[slot]
    x_tile = x_ref[0]
    if w_dtype != x_dtype or w_dtype == jnp.int8:
        w_tile = w_tile.astype(jnp.float32)
        x_tile = x_tile.astype(jnp.float32)
    contrib = jax.lax.dot_general(
        x_tile, w_tile,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = contrib

    @pl.when(k != 0)
    def _accum():
        acc_ref[...] = acc_ref[...] + contrib

    @pl.when(k == nk - 1)
    def _epilogue():
        out = acc_ref[...]
        if has_scale:
            out = out * scale_ref[...]           # (1, bn) per-expert dequant
        if has_bias:
            out = out + bias_ref[...]
        out = _ACTIVATIONS[activation](out)
        y_ref[0] = out.astype(out_dtype)


def _pad3(a: jnp.ndarray, d1: int, d2: int) -> jnp.ndarray:
    if a.shape[1:] == (d1, d2):
        return a
    return jnp.pad(a, ((0, 0), (0, d1 - a.shape[1]), (0, d2 - a.shape[2])))


def gpp_matmul_grouped(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bias: jnp.ndarray | None = None,
    w_scale: jnp.ndarray | None = None,
    activation: str | None = None,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    num_bufs: int | None = None,
    vmem_budget: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched-expert streaming matmul: y[e] = epilogue(x[e] @ w[e]).

    Args:
      x: (E, C, D) per-expert activation rows (MoE: C = expert capacity).
      w: (E, D, F) per-expert weights in HBM, streamed tile-by-tile with the
         expert axis as the outermost ring dimension (each expert's weights
         cross the link once per step; the ring pipelines across experts).
      bias: optional (E, F) per-expert bias fused into the epilogue.
      w_scale: optional per-expert per-column dequant scale — scalar, (E,)
         or (E, F) — applied to the f32 accumulator in the epilogue before
         bias/activation, so int8 expert weights stream raw through the ring
         and widen in-kernel exactly like the flat kernel's dequant path.
      activation: optional fused activation (see `_ACTIVATIONS`).
      block_*/num_bufs/vmem_budget: as `gpp_matmul`, planned per expert.
      interpret: run the kernel body in interpret mode (CPU validation).
    """
    E, M, K = x.shape
    E2, K2, N = w.shape
    if E != E2 or K != K2:
        raise ValueError(f"grouped shape mismatch: {x.shape} @ {w.shape}")
    if num_bufs is not None and num_bufs < 1:
        raise ValueError("num_bufs >= 1")
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    out_dtype = x.dtype

    chip = target_chip(interpret)
    vmem_limit = vmem_budget or chip.vmem_limit_bytes
    plan, (Mp, Kp, Np) = plan_call(
        M, K, N, x.dtype, w.dtype, block_m=block_m, block_n=block_n,
        block_k=block_k, num_bufs=num_bufs, vmem_budget=vmem_limit, chip=chip)
    bm, bn, bk = plan.block_m, plan.block_n, plan.block_k
    num_m, num_n, num_k = plan.grid(M, N, K)
    G = min(plan.num_bufs, max(1, E * num_m * num_n * num_k))
    C = ring_chunks(G, bk, sublane_tile(w.dtype.itemsize))

    xp = _pad3(x, Mp, Kp)
    wp = _pad3(w, Kp, Np)

    operands = [xp, wp]
    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda e, m, n, k: (e, m, k)),  # x tile
        pl.BlockSpec(memory_space=pl.ANY),                        # w: HBM
    ]
    has_scale = w_scale is not None
    has_bias = bias is not None
    if has_scale:
        sc = jnp.asarray(w_scale, jnp.float32)
        sc = jnp.broadcast_to(sc if sc.ndim == 0 else sc.reshape(E, -1), (E, N))
        if N != Np:
            sc = jnp.pad(sc, ((0, 0), (0, Np - N)))
        operands.append(sc)
        in_specs.append(pl.BlockSpec((1, bn), lambda e, m, n, k: (e, n)))
    if has_bias:
        b = jnp.asarray(bias, jnp.float32).reshape(E, N)
        if N != Np:
            b = jnp.pad(b, ((0, 0), (0, Np - N)))
        operands.append(b)
        in_specs.append(pl.BlockSpec((1, bn), lambda e, m, n, k: (e, n)))

    kernel = functools.partial(
        _gpp_grouped_kernel, grid_emnk=(E, num_m, num_n, num_k), num_bufs=G,
        bm=bm, bn=bn, bk=bk, C=C, has_scale=has_scale, has_bias=has_bias,
        activation=activation,
        out_dtype=out_dtype, w_dtype=w.dtype, x_dtype=x.dtype,
    )
    y = pl.pallas_call(
        kernel,
        name="gpp_matmul_grouped",
        grid=(E, num_m, num_n, num_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, m, n, k: (e, m, n)),
        out_shape=jax.ShapeDtypeStruct((E, Mp, Np), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),               # f32 accumulator
            pltpu.VMEM((G, bk, bn), w.dtype),                # weight ring
            pltpu.SemaphoreType.DMA((G,)),                   # per-slot sems
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,          # sequential grid
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
    )(*operands)
    if (Mp, Np) != (M, N):
        y = y[:, :M, :N]
    return y
