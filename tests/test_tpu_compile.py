"""Compile rehearsal for a TPU v5e chip, without the chip.

The TPU compiler is installed with jaxlib, so every kernel of the serving
path can be lowered and compiled for a *described* v5e
(`topologies.get_topology_desc`) while JAX itself runs on the CPU.  That
catches what interpret mode cannot: DMA slices not aligned to the VMEM
tiling, reshapes Mosaic cannot lay out, and working sets over the scoped
VMEM limit.  Nothing runs, so these tests say nothing about results or
speed.

The topology is described inside a module-scope fixture, never at import:
only one process may hold the TPU library, and every pytest-xdist worker
imports this file.  The kernels ask `jax.devices()` for the chip they plan
against, which here is the CPU, so the fixture points their chip lookup at
the described device's kind.
"""
from __future__ import annotations

import collections
import math
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.qwen1_5_0_5b import CONFIG as QWEN
from repro.core.schedule import chip_spec

HBM_BYTES = 16 * 2**30          # one v5e chip
D, FF, VOCAB = QWEN.d_model, QWEN.d_ff, QWEN.vocab_size
HEADS, HD = QWEN.num_heads, QWEN.d_model // QWEN.num_heads
BS, SLOTS, MAX_LEN = 16, 8, 2048
MB = MAX_LEN // BS
NB = SLOTS * MB + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else the compiler logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    import repro.kernels.gpp_matmul as gm
    import repro.kernels.ops as ops
    import repro.kernels.paged_attention as pa

    spec = chip_spec(topo.devices[0].device_kind)
    # a described chip's executables can be written to the persistent cache
    # but not read back without the chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        for mod in (gm, pa, ops):
            mp.setattr(mod, "target_chip", lambda interpret: spec)
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# ------------------------------------------------------------ gpp_matmul

@pytest.mark.parametrize("G", range(1, 9))
@pytest.mark.parametrize("M", [4, 8, 256])
def test_gpp_matmul_ring_depths(one_chip, M, G):
    """Decode (M 4-8) and prefill-chunk M at every ring depth: the chunk
    DMAs must stay aligned to the bf16 sublane tile."""
    from repro.kernels.gpp_matmul import gpp_matmul
    _compile(lambda x, w: gpp_matmul(x, w, num_bufs=G),
             _sds(one_chip, (M, D)), _sds(one_chip, (D, FF)))


@pytest.mark.parametrize("M,K,N", [
    (8, D, VOCAB),          # tied LM head at decode
    (4096, D, FF),          # long prefill: a working set over the default
                            # scoped VMEM limit
    (8, FF, D),             # MLP down-projection, K split into tiles
])
def test_gpp_matmul_planned(one_chip, M, K, N):
    from repro.kernels.gpp_matmul import gpp_matmul
    _compile(gpp_matmul, _sds(one_chip, (M, K)), _sds(one_chip, (K, N)))


def test_gpp_matmul_grouped_moe(one_chip):
    """deepseek-v2-lite routed experts: 64 experts, d 2048, d_ff 1408."""
    from repro.kernels.gpp_matmul import gpp_matmul_grouped
    _compile(gpp_matmul_grouped, _sds(one_chip, (64, 16, 2048)),
             _sds(one_chip, (64, 2048, 1408)))


# ------------------------------------------------------- paged_attention

@pytest.mark.parametrize("B,S,window", [
    (SLOTS, 1, None),       # decode
    (1, 256, None),         # prefill chunk
    (SLOTS, 5, None),       # speculative verify
    (SLOTS, 1, 1024),       # sliding window
])
def test_paged_attention_gqa(one_chip, B, S, window):
    from repro.kernels.paged_attention import paged_attention
    kv = _sds(one_chip, (NB, BS, HEADS, HD))
    _compile(lambda q, a, b, t, p: paged_attention(
                 q, a, b, t, p, num_kv_heads=HEADS, scale=HD ** -0.5,
                 window=window),
             _sds(one_chip, (B, S, HEADS, HD)), kv, kv,
             _sds(one_chip, (B, MB), jnp.int32),
             _sds(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("B,S", [(SLOTS, 1), (1, 128)])
def test_paged_attention_mla(one_chip, B, S):
    """deepseek-v2-lite latent pools: c_kv 512 lanes, k_rope 64 lanes
    zero-padded to one 128-lane tile (`paged_cache_specs`)."""
    from repro.kernels.ops import paged_attn
    _compile(lambda q, a, b, t, p: paged_attn(
                 q, a, b, t, p, num_kv_heads=1, scale=192 ** -0.5, mla=True,
                 mode="pallas"),
             _sds(one_chip, (B, S, 16, 512 + 64)),
             _sds(one_chip, (NB, BS, 512)), _sds(one_chip, (NB, BS, 128)),
             _sds(one_chip, (B, MB), jnp.int32),
             _sds(one_chip, (B,), jnp.int32))


# ------------------------------------------------------------ kernel names

@pytest.mark.parametrize("name", ["gpp_matmul", "gpp_matmul_grouped",
                                  "paged_attention"])
def test_kernels_carry_their_names(one_chip, name):
    """Each Pallas call is named, so the compiled module (and the device
    trace, which shows its instructions) calls the kernel by that name."""
    import re

    from repro.kernels.gpp_matmul import gpp_matmul, gpp_matmul_grouped
    from repro.kernels.paged_attention import paged_attention
    calls = {
        "gpp_matmul": (gpp_matmul, _sds(one_chip, (8, D)),
                       _sds(one_chip, (D, FF))),
        "gpp_matmul_grouped": (gpp_matmul_grouped,
                               _sds(one_chip, (4, 8, D)),
                               _sds(one_chip, (4, D, FF))),
        "paged_attention": (
            lambda q, a, b, t, p: paged_attention(
                q, a, b, t, p, num_kv_heads=HEADS, scale=HD ** -0.5),
            _sds(one_chip, (SLOTS, 1, HEADS, HD)),
            _sds(one_chip, (NB, BS, HEADS, HD)),
            _sds(one_chip, (NB, BS, HEADS, HD)),
            _sds(one_chip, (SLOTS, MB), jnp.int32),
            _sds(one_chip, (SLOTS,), jnp.int32)),
    }
    fn, *args = calls[name]
    lowered = jax.jit(fn).lower(*args)
    assert f'kernel_name = "{name}"' in lowered.as_text()
    hlo = lowered.compile().as_text()
    assert re.search(rf'%{name}(\.\d+)? = .*custom_call_target='
                     r'"tpu_custom_call"', hlo)


# ------------------------------------------------------ whole serve step

def _on_chip(sharding, tree):
    return jax.tree.map(lambda s: _sds(sharding, s.shape, s.dtype), tree)


def _full_width(sharding, cfg=QWEN):
    """`cfg` at its published widths with both kernels forced on:
    (cfg, params, caches)."""
    from repro.models import transformer as tf
    cfg = cfg.with_(dense_kernel="kernel", paged_attn_kernel="pallas")
    params = _on_chip(sharding, jax.eval_shape(
        lambda: tf.init_params(cfg, jax.random.PRNGKey(0))))
    return cfg, params, _on_chip(sharding, tf.paged_cache_specs(cfg, NB, BS))


def _compile_decode(sharding, cfg=QWEN):
    """The full-width decode step over SLOTS lanes of MAX_LEN tokens."""
    from repro.models import transformer as tf
    cfg, params, caches = _full_width(sharding, cfg)
    tables = tuple(_sds(sharding, (SLOTS, MB), jnp.int32)
                   for _ in tf.layer_group_keys(cfg))
    return _compile(
        lambda p, c, tok, tb, pos, act: tf.decode_step_paged(
            p, cfg, tok, c, tb, pos, act),
        params, caches, _sds(sharding, (SLOTS, 1), jnp.int32), tables,
        _sds(sharding, (SLOTS,), jnp.int32),
        _sds(sharding, (SLOTS,), jnp.bool_))


def _compile_prefill(sharding, cfg=QWEN):
    """One full-width prefill chunk (the engine's planned 32 tokens)."""
    from repro.models import transformer as tf
    cfg, params, caches = _full_width(sharding, cfg)
    rows = tuple(_sds(sharding, (1, MB), jnp.int32)
                 for _ in tf.layer_group_keys(cfg))
    scalar = _sds(sharding, (), jnp.int32)
    return _compile(
        lambda p, c, tok, row, start, last: tf.prefill_chunk(
            p, cfg, tok, c, row, start, last),
        params, caches, _sds(sharding, (1, 32), jnp.int32), rows, scalar,
        scalar)


@pytest.fixture(scope="module")
def qwen_steps(one_chip):
    """qwen1.5-0.5b's decode step and prefill chunk, compiled once for the
    tests below."""
    return {"decode": _compile_decode(one_chip),
            "prefill": _compile_prefill(one_chip)}


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB > 16 GiB"


def test_decode_step_paged_fits_one_chip(qwen_steps):
    """The full-width qwen1.5-0.5b decode step with both kernels on: 8
    lanes x 2048 tokens of paged KV must fit one chip's HBM."""
    _assert_fits(qwen_steps["decode"])


def test_prefill_chunk_fits_one_chip(qwen_steps):
    """One full-width prefill chunk (the engine's planned 32 tokens)."""
    _assert_fits(qwen_steps["prefill"])


# ------------------------------------------------- KV pool layout on chip

_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([a-z][\w\-]*)\(%([\w.\-]+)")
_MEMORY_SPACE = re.compile(r"S\(\d+\)")
RELAYOUT_OPS = ("copy", "copy-start", "reshape", "transpose")


def _pool_ops(hlo: str) -> "list[tuple[str, str, str, str]]":
    """(opcode, name, result, operand's result) of every instruction among
    RELAYOUT_OPS whose result carries the pools' (NB, BS) dims; a tuple
    result (`copy-start`) is read by its first element, the copy."""
    types, found = {}, []
    for line in hlo.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        name, result, opcode, operand = m.groups()
        result = result.lstrip("(").split(", ")[0]
        types[name] = result
        dims = re.match(r"\w+\[([\d,]*)\]", result)
        if (opcode in RELAYOUT_OPS and dims
                and f",{NB},{BS}," in f",{dims.group(1)},"):
            found.append((opcode, name, result, types.get(operand, "")))
    return found


def _pool_relayouts(hlo: str) -> "list[str]":
    """The pool ops that change a pool's layout: all of `_pool_ops` but a
    copy that moves a pool between memory spaces with its layout and tiling
    kept (the eviction of a pool staged in VMEM back to HBM)."""
    return [f"{opcode} {name}: {result}"
            for opcode, name, result, src in _pool_ops(hlo)
            if not (opcode in ("copy", "copy-start")
                    and _MEMORY_SPACE.sub("", result)
                    == _MEMORY_SPACE.sub("", src))]


_PAD = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = bf16\[([\d,]+)\]\S* pad\(")


def _weight_pads(hlo: str) -> "list[tuple[int, ...]]":
    """The dims of every `pad`, fused or not, whose result is a bf16 array
    of 1 MiB or more: a weight (or a prefill chunk's activations padded
    to its weight's rows)."""
    found = []
    for line in hlo.splitlines():
        m = _PAD.match(line)
        if m:
            dims = tuple(int(d) for d in m.group(1).split(","))
            if 2 * math.prod(dims) >= 2**20:
                found.append(dims)
    return found


@pytest.fixture(scope="module")
def served_steps(one_chip):
    """Step programs of the benchmark's larger configurations, compiled
    once for the tests below: `served_steps(model, step)`."""
    from repro.configs.deepseek_v2_lite_16b import CONFIG as DEEPSEEK
    from repro.configs.qwen2_7b import CONFIG as QWEN2_7B
    models = {"qwen2-7b": QWEN2_7B.with_(num_layers=2),
              "deepseek-v2-lite": DEEPSEEK.with_(num_layers=5)}
    compiled = {}

    def get(model, step):
        if (model, step) not in compiled:
            compile_step = {"decode": _compile_decode,
                            "prefill": _compile_prefill}[step]
            compiled[model, step] = compile_step(one_chip, models[model])
        return compiled[model, step]
    return get


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_qwen_pools_are_never_copied(qwen_steps, step):
    """qwen1.5-0.5b's (NB, BS, 16 x 64) pools: stored with the 64-lane head
    as a dim of its own, XLA relaid each pool out around every write and
    kernel call of every layer.  Stored lane-dense, the step programs copy,
    reshape or transpose no pool at all."""
    assert not _pool_ops(qwen_steps[step].as_text())


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_gqa_pools_keep_their_layout(served_steps, step):
    """qwen2-7b's GQA pools (4 kv heads x 128): (4, 128) heads fill no
    (16, 128) bf16 tile, so XLA reshaped each pool into the kernel's
    (BS, 512) rows.  Two layers show every per-layer op."""
    compiled = served_steps("qwen2-7b", step)
    assert not _pool_relayouts(compiled.as_text())


# weights whose lane dim has no 128-lane divisor: Mosaic slices HBM in
# whole lanes, so the wrapper pads them to the next one (DeepSeek-V2-Lite's
# latent projection, 512 + 64 columns, in the dense layer and the scan
# body, and the dense layer's gate and up, 10944 columns)
LANE_RAGGED_PADS = {
    "qwen2-7b": [],
    "deepseek-v2-lite": [(2048, 640), (2048, 640), (2048, 11008),
                         (2048, 11008)],
}


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("model", list(LANE_RAGGED_PADS))
def test_step_programs_pad_no_weight(served_steps, model, step):
    """The planner takes tiles that divide each weight, so a step program
    copies no weight into a padded operand before the kernels stream it
    (qwen2-7b's gate, up, down, q and o were padded to 2048-row tiles
    every call, DeepSeek-V2-Lite's expert stacks to 256-column tiles),
    but those whose lane dim no tile divides."""
    pads = _weight_pads(served_steps(model, step).as_text())
    assert sorted(pads) == LANE_RAGGED_PADS[model]


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_deepseek_v2_lite_steps_fit_one_chip(served_steps, step):
    """DeepSeek-V2-Lite's first five layers (the benchmark's one-chip
    stage: the dense layer, four MoE layers with all 64 experts, the
    embedding and the head) at published widths, YaRN and dropless experts
    included.  A step program fits one chip, and every MoE layer calls the
    grouped kernel for gate, up and down, every layer the paged MLA kernel
    and gpp_matmul for q, the latent, o and the three MLP (shared-expert)
    projections: in the HLO, the dense layer's calls and one scan body's."""
    compiled = served_steps("deepseek-v2-lite", step)
    _assert_fits(compiled)
    calls = collections.Counter(re.findall(
        r"%(gpp_matmul_grouped|gpp_matmul|paged_attention)[.\d]* = ",
        compiled.as_text()))
    assert calls == {"gpp_matmul": 12, "gpp_matmul_grouped": 3,
                     "paged_attention": 2}
