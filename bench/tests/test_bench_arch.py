"""The architecture modules (`bench/arch/<model_type>.py`), found by the
configuration's `model_type`, and the roofline readers that divide by the
call counts their step work gives."""
import types

import pytest

from bench import model, peaks, run, work
from bench import trace_reduce as tr

V5E = peaks.peaks_for("TPU v5 lite")
SLOTS = 16
LANES = [1, 2, 17, 100, 255, 256, 257, 1000, 1536, 2047, 2048, 3333, 4096]

# What bench/work.py gave before the Qwen2 arithmetic moved to
# bench/arch/qwen2.py, with the readers' call counts of then (7 x layers
# gpp_matmul calls and layers paged_attention calls a step program):
# parameters a step streams, KV bytes per token, calls a program, and for
# one decode step (LANES live over SLOTS lanes) and two prefill chunks
# (rows, start, real) the least time of the gpp_matmul calls and of the
# paged_attention calls on a v5e, and the model's operations.  Compared bit
# for bit: the readers divide by these.
PINNED = {
    "qwen2-7b-14L": {
        "streamed": 3807641600, "kv": 28672, "calls": (98, 14),
        "decode": (0.008016992273504274, 0.0005273337435897435,
                   101998804992.0),
        (32, 3200, 20): (0.008066284307692307, 0.00012098953846153846,
                         165192867840.0),
        (32, 0, 32): (0.008066284307692307, 8.962188034188034e-06,
                      243795034112.0)},
    "qwen1.5-0.5b": {
        "streamed": 463863808, "kv": 98304, "calls": (168, 24),
        "decode": (0.0007716683956043956, 0.0017964785934065934,
                   13529907200.0),
        (32, 3200, 20): (0.0007901529084249085, 0.0003917756483516483,
                         24866652160.0),
        (32, 0, 32): (0.0007901529084249085, 7.681875457875457e-06,
                      29739188224.0)},
}


def _arch_shape(name):
    hf = model.load_config(name)
    arch = model.arch_for(hf)
    return arch, arch.Shape.from_hf(hf)


def _reading(w):
    p = V5E
    return (w["gpp"].least_s(p.flops_per_s, p.hbm_bytes_per_s),
            w["attn"].least_s(p.flops_per_s, p.hbm_bytes_per_s),
            w["model_flops"])


@pytest.mark.parametrize("name", sorted(PINNED))
def test_pinned_sizes(name):
    _, shape = _arch_shape(name)
    assert shape.streamed_params() == PINNED[name]["streamed"]
    assert shape.kv_bytes_per_token() == PINNED[name]["kv"]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_pinned_decode_step(name):
    arch, shape = _arch_shape(name)
    w = arch.decode_step(shape, SLOTS, LANES)
    assert (w["gpp_calls"], w["attn_calls"]) == PINNED[name]["calls"]
    assert _reading(w) == PINNED[name]["decode"]


@pytest.mark.parametrize("chunk", [(32, 3200, 20), (32, 0, 32)])
@pytest.mark.parametrize("name", sorted(PINNED))
def test_pinned_prefill_chunk(name, chunk):
    arch, shape = _arch_shape(name)
    w = arch.prefill_chunk(shape, *chunk)
    assert (w["gpp_calls"], w["attn_calls"]) == PINNED[name]["calls"]
    assert _reading(w) == PINNED[name][chunk]


def test_arch_for_unknown_type_names_the_file():
    with pytest.raises(FileNotFoundError, match=r"bench/arch/deepseek_v2\.py"):
        model.arch_for({"model_type": "deepseek_v2"})


def test_kernel_weights_are_the_blocks_projections():
    """The routing check's leaves are every projection of a Qwen2 block."""
    import jax
    from repro.models import transformer as tf
    arch, _ = _arch_shape("qwen2-7b-14L")
    hf = arch.smoke_hf(model.load_config("qwen2-7b-14L"))
    specs = tf.param_specs(arch.model_config("smoke", hf))
    names = {str(p[-1].key) for p, _ in
             jax.tree_util.tree_leaves_with_path(specs["blocks"])}
    assert arch.KERNEL_WEIGHTS == {n for n in names if n.startswith("w_")}


# ------------------------------------------- readers against step counts

LAYERS = 2
GPP_OP = ('%gpp_matmul.7 = bf16[16,1024]{1,0:T(8,128)(2,1)} custom-call('
          'bf16[16,1024]{1,0:T(8,128)(2,1)} %x.1, bf16[1024,1024]'
          '{1,0:T(8,128)(2,1)} %w.1), custom_call_target="tpu_custom_call"')
ATTN_OP = ('%paged_attention.3 = bf16[2,1,4,128]{3,2,1,0} custom-call('
           's32[2,4]{1,0} %tables.1, s32[2]{0} %pos.1, bf16[2,1,4,128]'
           '{3,2,1,0} %q.1, bf16[17,16,256]{2,1,0} %k.1, bf16[17,16,256]'
           '{2,1,0} %v.1), custom_call_target="tpu_custom_call"')


def _fake_arch(gpp_per_layer):
    """An architecture whose step programs make `gpp_per_layer` gpp_matmul
    calls and one paged_attention call a layer."""
    def step(*_):
        return {"gpp": work.Work(1e9, 1e6), "attn": work.Work(1e6, 4e6),
                "gpp_calls": gpp_per_layer * LAYERS, "attn_calls": LAYERS,
                "model_flops": 1e9}
    return types.SimpleNamespace(decode_step=step, prefill_chunk=step)


def _ctx(arch):
    """One traced decode step over two lanes: 9 gpp_matmul calls and one
    paged_attention call a layer in the trace, 1 us each."""
    ops = [tr.Event(GPP_OP, 1000 * i, 1000) for i in range(9 * LAYERS)]
    ops += [tr.Event(ATTN_OP, 1000 * (9 * LAYERS + i), 1000)
            for i in range(LAYERS)]
    return run.Context(
        due={}, tokens={}, t0=0.0, t_end=1.0, setup_s=0.0, rows=[],
        steps=[{"prefill": None, "decode": [5, 9]}], arch=arch, shape=None,
        slots=4, peaks=V5E, memory_peak_bytes=0, traced=(0.0, 1.0, 0, 1),
        trace=tr.Trace({"/device:TPU:0": ops}, {}, []))


def _read(metric, ctx):
    return model.load_module(run.BENCH / "metrics" / f"{metric}.py").read(ctx)


def test_readers_compare_the_trace_with_the_steps_counts():
    nine = _ctx(_fake_arch(9))
    least = work.Work(1e9, 1e6).least_s(V5E.flops_per_s, V5E.hbm_bytes_per_s)
    assert _read("gpp_matmul_roofline", nine) == pytest.approx(
        100.0 * least / (9 * LAYERS * 1e-6))
    # the same trace against steps of 7 calls a layer: no reading
    seven = _ctx(_fake_arch(7))
    assert _read("gpp_matmul_roofline", seven) is None
    # the attention count matches in both
    least = work.Work(1e6, 4e6).least_s(V5E.flops_per_s, V5E.hbm_bytes_per_s)
    for ctx in (nine, seven):
        assert _read("paged_attn_roofline", ctx) == pytest.approx(
            100.0 * least / (LAYERS * 1e-6))
