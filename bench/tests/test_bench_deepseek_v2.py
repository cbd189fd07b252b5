"""DeepSeek-V2-Lite in the harness: the engine against the float32
reference at smoke width on the CPU (chunked prefill, then paged decode,
both kernels in interpret mode), the architecture module's step work for
the 5-layer configuration, the ledger's expert-row counters, and the two
readers this architecture adds."""
import types

import jax
import numpy as np
import pytest

from bench import model, peaks, run, work
from bench import trace_reduce as tr
from bench.references import deepseek_v2 as ref

NAME = "deepseek-v2-lite-5L"
V5E = peaks.peaks_for("TPU v5 lite")
# The harness draws projections at std 0.02, which at d 2048 gives
# activations and attention logits of order 1; at the smoke width of 128 it
# leaves attention near uniform, so rope, YaRN and the MLA scale would
# hardly move the logits.  The tests draw every projection 4x (sqrt(2048 /
# 128)) as wide, and then a rotate-half pairing, no YaRN, no mscale^2 or a
# renormalised top-k each put the program 32-117% of the largest logit
# away from the reference (seeds 3-4).
PROJ_GAIN = 4.0
# The program computes in bf16 (weights, activations and the latent pools;
# f32 accumulation and routing), the reference in float32 on the same bf16
# weights.  Over three layers the logits then differ by bf16 rounding of
# the activations: on seeds 3-7 the largest difference was 1.3-2.1% of the
# largest logit.  4% leaves about twice that, and stays far below the
# 18-31% by which the reference's own fp8 control differs on the same
# seeds (test_fp8_control_is_farther).
REL_TOL = 4e-2
PROMPT, NEW = 70, 6          # three 32-token chunks, the last one partial


def _smoke(seed):
    from repro.models import transformer as tf
    hf = model.load_config(NAME)
    arch = model.arch_for(hf)
    hf = arch.smoke_hf(hf)
    cfg = arch.model_config(NAME, hf)
    params = model.make_weights(tf.param_specs(cfg), seed)
    wider = {g: jax.tree_util.tree_map_with_path(
        lambda path, a: (a * PROJ_GAIN if str(path[-1].key).startswith("w_")
                         else a), params[g]) for g in ("prefix", "blocks")}
    return hf, cfg, {**params, **wider}


def _serve(seed):
    """(hf, params, prompt, served tokens, the program's logits at each
    served position, the engine's ledger rows)."""
    from repro.serving import ServeConfig, make_engine
    hf, cfg, params = _smoke(seed)
    engine = make_engine(cfg, params, ServeConfig(
        slots=2, max_len=128, dense_kernel="interpret",
        paged_attn_kernel="interpret"))
    seen = []
    prefill, decode = engine._prefill, engine._decode

    def keep_prefill(*a):
        out = prefill(*a)
        seen.append(np.asarray(out[0], np.float32)[0])
        return out

    def keep_decode(*a):
        out = decode(*a)
        seen.append(np.asarray(out[0], np.float32)[0, 0])
        return out

    engine._prefill, engine._decode = keep_prefill, keep_decode
    prompt = np.random.default_rng(seed).integers(0, cfg.vocab_size, PROMPT)
    rid = engine.submit(prompt.tolist(), NEW)
    out = engine.run()[rid]
    # the last prefill chunk's logits, then each decode step's (lane 0)
    got = np.stack([seen[2]] + seen[3:3 + NEW - 1])
    return hf, params, prompt, np.asarray(out), got, list(engine.metrics)


def _reference(hf, params, prompt, out, precision="f32"):
    toks = np.concatenate([prompt, out[:-1]])
    pos = np.arange(PROMPT - 1, len(toks))
    return ref.logits(hf, params, toks, pos, precision=precision)


def test_engine_matches_reference():
    hf, params, prompt, out, got, _ = _serve(3)
    want = _reference(hf, params, prompt, out)
    assert got.shape == want.shape
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel <= REL_TOL, rel
    # greedy: each served token is the program's argmax
    assert (out == got.argmax(-1)).all()


def test_fp8_control_is_farther():
    hf, params, prompt, out, got, _ = _serve(4)
    want = _reference(hf, params, prompt, out)
    low = _reference(hf, params, prompt, out, precision="fp8")
    prog = np.abs(got - want).max() / np.abs(want).max()
    ctrl = np.abs(low - want).max() / np.abs(want).max()
    assert ctrl > REL_TOL > prog
    assert ctrl > 4 * prog


def test_ledger_counts_the_expert_rows():
    """Each step's rows routed are its real tokens x experts per token x
    MoE layers; its rows computed are MoE layers x experts x the call's
    tokens (dropless: groups x capacity is every token of the call), for
    the 32-row prefill chunks and the 2-lane decode calls alike."""
    hf, *_, rows = _serve(5)
    k, E = hf["num_experts_per_tok"], hf["n_routed_experts"]
    L = hf["num_hidden_layers"] - hf["first_k_dense_replace"]
    assert len(rows) == 3 + NEW - 1
    for r in rows:
        real = r["prefill_real_tokens"] + r["decode_tokens"]
        calls = r["prefill_tokens"] + (2 if r["decode_tokens"] else 0)
        assert r["moe_rows_routed"] == L * k * real
        assert r["moe_rows_computed"] == L * E * calls
    assert [r["prefill_real_tokens"] for r in rows[:3]] == [32, 32, 6]


def test_model_config_refuses_what_it_cannot_map():
    arch = model.arch_for(model.load_config(NAME))
    hf = model.load_config(NAME)
    for key, value in [("n_group", 8), ("scoring_func", "sigmoid"),
                       ("q_lora_rank", 1536), ("v_head_dim", 64),
                       ("routed_scaling_factor", 16.0)]:
        with pytest.raises(ValueError, match=key):
            arch.model_config(NAME, dict(hf, **{key: value}))


def test_smoke_width_keeps_the_architecture():
    hf, cfg, _ = _smoke(1)
    assert cfg.kv_lora_rank and cfg.q_lora_rank is None
    assert cfg.prefix_pattern == ("dense",) and cfg.num_superblocks >= 2
    assert cfg.num_experts >= 8 and cfg.experts_per_token >= 2
    assert cfg.num_shared_experts == 2 and cfg.rope_scaling is not None
    assert cfg.rope_interleave and not cfg.moe_norm_topk_prob


def test_kernel_weights_are_found_in_the_blocks():
    """The routing check's leaves all exist under the MoE blocks."""
    from repro.models import transformer as tf
    _, cfg, _ = _smoke(1)
    arch = model.arch_for(model.load_config(NAME))
    specs = tf.param_specs(cfg)
    names = {str(p[-1].key) for p, _ in
             jax.tree_util.tree_leaves_with_path(specs["blocks"])}
    assert arch.KERNEL_WEIGHTS <= names
    assert "router" in names and "router" not in arch.KERNEL_WEIGHTS


# ------------------------------------------------------------- step work

SLOTS = 32
LANES = [1, 17, 255, 256, 1000, 2047]

# The 5-layer configuration's work, counted by hand from config.json:
# attention 13.76 M parameters a layer (q 2048 x 16 x 192, the latent
# 2048 x 576, w_uk and w_uv 512 x 16 x 128 each, o 2048 x 2048), the dense
# layer's MLP 3 x 2048 x 10944, each MoE layer's router 2048 x 64 and
# 6 routed + 2 shared experts of 3 x 2048 x 1408, the head 102400 x 2048.
ACTIVATED = (5 * (2048 * 16 * 192 + 2048 * 576 + 2 * 512 * 16 * 128
                  + 16 * 128 * 2048) + 3 * 2048 * 10944
             + 4 * (2048 * 64 + 8 * 3 * 2048 * 1408) + 102400 * 2048)


def _shape():
    hf = model.load_config(NAME)
    arch = model.arch_for(hf)
    return arch, arch.Shape.from_hf(hf)


def test_pinned_sizes():
    _, shape = _shape()
    assert shape.activated_params() == ACTIVATED == 623_116_288
    # every expert of the four MoE layers, on top
    assert shape.streamed_params() == ACTIVATED + 4 * 58 * 3 * 2048 * 1408
    # c_kv 512 lanes and k_rope padded from 64 to 128, bf16, 5 layers
    assert shape.kv_bytes_per_token() == 6400


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_pinned_step_counts(step):
    arch, shape = _shape()
    w = (arch.decode_step(shape, SLOTS, LANES) if step == "decode"
         else arch.prefill_chunk(shape, 32, 3200, 20))
    # q, latent, o and three MLP projections a layer; gate, up and down of
    # the routed experts in each of 4 MoE layers; one attention call a layer
    assert (w["gpp_calls"], w["grouped_calls"], w["attn_calls"]) == (30, 12, 5)
    tokens, attn = ((len(LANES), arch.attn_calls(shape, LANES, 1))
                    if step == "decode"
                    else (20, arch.attn_calls(shape, [3220], 20)))
    assert w["model_flops"] == 2.0 * ACTIVATED * tokens + attn.flops


def test_grouped_work_streams_every_expert():
    """A decode step's grouped calls read all 64 experts' weights of the 4
    MoE layers, and compute only the routed rows: 32 lanes x 6 experts."""
    arch, shape = _shape()
    g = arch.decode_step(shape, SLOTS, LANES)["grouped"]
    weights = 4 * 64 * 3 * 2048 * 1408 * 2
    acts = 4 * 64 * SLOTS * (2048 + 1408) * 2 * 3
    assert g.bytes == weights + acts
    assert g.flops == 4 * 3 * 2.0 * SLOTS * 6 * 2048 * 1408
    # bytes bound: 4.43 GB at 819 GB/s
    assert g.least_s(V5E.flops_per_s, V5E.hbm_bytes_per_s) == pytest.approx(
        (weights + acts) / 819e9)


def test_attention_reads_one_latent_row_a_token():
    arch, shape = _shape()
    a = arch.attn_calls(shape, [100, 1], 1)
    # 101 latent rows of 512 + 64 lanes, q (576) and out (512) of 16 heads
    # for each of the 2 lanes, bf16, 5 layers
    assert a.bytes == 5 * 2 * (101 * 576 + 2 * 16 * (576 + 512))
    assert a.flops == 5 * 2.0 * 16 * (576 + 512) * 101


# --------------------------------------------------------------- readers

GROUPED_OP = ('%gpp_matmul_grouped.3 = bf16[64,32,1408]{2,1,0} custom-call('
              'bf16[64,32,2048]{2,1,0} %x.1, bf16[64,2048,1408]{2,1,0} '
              '%w.1), custom_call_target="tpu_custom_call"')


def _ctx(calls_per_step, rows=()):
    def step(*_):
        return {"gpp": work.Work(), "attn": work.Work(),
                "grouped": work.Work(1e9, 1e9), "gpp_calls": 0,
                "attn_calls": 0, "grouped_calls": calls_per_step,
                "model_flops": 0.0}
    arch = types.SimpleNamespace(decode_step=step, prefill_chunk=step)
    ops = [tr.Event(GROUPED_OP, 1000 * i, 1_000_000) for i in range(12)]
    return run.Context(
        due={}, tokens={}, t0=0.0, t_end=1.0, setup_s=0.0, rows=list(rows),
        steps=[{"prefill": None, "decode": [5, 9]}], arch=arch, shape=None,
        slots=4, peaks=V5E, memory_peak_bytes=0, traced=(0.0, 1.0, 0, 1),
        trace=tr.Trace({"/device:TPU:0": ops}, {}, []))


def _read(metric, ctx):
    return model.load_module(run.BENCH / "metrics" / f"{metric}.py").read(ctx)


def test_grouped_roofline_reads_the_named_kernel():
    assert tr.kernel_of(GROUPED_OP) == "gpp_matmul_grouped"
    least = work.Work(1e9, 1e9).least_s(V5E.flops_per_s, V5E.hbm_bytes_per_s)
    assert _read("gpp_matmul_grouped_roofline", _ctx(12)) == pytest.approx(
        100.0 * least / (12 * 1e-3))
    # the steps count another number of calls: no reading
    assert _read("gpp_matmul_grouped_roofline", _ctx(9)) is None


def test_row_fill_is_routed_over_computed():
    rows = [{"moe_rows_routed": 48, "moe_rows_computed": 512},
            {"moe_rows_routed": 0, "moe_rows_computed": 0},
            {"moe_rows_routed": 16, "moe_rows_computed": 512}]
    assert _read("moe_row_fill", _ctx(12, rows)) == pytest.approx(6.25)
    # an engine without the counters, or without MoE layers
    assert _read("moe_row_fill", _ctx(12, [{"tokens": 3}])) is None
    assert _read("moe_row_fill", _ctx(12, rows[1:2])) is None
