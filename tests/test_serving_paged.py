"""Paged-KV serving engine: greedy parity vs the seed dense-cache engine,
bounded compilation, deterministic sampling, preemption/defrag correctness."""
import numpy as np
import pytest

import jax

from repro.models import registry
from repro.models import transformer as tf
from repro.serving import (DenseServingEngine, ServeConfig, ServingEngine,
                           make_engine)

pytestmark = pytest.mark.tier1

# three attention families: GQA+bias+tied (qwen), sliding-window local:global
# + embed scaling (gemma3), MLA latent cache + MoE + dense prefix (deepseek)
PARITY_ARCHS = ("qwen1.5-0.5b", "gemma3-12b", "deepseek-v2-lite-16b")


@pytest.fixture(scope="module")
def setups():
    out = {}
    for arch in PARITY_ARCHS:
        cfg = registry.get_config(arch, smoke=True)
        out[arch] = (cfg, tf.init_params(cfg, jax.random.PRNGKey(0)))
    return out


def _prompts(cfg, n, lengths=(4, 9, 13, 5, 21)):
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg.vocab_size, size=l).tolist()
            for l in list(lengths)[:n]]


class TestGreedyParity:
    @pytest.mark.parametrize("arch", PARITY_ARCHS)
    def test_paged_logits_match_dense_path(self, setups, arch):
        """LOGITS-level parity of the paged model path (chunked prefill +
        block-table decode) against the dense prefill/decode path.  Token
        streams from smoke-scale random params degenerate to one repeated
        argmax, so token comparison alone is vacuous — this asserts the
        distributions themselves agree at every step."""
        import jax.numpy as jnp
        cfg, params = setups[arch]
        bs, chunk, max_len = 8, 8, 64
        mb = max_len // bs
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, cfg.vocab_size, size=13)
        toks = jnp.asarray(prompt[None], jnp.int32)

        logits_d, caches_d = tf.prefill(params, cfg, {"tokens": toks},
                                        max_len=max_len)

        specs = tf.paged_cache_specs(cfg, num_blocks=mb + 1, block_size=bs)
        caches_p = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
        table_row = jnp.arange(1, mb + 1, dtype=jnp.int32)[None]
        padded = 16
        for c0 in range(0, padded, chunk):
            ctoks = np.zeros(chunk, np.int32)
            real = prompt[c0 : min(len(prompt), c0 + chunk)]
            ctoks[: len(real)] = real
            last = len(prompt) - 1 - c0 if c0 + chunk >= padded else 0
            logits_p, caches_p = tf.prefill_chunk(
                params, cfg, jnp.asarray(ctoks[None]), caches_p, table_row,
                c0, last)
        np.testing.assert_allclose(
            np.asarray(logits_p, np.float32),
            np.asarray(logits_d[:, -1], np.float32), rtol=2e-2, atol=2e-2)

        tok = int(np.argmax(np.asarray(logits_p[0], np.float32)))
        pos = len(prompt)
        for _ in range(4):
            t = jnp.asarray([[tok]], jnp.int32)
            logits_d, caches_d = tf.decode_step(params, cfg, t, caches_d, pos)
            logits_p, caches_p = tf.decode_step_paged(
                params, cfg, t, caches_p, table_row,
                jnp.asarray([pos], jnp.int32), jnp.asarray([True]))
            np.testing.assert_allclose(
                np.asarray(logits_p, np.float32),
                np.asarray(logits_d, np.float32), rtol=2e-2, atol=2e-2)
            tok = int(np.argmax(np.asarray(logits_p[0, -1], np.float32)))
            pos += 1

    def test_dense_engine_heterogeneous_lanes_match_solo(self, setups):
        """Regression for the seed engine's per-pos-group decode clobbering
        other lanes' KV: a lane batched with a lane at a different position
        must produce the same stream as when served alone."""
        cfg, params = setups["qwen1.5-0.5b"]
        prompts = _prompts(cfg, 2, (4, 9))   # different lengths => different pos
        solo = []
        for p in prompts:
            eng = DenseServingEngine(cfg, params, ServeConfig(slots=1, max_len=64))
            rid = eng.submit(p, max_new_tokens=5)
            solo.append(eng.run()[rid])
        both = DenseServingEngine(cfg, params, ServeConfig(slots=2, max_len=64))
        rids = [both.submit(p, max_new_tokens=5) for p in prompts]
        res = both.run()
        assert [res[r] for r in rids] == solo

    @pytest.mark.parametrize("arch", PARITY_ARCHS)
    def test_token_for_token_vs_dense_engine(self, setups, arch):
        """Chunked-prefill + paged decode reproduce the seed engine's greedy
        outputs exactly, across heterogeneous prompt lengths."""
        cfg, params = setups[arch]
        paged = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=64, block_size=8, prefill_chunk=16))
        dense = DenseServingEngine(cfg, params, ServeConfig(slots=2, max_len=64))
        prompts = _prompts(cfg, 5)
        pr = [paged.submit(p, max_new_tokens=5) for p in prompts]
        dr = [dense.submit(p, max_new_tokens=5) for p in prompts]
        pres, dres = paged.run(), dense.run()
        for a, b in zip(pr, dr):
            assert pres[a] == dres[b]

    def test_single_token_request_parity(self, setups):
        """max_new_tokens=1 finishes on the prefill-sampled token in BOTH
        engines (the dense engine used to decode one extra)."""
        cfg, params = setups["qwen1.5-0.5b"]
        paged = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=64, block_size=8, prefill_chunk=16))
        dense = DenseServingEngine(cfg, params, ServeConfig(slots=2, max_len=64))
        p = _prompts(cfg, 1)[0]
        pr, dr = paged.submit(p, 1), dense.submit(p, 1)
        pres, dres = paged.run(), dense.run()
        assert len(pres[pr]) == len(dres[dr]) == 1
        assert pres[pr] == dres[dr]

    def test_matches_manual_decode(self, setups):
        """Paged engine output == hand-rolled dense prefill+decode loop."""
        import jax.numpy as jnp
        cfg, params = setups["qwen1.5-0.5b"]
        prompt = [3, 1, 4, 1, 5]
        eng = ServingEngine(cfg, params, ServeConfig(slots=1, max_len=64,
                                                     block_size=8,
                                                     prefill_chunk=8))
        rid = eng.submit(prompt, max_new_tokens=4)
        got = eng.run()[rid]

        toks = jnp.asarray([prompt], jnp.int32)
        logits, caches = tf.prefill(params, cfg, {"tokens": toks}, max_len=64)
        expect = [int(jnp.argmax(logits[0, -1]))]
        pos = len(prompt)
        for _ in range(3):
            logits, caches = tf.decode_step(
                params, cfg, jnp.asarray([[expect[-1]]], jnp.int32), caches, pos)
            expect.append(int(jnp.argmax(logits[0, -1])))
            pos += 1
        assert got == expect


class TestBoundedCompilation:
    def test_two_step_shapes_regardless_of_prompt_lengths(self, setups):
        """The re-jit fix: any mix of prompt lengths compiles exactly one
        chunked-prefill shape and one decode shape.  (The seed engine traced
        prefill once per distinct length — asserted as the contrast.)"""
        cfg, params = setups["qwen1.5-0.5b"]
        paged = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=64, block_size=8, prefill_chunk=16))
        lengths = (3, 5, 7, 9, 11, 14, 17, 21)
        for p in _prompts(cfg, len(lengths), lengths):
            paged.submit(p, max_new_tokens=3)
        paged.run()
        assert paged.trace_counts == {"prefill_chunk": 1, "decode": 1,
                                      "verify": 0}

        dense = DenseServingEngine(cfg, params, ServeConfig(slots=2, max_len=64))
        for p in _prompts(cfg, len(lengths), lengths):
            dense.submit(p, max_new_tokens=3)
        dense.run()
        assert dense.trace_counts["prefill"] == len(lengths)

    def test_flatness_beats_dense_engine(self, setups):
        cfg, params = setups["qwen1.5-0.5b"]
        serve = ServeConfig(slots=2, max_len=64, block_size=8, prefill_chunk=16)
        paged = ServingEngine(cfg, params, serve)
        dense = DenseServingEngine(cfg, params, serve)
        for eng in (paged, dense):
            for p in _prompts(cfg, 5, (20, 17, 22, 19, 21)):
                eng.submit(p, max_new_tokens=4)
            eng.run()
        assert paged.flatness_cov() < dense.flatness_cov()


class TestSampling:
    def test_temperature_stream_is_reproducible(self, setups):
        """Identical request streams + same ServeConfig.seed => identical
        outputs (per-lane keys fold (seed, rid, token_idx) — no shared
        state), regardless of slot count / interleaving."""
        cfg, params = setups["qwen1.5-0.5b"]
        prompts = _prompts(cfg, 4)

        def run(slots, seed):
            eng = ServingEngine(cfg, params, ServeConfig(
                slots=slots, max_len=64, block_size=8, prefill_chunk=16,
                temperature=0.8, seed=seed))
            rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
            res = eng.run()
            return [res[r] for r in rids]

        assert run(2, seed=7) == run(2, seed=7)
        # lane assignment / batching must not leak into sampling
        assert run(2, seed=7) == run(4, seed=7)
        assert run(2, seed=7) != run(2, seed=8)

    def test_eos_stops_lane(self, setups):
        cfg, params = setups["qwen1.5-0.5b"]
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=1, max_len=64, block_size=8, prefill_chunk=16))
        rid = eng.submit([1, 2, 3], max_new_tokens=8)
        greedy = eng.run()[rid]
        eos = greedy[1]
        eng2 = ServingEngine(cfg, params, ServeConfig(
            slots=1, max_len=64, block_size=8, prefill_chunk=16,
            eos_token=eos))
        rid2 = eng2.submit([1, 2, 3], max_new_tokens=8)
        out = eng2.run()[rid2]
        # seed-engine semantics: eos is included, lane stops at its first
        # occurrence in the greedy stream
        assert out == greedy[: greedy.index(eos) + 1]


class TestBlockPressure:
    def test_preemption_resume_preserves_greedy_outputs(self, setups):
        """A pool too small for both lanes forces preempt + recompute-resume;
        outputs still match the unconstrained engine token-for-token."""
        cfg, params = setups["qwen1.5-0.5b"]
        # r0 grows from 1 block (5-token prompt) to 3 blocks over 12 decode
        # steps; r1 holds 2 blocks — a 3-block pool forces r0's growth to
        # evict r1 mid-flight, which then resumes by recompute
        prompts = _prompts(cfg, 2, (5, 9))
        max_new = (12, 4)
        big = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=64, block_size=8, prefill_chunk=8))
        br = [big.submit(p, max_new_tokens=n) for p, n in zip(prompts, max_new)]
        bres = big.run()

        tight = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=64, block_size=8, prefill_chunk=8,
            num_blocks=4))   # 3 allocatable blocks = 24 token-slots shared
        tr = [tight.submit(p, max_new_tokens=n) for p, n in zip(prompts, max_new)]
        tres = tight.run()
        assert [tres[r] for r in tr] == [bres[r] for r in br]
        assert any(m["preempted"] for m in tight.metrics)

    def test_pool_too_small_raises(self, setups):
        cfg, params = setups["qwen1.5-0.5b"]
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=1, max_len=64, block_size=8, prefill_chunk=8,
            num_blocks=2))   # 1 allocatable block < one 16-token context
        eng.submit([1, 2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=4)
        with pytest.raises(RuntimeError):
            eng.run()

    def test_defragment_mid_stream_is_transparent(self, setups):
        cfg, params = setups["qwen1.5-0.5b"]
        prompts = _prompts(cfg, 3, (9, 5, 13))

        def run(defrag):
            eng = ServingEngine(cfg, params, ServeConfig(
                slots=2, max_len=64, block_size=8, prefill_chunk=8))
            rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
            steps = 0
            while eng.pending and steps < 500:
                eng.step()
                steps += 1
                if defrag and steps % 3 == 0:
                    eng.defragment()
            res = eng._results
            return [res[r] for r in rids]

        assert run(defrag=True) == run(defrag=False)


class TestEngineSelection:
    def test_recurrent_arch_falls_back_to_dense_engine(self):
        cfg = registry.get_config("xlstm-1.3b", smoke=True)
        params = tf.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError):
            ServingEngine(cfg, params, ServeConfig(slots=1, max_len=32))
        eng = make_engine(cfg, params, ServeConfig(slots=1, max_len=32))
        assert isinstance(eng, DenseServingEngine)
        rid = eng.submit([1, 2, 3], max_new_tokens=3)
        assert len(eng.run()[rid]) == 3

    def test_attention_arch_gets_paged_engine(self, setups):
        cfg, params = setups["qwen1.5-0.5b"]
        eng = make_engine(cfg, params, ServeConfig(slots=1, max_len=32))
        assert isinstance(eng, ServingEngine)

    def test_metrics_exported(self, setups):
        cfg, params = setups["qwen1.5-0.5b"]
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=64, block_size=8, prefill_chunk=16))
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)
        eng.run()
        assert eng.metrics
        keys = {"step", "tokens", "prefill_tokens", "decode_tokens",
                "blocks_in_use", "free_blocks", "queue_depth", "preempted",
                "hbm_bytes"}
        assert keys <= set(eng.metrics[0])
        assert all(m["hbm_bytes"] > 0 for m in eng.metrics)


class TestWindowReclamation:
    """Sliding-window block reclamation: an all-window (gemma3-local-style)
    stack frees blocks that fall behind the window, so blocks_in_use
    plateaus instead of growing with context — without changing outputs."""

    @pytest.fixture(scope="class")
    def allwin(self):
        cfg = registry.get_config("gemma3-12b", smoke=True).with_(
            pattern=("dense:window",) * 6)    # drop the global layers
        return cfg, tf.init_params(cfg, jax.random.PRNGKey(0))

    def test_blocks_plateau(self, allwin):
        cfg, params = allwin
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=1, max_len=64, block_size=8, prefill_chunk=8))
        assert eng.window_horizon == cfg.window_size == 16
        rid = eng.submit(list(range(1, 9)), max_new_tokens=40)
        out = eng.run()[rid]
        assert len(out) == 40
        peak = max(m["blocks_in_use"] for m in eng.metrics)
        # 48-token context = 6 blocks unreclaimed; window 16 needs <= 3 live
        # (2 visible + the write block)
        assert peak <= 3

    def test_outputs_match_dense_engine(self, allwin):
        """Reclamation must be invisible: the dense engine's ring-buffer
        window cache is the oracle."""
        cfg, params = allwin
        paged = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=64, block_size=8, prefill_chunk=16))
        dense = DenseServingEngine(cfg, params, ServeConfig(slots=2, max_len=64))
        prompts = _prompts(cfg, 4, (4, 9, 13, 21))
        pr = [paged.submit(p, max_new_tokens=20) for p in prompts]
        dr = [dense.submit(p, max_new_tokens=20) for p in prompts]
        pres, dres = paged.run(), dense.run()
        assert [pres[a] for a in pr] == [dres[b] for b in dr]
        assert any(m["blocks_in_use"] for m in paged.metrics)

    def test_full_attention_layer_disables_reclamation(self, setups):
        """gemma3 proper keeps its global layers -> shared tables cannot be
        reclaimed; qwen (no window at all) likewise."""
        for arch in ("gemma3-12b", "qwen1.5-0.5b"):
            cfg, params = setups[arch]
            eng = ServingEngine(cfg, params, ServeConfig(slots=1, max_len=64))
            assert eng.window_horizon is None


class TestAttnReadMetrics:
    def test_gather_vs_stream_bytes_exported(self, setups):
        cfg, params = setups["qwen1.5-0.5b"]
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=64, block_size=8, prefill_chunk=16))
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)
        eng.run()
        assert eng.paged_attn_mode in ("ref", "pallas", "interpret")
        for m in eng.metrics:
            assert m["attn_bytes_gather"] >= m["attn_bytes_stream"] > 0

    def test_paged_attn_kernel_override_threads_through(self, setups):
        """ServeConfig.paged_attn_kernel overrides cfg, token streams are
        unchanged, and the two jitted step shapes stay at two."""
        cfg, params = setups["qwen1.5-0.5b"]
        prompts = _prompts(cfg, 3, (4, 9, 13))

        def run(mode):
            eng = ServingEngine(cfg, params, ServeConfig(
                slots=2, max_len=64, block_size=8, prefill_chunk=16,
                paged_attn_kernel=mode))
            rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
            res = eng.run()
            return [res[r] for r in rids], eng

        ref_streams, ref_eng = run("ref")
        ker_streams, ker_eng = run("interpret")
        assert ref_eng.paged_attn_mode == "ref"
        assert ker_eng.paged_attn_mode == "interpret"
        assert ker_streams == ref_streams
        assert ker_eng.trace_counts == {"prefill_chunk": 1, "decode": 1,
                                        "verify": 0}


class TestGppPadBytes:
    def test_served_weights_pad_only_where_no_lane_tile_divides(self):
        """At published widths, the kernels' planned tiles divide every
        weight of qwen2-7b and qwen1.5-0.5b.  DeepSeek-V2-Lite pads those
        whose lane dim is no 128-lane multiple, to the next one: w_dkv
        (576 -> 640 columns) in each of 5 layers, the dense layer's gate
        and up (10944 -> 11008), and, forced onto the kernel, each MoE
        layer's f32 router (64 -> 128).  Routed to the reference, as auto
        routes on the CPU, nothing is padded."""
        from repro.configs.deepseek_v2_lite_16b import CONFIG as DEEPSEEK
        from repro.configs.qwen1_5_0_5b import CONFIG as QWEN_05B
        from repro.configs.qwen2_7b import CONFIG as QWEN2_7B
        for cfg, rows in ((QWEN2_7B.with_(num_layers=14), 16),
                          (QWEN_05B, 4)):
            assert tf.served_gpp_pad_bytes(
                cfg.with_(dense_kernel="interpret"), rows) == 0
        ds = DEEPSEEK.with_(num_layers=5)
        want = (5 * 2048 * 640 * 2 + 2 * 2048 * 11008 * 2
                + 4 * 2048 * 128 * 4)
        for rows in (32, 1):
            assert tf.served_gpp_pad_bytes(
                ds.with_(dense_kernel="interpret"), rows) == want
        assert tf.served_gpp_pad_bytes(ds, 32) == 0

    def test_ledger_rows_carry_each_programs_pad_bytes(self, setups,
                                                       monkeypatch):
        """A step's `gpp_pad_bytes` adds the count of each program it
        launched: the prefill chunk's (16 rows) and the decode call's (2
        lanes)."""
        monkeypatch.setattr(tf, "served_gpp_pad_bytes",
                            lambda cfg, rows: 1000 * rows + 1)
        cfg, params = setups["qwen1.5-0.5b"]
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=64, block_size=8, prefill_chunk=16))
        for p in _prompts(cfg, 2, (9, 21)):
            eng.submit(p, max_new_tokens=3)
        eng.run()
        both = 0
        for m in eng.metrics:
            want = ((16_001 if m["prefill_tokens"] else 0)
                    + (2_001 if m["decode_tokens"] else 0))
            assert m["gpp_pad_bytes"] == want
            both += bool(m["prefill_tokens"] and m["decode_tokens"])
        assert both
