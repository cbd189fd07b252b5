"""Unified dense() routing parity: every model projection now flows through
`kernels.ops.dense` / `dense_grouped` — these tests pin the refactor to the
seed einsum math.

Each *oracle* below is a line-for-line copy of the pre-refactor (seed)
einsum implementation of that block's projections.  With dense_mode="ref"
the refactored module must reproduce the oracle's forward outputs AND
gradients to f32 accumulation tolerance, for every model kind in
models/registry.py (mha, gqa, mla, moe, ssm, mlstm, slstm, cross-attn).

Also covered: the einsum-shaped projection adapter itself, interpret-mode
kernel parity for `dense_grouped` at a ragged expert-capacity shape, and
the TimingCache feedback into `plan_matmul_tiles`.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.schedule import (
    TimingCache, plan_matmul_tiles, set_default_timing_cache,
)
from repro.kernels.gpp_matmul import gpp_matmul_grouped
from repro.kernels.ops import dense, dense_grouped
from repro.kernels.ref import dense_grouped_ref
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import init_from_specs, rmsnorm, rope

pytestmark = pytest.mark.tier1

KEY = jax.random.PRNGKey(7)
B, S, D = 2, 16, 32
TOL = dict(rtol=2e-5, atol=2e-5)   # f32 accumulation tolerance
GTOL = dict(rtol=1e-4, atol=1e-5)


def init(specs, key=KEY):
    return init_from_specs(specs, key)


def seq_input(d=D, s=S, key=KEY):
    return jax.random.normal(key, (B, s, d), jnp.float32) * 0.5


def assert_fwd_and_grad(fn_new, fn_oracle, params, x):
    y_new, y_old = fn_new(params, x), fn_oracle(params, x)
    np.testing.assert_allclose(np.asarray(y_new), np.asarray(y_old), **TOL)
    g_new = jax.grad(lambda p: (fn_new(p, x) ** 2).mean())(params)
    g_old = jax.grad(lambda p: (fn_oracle(p, x) ** 2).mean())(params)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(g_new)[0],
            jax.tree_util.tree_flatten_with_path(g_old)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   err_msg=str(path), **GTOL)


# ---------------------------------------------------------------------------
# the einsum-shaped projection adapter
# ---------------------------------------------------------------------------

class TestProjectionAdapter:
    def test_dhk_weight(self):
        x = seq_input()
        w = jax.random.normal(KEY, (D, 4, 8), jnp.float32)
        got = dense(x, w, mode="ref")
        want = jnp.einsum("bsd,dhk->bshk", x, w)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_hkd_weight_contract2(self):
        x = jax.random.normal(KEY, (B, S, 4, 8), jnp.float32)
        w = jax.random.normal(KEY, (4, 8, D), jnp.float32)
        got = dense(x, w, mode="ref", contract_dims=2)
        want = jnp.einsum("bshk,hkd->bsd", x, w)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_bias_matches_post_add(self):
        x = seq_input()
        w = jax.random.normal(KEY, (D, 4, 8), jnp.float32)
        b = jax.random.normal(KEY, (4, 8), jnp.float32)
        got = dense(x, w, bias=b, mode="ref")
        want = jnp.einsum("bsd,dhk->bshk", x, w) + b
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)

    def test_2d_x_leading_dims(self):
        x = jax.random.normal(KEY, (B, D), jnp.float32)
        w = jax.random.normal(KEY, (D, 4, 8), jnp.float32)
        got = dense(x, w, mode="ref")
        want = jnp.einsum("bd,dhk->bhk", x, w)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_shape_mismatch_raises(self):
        x = seq_input()
        w = jax.random.normal(KEY, (4, 8, D), jnp.float32)
        with pytest.raises(ValueError, match="contraction mismatch"):
            dense(x, w, mode="ref")

    def test_interpret_kernel_matches_ref_on_projection(self):
        x = seq_input()
        w = jax.random.normal(KEY, (D, 4, 8), jnp.float32)
        got = dense(x, w, mode="interpret")
        want = dense(x, w, mode="ref")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# attention: mha / gqa (+bias) / mla / cross — vs seed einsum oracles
# ---------------------------------------------------------------------------

def _gqa_oracle(p, c, x, pos):
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
    k = jnp.einsum("bsd,dgk->bsgk", x, p["w_k"])
    v = jnp.einsum("bsd,dgk->bsgk", x, p["w_v"])
    if c.qkv_bias:
        q = q + p["b_q"].astype(q.dtype)
        k = k + p["b_k"].astype(k.dtype)
        v = v + p["b_v"].astype(v.dtype)
    q = rope(q, pos, c.rope_theta)
    k = rope(k, pos, c.rope_theta)
    out = attn._attend(q, k, v, 1.0 / math.sqrt(c.head_dim), window=c.window)
    return jnp.einsum("bshk,hkd->bsd", out, p["w_o"])


@pytest.mark.parametrize("kv_heads,bias", [(4, False), (2, False), (2, True)],
                         ids=["mha", "gqa", "gqa_bias"])
def test_gqa_parity(kv_heads, bias):
    c = attn.AttnConfig(d_model=D, num_heads=4, num_kv_heads=kv_heads,
                        head_dim=8, qkv_bias=bias, dtype=jnp.float32,
                        dense_mode="ref")
    p = init(attn.attn_specs(c))
    x = seq_input()
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    assert_fwd_and_grad(lambda p, x: attn.gqa_forward(p, c, x, pos),
                        lambda p, x: _gqa_oracle(p, c, x, pos), p, x)


def _mla_oracle(p, c, x, pos):
    nope = c.head_dim
    if c.q_lora_rank:
        cq = rmsnorm(p["q_norm"], x @ p["w_dq"])
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q = jnp.concatenate([q_nope, rope(q_rope, pos, c.rope_theta)], axis=-1)
    d = x @ p["w_dkv"]
    c_kv, k_rope = d[..., : c.kv_lora_rank], d[..., c.kv_lora_rank:]
    c_kv = rmsnorm(p["kv_norm"], c_kv)
    k_rope = rope(k_rope[..., None, :], pos, c.rope_theta)[..., 0, :]
    k_nope = jnp.einsum("btr,rhk->bthk", c_kv, p["w_uk"])
    v = jnp.einsum("btr,rhk->bthk", c_kv, p["w_uv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (*k_nope.shape[:3], c.rope_head_dim))], axis=-1)
    out = attn._sdpa_chunked(q, k, v, 1.0 / math.sqrt(nope + c.rope_head_dim))
    return jnp.einsum("bshk,hkd->bsd", out, p["w_o"])


@pytest.mark.parametrize("q_lora", [None, 12], ids=["mla", "mla_qlora"])
def test_mla_parity(q_lora):
    c = attn.AttnConfig(d_model=D, num_heads=4, num_kv_heads=4, head_dim=8,
                        kv_lora_rank=16, q_lora_rank=q_lora, rope_head_dim=4,
                        dtype=jnp.float32, dense_mode="ref")
    p = init(attn.attn_specs(c))
    x = seq_input()
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    assert_fwd_and_grad(lambda p, x: attn.mla_forward(p, c, x, pos),
                        lambda p, x: _mla_oracle(p, c, x, pos), p, x)


def _cross_oracle(p, c, x, enc):
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
    k = jnp.einsum("btd,dgk->btgk", enc, p["w_k"])
    v = jnp.einsum("btd,dgk->btgk", enc, p["w_v"])
    q = rmsnorm({"scale": p["q_norm"]}, q)
    k = rmsnorm({"scale": p["k_norm"]}, k)
    mask = jnp.ones((x.shape[0], x.shape[1], enc.shape[1]), bool)
    out = attn._sdpa(q, k, v, mask, 1.0 / math.sqrt(c.head_dim))
    return jnp.einsum("bshk,hkd->bsd", out, p["w_o"])


def test_cross_attn_parity():
    c = attn.AttnConfig(d_model=D, num_heads=4, num_kv_heads=4, head_dim=8,
                        dtype=jnp.float32, dense_mode="ref")
    p = init(attn.cross_attn_specs(c))
    x = seq_input()
    enc = seq_input(s=8, key=jax.random.PRNGKey(9))
    assert_fwd_and_grad(lambda p, x: attn.cross_attn_forward(p, c, x, enc),
                        lambda p, x: _cross_oracle(p, c, x, enc), p, x)


# ---------------------------------------------------------------------------
# MoE — vs the seed batched-einsum expert FFN + raw router matmul
# ---------------------------------------------------------------------------

def _moe_oracle(p, c, x):
    """Seed moe_apply (no-mesh grouped path) with raw einsums throughout."""
    B_, S_, D_ = x.shape
    T = B_ * S_
    G = moe_mod._dispatch_groups(c, T)
    Tg = T // G
    C = moe_mod.capacity(c, Tg)
    xg = x.reshape(G, Tg, D_)

    def dispatch(xt):
        k, E = c.experts_per_token, c.num_experts
        logits = xt.astype(c.router_dtype) @ p["router"].astype(c.router_dtype)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        flat_e = top_e.reshape(-1)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        grp_start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
        slot = jnp.arange(Tg * k) - grp_start[sorted_e]
        keep = slot < C
        token_idx = order // k
        buf = jnp.zeros((E, C, D_), xt.dtype)
        buf = buf.at[sorted_e, jnp.where(keep, slot, 0)].add(
            jnp.where(keep[:, None], xt[token_idx], 0).astype(xt.dtype))
        w = top_p.reshape(-1)[order]
        return buf, (sorted_e, slot, keep, token_idx, w)

    buf, meta = jax.vmap(dispatch)(xg)
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, wg))
    h = h * jnp.einsum("gecd,edf->gecf", buf, wu)
    out_buf = jnp.einsum("gecf,efd->gecd", h, wd)
    out = jax.vmap(lambda ob, m: moe_mod._combine(ob, m, Tg, x.dtype))(out_buf, meta)
    out = out.reshape(B_, S_, D_)
    if c.num_shared_experts:
        xt = x.reshape(T, D_)
        sh = p["shared"]
        hs = jax.nn.silu(xt @ sh["w_gate"]) * (xt @ sh["w_up"])
        out = out + (hs @ sh["w_down"]).reshape(B_, S_, D_)
    return out


@pytest.mark.parametrize("shared", [0, 1], ids=["moe", "moe_shared"])
def test_moe_parity(shared):
    c = moe_mod.MoeConfig(d_model=D, d_ff=24, num_experts=8,
                          experts_per_token=2, capacity_factor=8.0,
                          num_shared_experts=shared, dtype=jnp.float32,
                          dispatch_groups=4, dense_kernel="ref")
    p = init(moe_mod.moe_specs(c))
    x = seq_input()
    assert_fwd_and_grad(lambda p, x: moe_mod.moe_apply(p, c, x),
                        lambda p, x: _moe_oracle(p, c, x), p, x)


# ---------------------------------------------------------------------------
# SSM — vs seed raw-matmul projections
# ---------------------------------------------------------------------------

def _ssm_oracle(p, c, u):
    """Seed ssm_forward: _ssd_chunked with raw @-projections."""
    import repro.models.ssm as S_

    B_, S_len, _ = u.shape
    H, P_, N = c.n_heads, c.head_dim, c.d_state
    xz = u @ p["w_in"]
    x, z = jnp.split(xz, 2, axis=-1)
    x = S_._conv1d_causal(x, p["conv_w"])
    x = jax.nn.silu(x)
    bc = u @ p["w_bc"]
    Bm, Cm = jnp.split(bc, 2, axis=-1)
    dt = jax.nn.softplus((u @ p["w_dt"]).astype(jnp.float32) + p["dt_bias"])
    a = jnp.exp(-dt * jnp.exp(p["A_log"]))

    Lc = min(S_.SSD_CHUNK, S_len)
    nc = S_len // Lc
    xh = x.reshape(B_, S_len, H, P_).astype(jnp.float32)
    loga = jnp.log(jnp.maximum(a, 1e-30))

    def resh(t):
        return t.reshape(B_, nc, Lc, *t.shape[2:]).swapaxes(0, 1)

    xs, Bs, Cs, dts, logas = map(resh, (
        xh, Bm.astype(jnp.float32), Cm.astype(jnp.float32), dt, loga))
    s0 = jnp.zeros((B_, H, P_, N), jnp.float32)

    def step(s_prev, inp):
        xc, bc_, cc, dtc, lac = inp
        A = jnp.cumsum(lac, axis=1)
        decay = A[:, :, None, :] - A[:, None, :, :]
        causal = jnp.tril(jnp.ones((Lc, Lc), bool))
        decay = jnp.where(causal[None, :, :, None], decay, -jnp.inf)
        gates = jnp.exp(decay) * dtc[:, None, :, :]
        scores = jnp.einsum("btn,bsn->bts", cc, bc_)
        w = gates * scores[..., None]
        y_intra = jnp.einsum("btsh,bshp->bthp", w, xc)
        y_inter = jnp.exp(A)[..., None] * jnp.einsum("btn,bhpn->bthp", cc, s_prev)
        wA = jnp.exp(A[:, -1:, :] - A) * dtc
        s_new = (s_prev * jnp.exp(A[:, -1])[..., None, None]
                 + jnp.einsum("bsh,bshp,bsn->bhpn", wA, xc, bc_))
        return s_new, y_intra + y_inter

    _, ys = jax.lax.scan(step, s0, (xs, Bs, Cs, dts, logas))
    y = ys.swapaxes(0, 1).reshape(B_, S_len, H, P_)
    y = y + p["D"][None, None, :, None] * xh
    y = y.reshape(B_, S_len, H * P_).astype(u.dtype)
    y = y * jax.nn.silu(z)
    return y @ p["w_out"]


def test_ssm_parity():
    c = ssm_mod.SsmConfig(d_model=D, d_inner=2 * D, d_state=8, n_heads=4,
                          dtype=jnp.float32, dense_mode="ref")
    p = init(ssm_mod.ssm_specs(c))
    x = seq_input()
    assert_fwd_and_grad(lambda p, x: ssm_mod.ssm_forward(p, c, x),
                        lambda p, x: _ssm_oracle(p, c, x), p, x)


# ---------------------------------------------------------------------------
# xLSTM — vs seed einsum projections
# ---------------------------------------------------------------------------

def _mlstm_oracle(p, c, x):
    hd = c.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"]).astype(jnp.float32)
    k = jnp.einsum("bsd,dhk->bshk", x, p["w_k"]).astype(jnp.float32) / (hd ** 0.5)
    v = jnp.einsum("bsd,dhk->bshk", x, p["w_v"]).astype(jnp.float32)
    i = (x @ p["w_i"]).astype(jnp.float32) + p["b_i"]
    f = (x @ p["w_f"]).astype(jnp.float32) + p["b_f"]
    logf = -jax.nn.softplus(-f)

    import repro.models.xlstm as X_
    B_ = x.shape[0]
    orig_qkv, orig_gates = X_._mlstm_qkv, X_._mlstm_gates
    X_._mlstm_qkv = lambda *_: (q, k, v)
    X_._mlstm_gates = lambda *_: (i, logf)
    try:
        hid, _ = X_._mlstm_chunk_scan(p, c, x, X_._mlstm_state0(c, B_))
    finally:
        X_._mlstm_qkv, X_._mlstm_gates = orig_qkv, orig_gates
    o = jax.nn.sigmoid(x @ p["ogate"])
    y = jnp.einsum("bthk,hkd->btd", hid.astype(x.dtype), p["w_o"])
    return y * o


def test_mlstm_parity():
    c = xlstm_mod.XlstmConfig(d_model=D, n_heads=4, dtype=jnp.float32,
                              dense_mode="ref")
    p = init(xlstm_mod.mlstm_specs(c))
    x = seq_input()
    assert_fwd_and_grad(lambda p, x: xlstm_mod.mlstm_forward(p, c, x),
                        lambda p, x: _mlstm_oracle(p, c, x), p, x)


def _slstm_oracle(p, c, x):
    B_, S_len, D_ = x.shape
    z = jnp.tanh((x @ p["w_z"]).astype(jnp.float32)).reshape(
        B_, S_len, c.n_heads, c.head_dim)
    i = (x @ p["w_i"]).astype(jnp.float32) + p["b_i"]
    f = (x @ p["w_f"]).astype(jnp.float32) + p["b_f"]
    logf = -jax.nn.softplus(-f)
    og = jax.nn.sigmoid(x @ p["w_og"])
    state0 = {
        "c": jnp.zeros((B_, c.n_heads, c.head_dim), jnp.float32),
        "n": jnp.zeros((B_, c.n_heads), jnp.float32),
        "m": jnp.full((B_, c.n_heads), -1e30, jnp.float32),
    }

    def step(st, xs):
        return xlstm_mod._slstm_step(p, c, st, xs)

    _, hs = jax.lax.scan(
        step, state0,
        (z.swapaxes(0, 1), i.swapaxes(0, 1), logf.swapaxes(0, 1),
         jnp.zeros((S_len, 1), jnp.float32)))
    h = hs.swapaxes(0, 1).reshape(B_, S_len, D_).astype(x.dtype)
    return (h * og) @ p["w_out"]


def test_slstm_parity():
    c = xlstm_mod.XlstmConfig(d_model=D, n_heads=4, dtype=jnp.float32,
                              dense_mode="ref")
    p = init(xlstm_mod.slstm_specs(c))
    x = seq_input()
    assert_fwd_and_grad(lambda p, x: xlstm_mod.slstm_forward(p, c, x),
                        lambda p, x: _slstm_oracle(p, c, x), p, x)


# ---------------------------------------------------------------------------
# dense_grouped: interpret-mode kernel parity at ragged expert-capacity
# ---------------------------------------------------------------------------

class TestDenseGrouped:
    def test_ragged_capacity_interpret_matches_oracle(self):
        """C=13 / F=40 don't divide any tile size: zero-padding + expert-ring
        schedule must still match the batched-einsum oracle."""
        E, C, D_, F = 4, 13, 24, 40
        k1, k2, k3 = jax.random.split(KEY, 3)
        x = jax.random.normal(k1, (E, C, D_), jnp.float32)
        w = jax.random.normal(k2, (E, D_, F), jnp.float32)
        b = jax.random.normal(k3, (E, F), jnp.float32)
        got = dense_grouped(x, w, bias=b, activation="silu", mode="interpret")
        want = dense_grouped_ref(x, w, bias=b, activation="silu")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_multi_tile_expert_ring(self):
        """Pinned small tiles force a multi-step grid so the ring pipelines
        across expert boundaries (the outer ring dimension)."""
        E, C, D_, F = 3, 17, 48, 256
        k1, k2 = jax.random.split(KEY)
        x = jax.random.normal(k1, (E, C, D_), jnp.float32)
        w = jax.random.normal(k2, (E, D_, F), jnp.float32)
        got = gpp_matmul_grouped(x, w, block_m=8, block_n=128, block_k=16,
                                 num_bufs=3, interpret=True)
        want = dense_grouped_ref(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_insitu_and_naive_rings(self):
        E, C, D_, F = 2, 8, 16, 128
        k1, k2 = jax.random.split(KEY)
        x = jax.random.normal(k1, (E, C, D_), jnp.float32)
        w = jax.random.normal(k2, (E, D_, F), jnp.float32)
        want = dense_grouped_ref(x, w)
        for G in (1, 2):
            got = gpp_matmul_grouped(x, w, num_bufs=G, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5, err_msg=f"G={G}")

    def test_kernel_path_gradients_match_ref(self):
        E, C, D_, F = 4, 13, 24, 40
        k1, k2 = jax.random.split(KEY)
        x = jax.random.normal(k1, (E, C, D_), jnp.float32)
        w = jax.random.normal(k2, (E, D_, F), jnp.float32)

        def loss(mode):
            return lambda xx, ww: (
                dense_grouped(xx, ww, activation="silu", mode=mode) ** 2).mean()

        gx_k, gw_k = jax.grad(loss("interpret"), argnums=(0, 1))(x, w)
        gx_r, gw_r = jax.grad(loss("ref"), argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx_k), np.asarray(gx_r),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw_k), np.asarray(gw_r),
                                   rtol=1e-5, atol=1e-5)

    def test_shape_validation(self):
        x = jnp.zeros((2, 4, 8))
        with pytest.raises(ValueError, match="grouped shape mismatch"):
            dense_grouped(x, jnp.zeros((3, 8, 16)), mode="ref")
        with pytest.raises(ValueError, match="wants"):
            dense_grouped(jnp.zeros((4, 8)), jnp.zeros((3, 8, 16)), mode="ref")


# ---------------------------------------------------------------------------
# TimingCache: measurements override the analytic model in the planner
# ---------------------------------------------------------------------------

class TestTimingCache:
    def test_measured_rates_change_tile_choice(self):
        """The analytic model (small M => DMA-bound) plans a deep ring; a
        TimingCache whose measurements say compute is the bottleneck must
        flip the plan to a shallow ring."""
        M, K, N = 8, 4096, 8192   # small-M: analytically t_dma >> t_compute
        base = plan_matmul_tiles(M, K, N)
        assert base.num_bufs >= 3  # sanity: analytic model wants a deep ring

        # contradicting measurements: transfers are ~instant, compute is slow
        tc = TimingCache()
        for _ in range(3):
            tc.record(block_bytes=1e6, compute_flops=1e9,
                      t_dma=1e-6, t_compute=1e-2)
        measured = plan_matmul_tiles(M, K, N, timing=tc)
        assert measured.num_bufs == 2  # compute-bound: naive double-buffer
        assert measured.num_bufs != base.num_bufs

    def test_median_rejects_outlier(self):
        tc = TimingCache()
        # steady-state: 1 GB/s; one preempted outlier at 1 KB/s
        for t in (1e-3, 1e-3, 1e-3, 1.0):
            tc.record(block_bytes=1e6, compute_flops=1e9,
                      t_dma=t, t_compute=1e-3)
        _, bps = tc.effective_rates()
        assert bps == pytest.approx(1e9)

    def test_default_cache_install(self):
        M, K, N = 8, 4096, 8192
        base = plan_matmul_tiles(M, K, N)
        tc = TimingCache()
        tc.record(block_bytes=1e6, compute_flops=1e9,
                  t_dma=1e-6, t_compute=1e-2)
        set_default_timing_cache(tc)
        try:
            assert plan_matmul_tiles(M, K, N).num_bufs != base.num_bufs
            # explicitly passed rates beat the ambient default cache
            from repro.core.schedule import V5E
            explicit = plan_matmul_tiles(M, K, N,
                                         flops_per_s=V5E.peak_flops * 2)
            no_cache = plan_matmul_tiles(M, K, N,
                                         flops_per_s=V5E.peak_flops * 2,
                                         timing=TimingCache())
            assert explicit.num_bufs == no_cache.num_bufs
        finally:
            set_default_timing_cache(None)
        assert plan_matmul_tiles(M, K, N).num_bufs == base.num_bufs

    def test_json_roundtrip(self, tmp_path):
        import json
        tc = TimingCache()
        tc.record(block_bytes=2e6, compute_flops=3e9, t_dma=2e-4, t_compute=1e-4)
        bench = {"dense_timing_samples": {"samples": tc.to_json()}}
        path = tmp_path / "BENCH_kernels.json"
        path.write_text(json.dumps(bench))
        tc2 = TimingCache.from_bench_json(str(path))
        assert len(tc2) == 1
        assert tc2.effective_rates() == tc.effective_rates()


class TestTimingProvenance:
    """measured_on tags: compiled-run samples outrank host ones."""

    def test_compiled_samples_preferred(self):
        tc = TimingCache()
        # host loop says the link is slow (deep ring)...
        tc.record(block_bytes=1e6, compute_flops=1e9,
                  t_dma=1e-2, t_compute=1e-6, measured_on="host")
        # ...the compiled run says compute is the bottleneck (shallow ring)
        tc.record(block_bytes=1e6, compute_flops=1e9,
                  t_dma=1e-6, t_compute=1e-2, measured_on="compiled")
        fps, bps = tc.effective_rates()
        assert bps == pytest.approx(1e6 / 1e-6)     # compiled sample only
        assert fps == pytest.approx(1e9 / 1e-2)
        shallow = plan_matmul_tiles(8, 4096, 8192, timing=tc)
        assert shallow.num_bufs == 2                # compiled verdict wins

    def test_host_only_cache_unchanged(self):
        tc = TimingCache()
        tc.record(block_bytes=1e6, compute_flops=1e9,
                  t_dma=1e-3, t_compute=1e-3)       # default: host
        assert tc.samples[0].measured_on == "host"
        fps, bps = tc.effective_rates()
        assert bps == pytest.approx(1e9)

    def test_bad_provenance_rejected(self):
        with pytest.raises(ValueError):
            TimingCache().record(block_bytes=1e6, compute_flops=1e9,
                                 t_dma=1e-3, t_compute=1e-3,
                                 measured_on="gpu-ish")

    def test_json_roundtrip_preserves_and_defaults_provenance(self):
        tc = TimingCache()
        tc.record(block_bytes=1e6, compute_flops=1e9, t_dma=1e-3,
                  t_compute=1e-3, measured_on="compiled")
        tc2 = TimingCache.from_json(tc.to_json())
        assert tc2.samples[0].measured_on == "compiled"
        # pre-provenance records (no measured_on key) load as host samples
        legacy = [{"block_bytes": 1e6, "compute_flops": 1e9,
                   "t_dma": 1e-3, "t_compute": 1e-3}]
        assert TimingCache.from_json(legacy).samples[0].measured_on == "host"
