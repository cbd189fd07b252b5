"""The mechanisms DeepSeek-V2 brings to the program: YaRN-scaled rotary
embeddings with the interleaved lane pairing, the MLA softmax scale with
YaRN's mscale^2, top-k gate weights without renormalisation, and serving's
dropless expert capacity.  The unscaled, rotate-half rope every other model
uses stays bit-identical."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import YarnScaling
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.layers import init_from_specs, rope

# DeepSeek-V2-Lite's config.json rope_scaling
V2_LITE = YarnScaling(factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707)


def _hf_yarn_rope(x, positions, theta, s: "YarnScaling | None"):
    """transformers' DeepseekV2YarnRotaryEmbedding + apply_rotary_pos_emb
    in NumPy (float64): x (S, H, d) at positions (S,)."""
    d = x.shape[-1]
    extra = 1.0 / theta ** (np.arange(0, d, 2) / d)
    m = 1.0
    inv = extra
    if s is not None:
        def corr(rot):
            return (d * math.log(s.original_max_position_embeddings
                                 / (rot * 2 * math.pi))) / (2 * math.log(theta))

        def mscale(scale, ms):
            return 1.0 if scale <= 1 else 0.1 * ms * math.log(scale) + 1.0

        low = max(math.floor(corr(s.beta_fast)), 0)
        high = min(math.ceil(corr(s.beta_slow)), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
        mask = 1.0 - ramp
        inv = extra / s.factor * (1 - mask) + extra * mask
        m = mscale(s.factor, s.mscale) / mscale(s.factor, s.mscale_all_dim)
    emb = np.concatenate([np.outer(positions, inv)] * 2, -1)
    cos, sin = np.cos(emb)[:, None] * m, np.sin(emb)[:, None] * m
    S, H, _ = x.shape
    x = x.reshape(S, H, d // 2, 2).swapaxes(-1, -2).reshape(S, H, d)
    rot = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _seed_rope(x, positions, theta):
    """The program's rope before scaling existed, verbatim."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (jnp.log(theta) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


@pytest.mark.parametrize("scaling", [V2_LITE, None], ids=["yarn", "plain"])
def test_rope_matches_transformers_deepseek_v2(scaling):
    """The program's rope with the interleaved pairing against a NumPy
    transcription of transformers' formula, at positions up to 4095.  The
    program computes angles in float32: at |angle| < 4096 rad a float32
    angle is within 2.5e-4 rad of the exact one, so cos/sin, and with |x|
    < 4 each output lane, are within 1e-3 (times m <= 1)."""
    rng = np.random.default_rng(0)
    x = rng.uniform(-1, 1, (64, 4, 64)).astype(np.float32)
    pos = np.sort(rng.integers(0, 4096, 64)).astype(np.int32)
    got = rope(jnp.asarray(x), jnp.asarray(pos), 1e4, scaling,
               interleave=True)
    want = _hf_yarn_rope(x.astype(np.float64), pos, 1e4, scaling)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-3)
    # the comparison has teeth: rotate-half pairing, or no scaling, is far
    other = rope(jnp.asarray(x), jnp.asarray(pos), 1e4, scaling)
    assert np.abs(np.asarray(other) - want).max() > 0.1
    if scaling is not None:
        plain = _hf_yarn_rope(x.astype(np.float64), pos, 1e4, None)
        assert np.abs(np.asarray(got) - plain).max() > 0.1


def test_yarn_frequencies_blend_between_the_correction_dims():
    """V2-Lite's 32 frequencies: dims below the ramp (rotations > 32 over
    4096 positions) keep theta^(-2j/64), those above it are divided by 40,
    and the cos/sin factor mscale(40, .707) / mscale(40, .707) is 1."""
    from repro.models.layers import yarn_inv_freq, yarn_mscale
    inv = yarn_inv_freq(64, 1e4, V2_LITE)
    extra = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    assert np.all((inv[11:23] < extra[11:23]) & (inv[11:23] > extra[11:23] / 40))
    assert yarn_mscale(40, 0.707) == pytest.approx(1.26080, abs=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_unscaled_rope_is_bit_identical(dtype):
    """Without scaling and with the default pairing (every Qwen, Gemma,
    Llama path), rope computes exactly what it computed before."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 48, 4, 64)), dtype)
    pos = jnp.asarray(rng.integers(0, 32768, (2, 48)), jnp.int32)
    for theta in (1e4, 1e6):
        a = rope(x, pos, theta)
        b = _seed_rope(x, pos, theta)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ------------------------------------------------------------- MLA scale

def _mla_cfg(scaling):
    return attn.AttnConfig(d_model=32, num_heads=2, num_kv_heads=2,
                           head_dim=16, kv_lora_rank=16, rope_head_dim=8,
                           rope_scaling=scaling, rope_interleave=True,
                           dtype=jnp.float32, dense_mode="ref",
                           paged_mode="ref")


def test_mla_scale_includes_mscale_squared():
    """(nope + rope)^-0.5 x (0.1 x 0.707 x ln 40 + 1)^2 = x 1.5896 under
    V2-Lite's YaRN; unscaled, exactly the old 1/sqrt(nope + rope)."""
    assert _mla_cfg(None).mla_scale == 1.0 / math.sqrt(16 + 8)
    assert _mla_cfg(V2_LITE).mla_scale / _mla_cfg(None).mla_scale == (
        pytest.approx(1.5896, abs=1e-4))


def test_mla_forward_attends_with_the_scale():
    """The full-sequence MLA forward against attention written out by hand
    with mla_scale (and not without its mscale^2 term).  Float32 on both
    sides: 1e-5 allows for the order of the sums."""
    c = _mla_cfg(V2_LITE)
    p = init_from_specs(attn.attn_specs(c), jax.random.PRNGKey(0), scale=0.3)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 32), jnp.float32)
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    got = attn.mla_forward(p, c, x, pos)

    def by_hand(scale):
        q = attn._mla_q(p, c, x, pos)
        c_kv, k_rope = attn._mla_latent(p, c, x, pos)
        k_nope = jnp.einsum("btr,rhn->bthn", c_kv, p["w_uk"])
        v = jnp.einsum("btr,rhn->bthn", c_kv, p["w_uv"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None], (*k_nope.shape[:3], 8))], -1)
        s = jnp.einsum("bshd,bthd->bhst", q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), s, -jnp.inf)
        o = jnp.einsum("bhst,bthn->bshn", jax.nn.softmax(s, -1), v)
        return jnp.einsum("bshn,hnd->bsd", o, p["w_o"])

    np.testing.assert_allclose(got, by_hand(c.mla_scale), rtol=1e-5,
                               atol=1e-5)
    assert np.abs(got - by_hand(1.0 / math.sqrt(24))).max() > 1e-3


# -------------------------------------------------------------- MoE gate

def _moe(**kw):
    base = dict(d_model=16, d_ff=8, num_experts=8, experts_per_token=3,
                dtype=jnp.float32, dispatch_groups=4, dense_kernel="ref",
                capacity_factor=8.0)
    return moe_mod.MoeConfig(**{**base, **kw})


def _per_token(p, c, x):
    """Each token alone through its own top-k experts, in float32: the
    gate weights renormalised only where the config says so."""
    xt = np.asarray(x, np.float64).reshape(-1, c.d_model)
    w = {k: np.asarray(p[k], np.float64)
         for k in ("router", "w_gate", "w_up", "w_down")}
    out = np.zeros_like(xt)
    for t, v in enumerate(xt):
        logits = v @ w["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs)[:c.experts_per_token]
        wt = probs[top]
        if c.norm_topk_prob:
            wt = wt / wt.sum()
        for e, g in zip(top, wt):
            h = v @ w["w_gate"][e]
            h = h / (1 + np.exp(-h)) * (v @ w["w_up"][e])
            out[t] += g * (h @ w["w_down"][e])
    return out.reshape(np.shape(x))


@pytest.mark.parametrize("norm", [False, True])
def test_topk_weights_renormalised_only_when_asked(norm):
    """Float32 against a float64 per-token loop: 1e-5 is float32 rounding
    of a few dozen-term sums.  Without renormalisation the routed output is
    the softmax mass of the top k, which is well under 1 here."""
    c = _moe(norm_topk_prob=norm)
    p = init_from_specs(moe_mod.moe_specs(c), jax.random.PRNGKey(2), scale=0.5)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 16), jnp.float32)
    got = np.asarray(moe_mod.moe_apply(p, c, x))
    np.testing.assert_allclose(got, _per_token(p, c, x), rtol=1e-5,
                               atol=1e-5)
    if not norm:
        renorm = _per_token(p, _moe(norm_topk_prob=True), x)
        assert np.abs(got - renorm).max() > 1e-2


def test_serving_capacity_drops_no_token():
    """A router that sends every token to the same 6 of 64 experts meets a
    verify-sized batch (32 lanes x 5 rows = 160 tokens, 16 groups of 10):
    the capacity factor's 8 slots an expert would drop 2 tokens a group.
    Served dropless, each token's output equals its own, alone.  Float32
    both ways; 1e-5 allows for the order of the combine's additions."""
    kw = dict(num_experts=64, experts_per_token=6, dispatch_groups=16,
              capacity_factor=1.25, norm_topk_prob=False)
    served, trained = _moe(dropless=True, **kw), _moe(**kw)
    p = init_from_specs(moe_mod.moe_specs(served), jax.random.PRNGKey(4),
                        scale=0.3)
    # feature 0 is 1 in every token, and it votes for experts 0-5
    p["router"] = p["router"].at[0, :6].set(20.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 5, 16), jnp.float32)
    x = x.at[..., 0].set(1.0)
    assert moe_mod.capacity(trained, 10) == 8
    assert moe_mod.capacity(served, 10) == 10
    one = jax.jit(lambda t: moe_mod.moe_apply(p, served, t))
    alone = jnp.stack([one(x[b:b + 1, s:s + 1]) for b in range(32)
                       for s in range(5)]).reshape(x.shape)
    np.testing.assert_allclose(moe_mod.moe_apply(p, served, x), alone,
                               rtol=1e-5, atol=1e-5)
    # the training capacity drops tokens at this batch
    assert np.abs(moe_mod.moe_apply(p, trained, x) - alone).max() > 1e-2


def test_expert_rows_are_the_tokens_when_dropless():
    """The ledger's rows computed per MoE layer: experts x groups x
    capacity, which dropless is experts x tokens."""
    served = _moe(num_experts=64, dispatch_groups=16, dropless=True)
    for T in (1, 2, 32, 160):
        assert moe_mod.expert_rows(served, T) == 64 * T
    # the training capacity keeps at least 8 slots an expert and group
    assert moe_mod.expert_rows(_moe(num_experts=64, dispatch_groups=16),
                               32) == 64 * 16 * 8
