"""A benchmark configuration, by name: its published config, the program's
`ModelConfig` built from it, and weights made on the device from a seed.

`bench/configs/<name>.json` holds the model's published `config.json` keys
as they are run (a key cut for one chip is listed under `reduced` with its
published value), plus `source`, `assumed`, `deployment` and the name of
its plain reference under `bench/references/`.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parent

# weight scales: projections ~ N(0, 0.02) as in the published initializer
# range; norm scales near 1 and biases small but non-zero, so that every
# parameter moves the logits and the comparison with the reference sees it
PROJ_STD, BIAS_STD, NORM_STD = 0.02, 0.1, 0.1


def load_config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} ({path})")
    return json.loads(path.read_text())


def model_config(name: str, hf: dict):
    """The program's ModelConfig for a Qwen2-architecture config.json."""
    from repro.configs.base import ModelConfig
    if hf["model_type"] != "qwen2" or hf.get("use_sliding_window"):
        raise ValueError(f"{name}: only full-attention qwen2 configs are "
                         f"mapped, got {hf['model_type']}")
    cfg = ModelConfig(
        name=name, family="dense",
        d_model=hf["hidden_size"], num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        pattern=("dense",), qkv_bias=True, rope_theta=hf["rope_theta"],
        norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"], dtype=hf["torch_dtype"])
    return cfg


def smoke_hf(hf: dict) -> dict:
    """The config at a width the CPU tests can run, with the architecture
    kept: GQA stays grouped, MHA stays whole."""
    kv = 2 if hf["num_key_value_heads"] < hf["num_attention_heads"] else 4
    return dict(hf, hidden_size=128, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=kv,
                intermediate_size=256, vocab_size=512)


def _leaf_init(name: str, key, shape, dtype):
    if name == "scale":
        return (1.0 + NORM_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    std = BIAS_STD if name.startswith("b_") else PROJ_STD
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def make_weights(specs, seed: int):
    """Every leaf of the spec tree, drawn on the device in the served dtype
    by ONE jitted call from `seed` (the same seed gives the same weights).
    The seed may exceed 32 bits: it is folded in as two 32-bit words."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(specs)
    names = [str(getattr(p[-1], "key", p[-1])) for p, _ in leaves]
    shapes = [(tuple(s.shape), s.dtype) for _, s in leaves]

    def init(hi, lo):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(0, impl="rbg"), hi), lo)
        keys = jax.random.split(key, len(shapes))
        return [_leaf_init(n, k, sh, dt)
                for n, k, (sh, dt) in zip(names, keys, shapes)]

    hi, lo = divmod(int(seed) % (1 << 64), 1 << 32)
    out = jax.jit(init)(jnp.uint32(hi), jnp.uint32(lo))
    return jax.tree_util.tree_unflatten(treedef, out)
