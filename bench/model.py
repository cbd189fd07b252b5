"""A benchmark configuration, by name: its published config, the module of
its architecture, and weights made on the device from a seed.

`bench/configs/<name>.json` holds the model's published `config.json` keys
as they are run (a key cut for one chip is listed under `reduced` with its
published value), plus `source`, `assumed`, `deployment` and the name of
its plain reference under `bench/references/`.  What depends on the
architecture is in `bench/arch/<model_type>.py` (`arch_for`).
"""
from __future__ import annotations

import importlib.util
import json
import sys
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


def load_module(path: Path):
    """Import a file of the benchmark by path (metric names hold dots), as
    the module `bench_<directory>_<name>`."""
    name = f"bench_{path.parent.name}_{path.stem}"
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    # registered, as dataclasses look their module up while it runs
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def arch_for(hf: dict):
    """The module of the configuration's architecture,
    `bench/arch/<model_type>.py`: its config mapping, smoke width, kernel
    weights and step work (the names are listed in `bench/arch/qwen2.py`)."""
    path = BENCH / "arch" / f"{hf['model_type']}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no architecture module for model_type {hf['model_type']!r}: "
            f"bench/arch/{hf['model_type']}.py is missing")
    return load_module(path)


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
