"""`trace_reduce.KERNELS` on the HLO text of real kernel calls, compiled for
a described TPU v5e (`topologies.get_topology_desc`) while JAX runs on the
CPU: each call is matched by its own entry only, by the Pallas call's
name, and again by its operands alone once the name is taken away.

The topology is described inside a module-scope fixture, never at import:
only one process may hold the TPU library, and every pytest-xdist worker
imports this file."""
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from bench import trace_reduce as tr

D, FF, E = 1024, 2816, 4
BS, SLOTS, HEADS, HD = 16, 8, 16, 64
NB, MB = 129, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.core.schedule import chip_spec
    import repro.kernels.gpp_matmul as gm
    import repro.kernels.ops as ops
    import repro.kernels.paged_attention as pa

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else the compiler logs
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        spec = chip_spec(topo.devices[0].device_kind)
        # a described chip's executables can be written to the persistent
        # cache but not read back without the chip: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        for mod in (gm, pa, ops):
            mp.setattr(mod, "target_chip", lambda interpret: spec)
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _calls(one_chip):
    from repro.kernels.gpp_matmul import gpp_matmul, gpp_matmul_grouped
    from repro.kernels.paged_attention import paged_attention
    kv = _sds(one_chip, (NB, BS, HEADS * HD))
    return {
        "gpp_matmul": (gpp_matmul, _sds(one_chip, (8, D)),
                       _sds(one_chip, (D, FF))),
        "gpp_matmul_grouped": (gpp_matmul_grouped, _sds(one_chip, (E, 8, D)),
                               _sds(one_chip, (E, D, FF))),
        "paged_attn": (
            lambda q, a, b, t, p: paged_attention(
                q, a, b, t, p, num_kv_heads=HEADS, scale=HD ** -0.5),
            _sds(one_chip, (SLOTS, 1, HEADS, HD)), kv, kv,
            _sds(one_chip, (SLOTS, MB), jnp.int32),
            _sds(one_chip, (SLOTS,), jnp.int32)),
    }


@pytest.mark.parametrize("kernel", ["gpp_matmul", "gpp_matmul_grouped",
                                    "paged_attn"])
def test_each_call_matches_its_own_entry(one_chip, kernel):
    fn, *args = _calls(one_chip)[kernel]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line.strip() for line in hlo.splitlines()
             if tr.CUSTOM_CALL in line]
    assert len(calls) == 1
    call = calls[0].removeprefix("ROOT ")
    assert [k for k, match in tr.KERNELS.items() if match(call)] == [kernel]
    # unnamed (as a call from before the kernels were named): the operands
    unnamed = re.sub(r"^%[\w\-]+", "%custom-call", call)
    assert unnamed != call
    assert [k for k, match in tr.KERNELS.items() if match(unnamed)] == [kernel]
