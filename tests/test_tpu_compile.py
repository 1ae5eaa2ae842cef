"""Compiles for a described TPU v5e chip: no chip is attached, so nothing
runs, but the TPU compiler refuses what the chip would refuse (unaligned
blocks, unsupported vector layouts, programs over HBM).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library, and
under several test workers only the worker that runs this file loads it.
Every case in one file, so the fixture is set up once.
"""
import dataclasses
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip; keep the cache out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, sh):
    """(fn, argument shapes) of one Pallas kernel at a real width."""
    from repro.kernels import gt_update, quantize, ssd_scan

    # the package re-exports the function under the module's name
    fa = importlib.import_module("repro.kernels.flash_attention")

    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "fused_local_step":
        # four agents of mamba2-370m's in_proj (1024 x 4384)
        s = _sds((4, 1024, 4384), bf16, sh)
        return lambda x, y, a, b: gt_update.fused_local_step(x, y, a, b, 0.05), [s] * 4
    if name == "fused_compressed_mix":
        # eight agents of a flattened 4.49M-parameter state
        return (
            lambda x, w: quantize.fused_compressed_mix(x, w, bits=8),
            [_sds((8, 4_490_000), f32, sh), _sds((8, 8), f32, sh)],
        )
    if name == "ssd_scan":
        # mamba2-370m: 32 heads of 64, state 128, one group, chunk 256
        return (
            lambda x, dt, a, b, c: ssd_scan.ssd_scan_kernel(x, dt, a, b, c, chunk=256),
            [
                _sds((2, 1024, 32, 64), bf16, sh),
                _sds((2, 1024, 32), f32, sh),
                _sds((32,), f32, sh),
                _sds((2, 1024, 1, 128), bf16, sh),
                _sds((2, 1024, 1, 128), bf16, sh),
            ],
        )
    assert name == "flash_attention"
    # qwen3-8b: 32 query heads over 8 KV heads of 128, 4k context
    return (
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        [
            _sds((1, 32, 4096, 128), bf16, sh),
            _sds((1, 8, 4096, 128), bf16, sh),
            _sds((1, 8, 4096, 128), bf16, sh),
        ],
    )


@pytest.mark.parametrize(
    "name", ["fused_local_step", "fused_compressed_mix", "ssd_scan", "flash_attention"]
)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_training_block_donates_its_carry_on_v5e(one_chip):
    """mamba2-370m at full width, 2 agents, PISCO on a ring, cut to one
    layer: the scan block compiles for the chip and its carry (the
    agent-stacked state) is aliased to the output, not held twice."""
    from repro.configs import get_config
    from repro.core.algorithms import get_algorithm
    from repro.core.driver import make_block_fn
    from repro.core.mixing import make_sparse_network_mixing
    from repro.core.pisco import PiscoConfig
    from repro.core.topology import make_sparse_topology
    from repro.models import get_bundle

    n_agents, t_o, batch, seq = 2, 2, 2, 1024
    cfg = dataclasses.replace(get_config("mamba2-370m"), n_layers=1)
    bundle = get_bundle(cfg)
    pcfg = PiscoConfig(n_agents=n_agents, t_o=t_o, eta_l=0.05, p=0.5)
    mixing = make_sparse_network_mixing(make_sparse_topology("ring", n_agents))
    bound = get_algorithm("pisco").bind(bundle.loss, pcfg, mixing)

    params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    x0 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_agents,) + s.shape, s.dtype), params
    )
    tok = lambda *lead: jax.ShapeDtypeStruct(lead + (n_agents, batch, seq), jnp.int32)
    state = jax.eval_shape(
        lambda x, c: bound.init(bundle.loss, x, c), x0, {"tokens": tok()}
    )
    on_chip = lambda t: jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip), t
    )
    compiled = make_block_fn(bound).lower(
        on_chip(state),
        _sds((1,), jnp.bool_, one_chip),
        on_chip({"tokens": tok(1, t_o)}),
        on_chip({"tokens": tok(1)}),
    ).compile()

    state_bytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(state))
    ma = compiled.memory_analysis()
    # aliased buffers are counted at their padded size on the chip
    assert state_bytes <= ma.alias_size_in_bytes <= ma.argument_size_in_bytes
