"""Compile the simulator's main path and the Pallas kernels for a described
TPU v5e (no chip attached). What the TPU compiler refuses fails here, at no
chip time: a scatter the compiler aborts on, a kernel block that breaks the
tiling rule, a program that does not fit the device.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

import chip_smoke
from bench import harness
from repro.core import modes
from repro.experiments import sweep
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.quant_page.ops import quant_pages
from repro.kernels.tiered_attention.ops import tiered_decode_attention
from repro.kvcache import paged
from repro.launch import serve
from repro.ssdsim import engine, geometry, policies

V5E_HBM_BYTES = 16 * 2**30
REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


def test_engine_table3_mixed_compiles(one_chip):
    """The chip smoke's phase-B program at Table III geometry (writes, GC,
    conversion, faults, lattice, obs counters). Its init_state once held an
    iota-indexed scatter that aborted the TPU compiler."""
    cfg = chip_smoke.phase_b_config(geometry.SimConfig())
    assert (cfg.n_blocks, cfg.slots_per_block, cfg.n_logical) == (1024, 1024, 524_288)
    x = jax.ShapeDtypeStruct((chip_smoke.N_CHUNKS, cfg.chunk), jnp.int32,
                             sharding=one_chip)
    _fits(engine._run_jit.lower(cfg, x, x, True).compile())


def test_sweep_sharded_compiles_on_four_chips(topo):
    """A grid's run axis split over a 2x2 mesh, one run per chip, as
    ``run_sweep(devices=4)`` dispatches it. The unit-test geometry keeps
    the compile short; its time barely depends on the drive's size."""
    assert len(topo.devices) == 4
    mesh = Mesh(np.asarray(topo.devices), ("runs",))
    runs = NamedSharding(mesh, PartitionSpec("runs"))
    cfg = geometry.tiny_config()
    r = len(topo.devices)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=runs)

    lpns = sds((r, 4, cfg.chunk))
    knobs = policies.RunKnobs(r1=sds((r,)), r2_override=sds((r,)),
                              initial_pe=sds((r,)))
    compiled = sweep._sweep_sharded_jit.lower(
        cfg, lpns, lpns, False, knobs, None, mesh).compile()
    _fits(compiled)


def test_table3_age_grid_sweep_fits_one_chip(one_chip):
    """The benchmark's twelve-lane Table III grid as ``run_sweep`` compiles
    it on one chip, with its reclaim and GC passes behind the batch gate."""
    cell = harness.find_cell("table3_age_grid.fio_zipf_read", REPO)
    cfg = harness.sim_config(cell.config)
    assert (cfg.n_blocks, cfg.slots_per_block) == (1024, 1024)
    r = len(cell.config["initial_pe"]) * cell.config["seeds_per_stage"]
    n_chunks = -(-cell.config["requests_per_run"] // cfg.chunk)
    x = jax.ShapeDtypeStruct((r, n_chunks, cfg.chunk), jnp.int32,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((r,), jnp.int32, sharding=one_chip)
    knobs = policies.RunKnobs(r1=k, r2_override=k, initial_pe=k)
    compiled = sweep._sweep_jit.lower(cfg, x, x, False, knobs).compile()
    _fits(compiled)
    assert " conditional(" in compiled.as_text()


def _tiered_args(one_chip):
    mcfg = serve.serve_cfg()
    ccfg = serve.serve_cache_cfg(mcfg, steps=64, batch=4)
    cache = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: paged.init(ccfg, jnp.float32)))
    q = jax.ShapeDtypeStruct((4, mcfg.n_heads, mcfg.head_dim), jnp.float32,
                             sharding=one_chip)
    return tiered_decode_attention, (q, cache, ccfg), {}


def _quant_args(one_chip, tier, dtype):
    ccfg = serve.serve_cache_cfg(serve.serve_cfg(), steps=64, batch=4)
    x = jax.ShapeDtypeStruct(
        (16, ccfg.page_size, ccfg.n_kv_heads, ccfg.head_dim), dtype,
        sharding=one_chip)
    return quant_pages, (x,), {"tier": tier}


def _flash_args(one_chip, dtype):
    q = jax.ShapeDtypeStruct((1, 256, 4, 128), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 256, 2, 128), dtype, sharding=one_chip)
    return flash_attention, (q, kv, kv), {}


KERNELS = {
    "tiered_attention": _tiered_args,
    "quant_int8_f32": lambda s: _quant_args(s, modes.TIER_INT8, jnp.float32),
    "quant_int4_bf16": lambda s: _quant_args(s, modes.TIER_INT4, jnp.bfloat16),
    "flash_attention_f32": lambda s: _flash_args(s, jnp.float32),
    "flash_attention_bf16": lambda s: _flash_args(s, jnp.bfloat16),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args, kw = KERNELS[name](one_chip)
    lowered = fn.lower(*args, interpret=False, **kw)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()
