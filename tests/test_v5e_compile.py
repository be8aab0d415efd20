"""Compile the simulator's main path and the Pallas kernels for a described
TPU v5e (no chip attached). What the TPU compiler refuses fails here, at no
chip time: a scatter the compiler aborts on, a kernel block that breaks the
tiling rule, a program that does not fit the device.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import os
import re
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


def _grid_cell(name):
    cell = harness.find_cell(name, REPO)
    cfg = harness.sim_config(cell.config)
    r = len(cell.config["initial_pe"]) * cell.config["seeds_per_stage"]
    n_chunks = -(-cell.config["requests_per_run"] // cfg.chunk)
    return cell, cfg, r, n_chunks


@pytest.fixture(scope="module")
def age_grid(one_chip):
    """The benchmark's twelve-lane Table III grid as ``run_sweep`` compiles
    it on one chip: ``(cfg, lanes, compiled)``."""
    _, cfg, r, n_chunks = _grid_cell("table3_age_grid.fio_zipf_read")
    x = jax.ShapeDtypeStruct((r, n_chunks, cfg.chunk), jnp.int32,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((r,), jnp.int32, sharding=one_chip)
    knobs = policies.RunKnobs(r1=k, r2_override=k, initial_pe=k)
    return cfg, r, sweep._sweep_jit.lower(cfg, x, x, False, knobs).compile()


@pytest.fixture(scope="module")
def age_grid_4chip(topo):
    """The four-chip benchmark grid as ``run_sweep(devices=4)`` compiles it:
    ``(cfg, lanes a chip, compiled)``."""
    cell, cfg, r, n_chunks = _grid_cell("table3_age_grid_4chip.fio_zipf_read")
    assert (cell.chips, r, n_chunks, cfg.chunk) == (4, 48, 64, 1024)
    mesh = Mesh(np.asarray(topo.devices[:cell.chips]), ("runs",))
    runs = NamedSharding(mesh, PartitionSpec("runs"))
    x = jax.ShapeDtypeStruct((r, n_chunks, cfg.chunk), jnp.int32, sharding=runs)
    k = jax.ShapeDtypeStruct((r,), jnp.int32, sharding=runs)
    knobs = policies.RunKnobs(r1=k, r2_override=k, initial_pe=k)
    compiled = sweep._sweep_sharded_jit.lower(
        cfg, x, x, False, knobs, None, mesh).compile()
    return cfg, r // cell.chips, compiled


def test_table3_age_grid_sweep_fits_one_chip(age_grid):
    """The twelve-lane grid fits one chip, with its reclaim and GC passes
    behind the batch gate."""
    cfg, _, compiled = age_grid
    assert (cfg.n_blocks, cfg.slots_per_block) == (1024, 1024)
    _fits(compiled)
    assert " conditional(" in compiled.as_text()


def test_table3_age_grid_4chip_sweep_fits_each_chip(age_grid_4chip):
    """48 Table III lanes of 64 chunks split 12 a chip over a 2x2 mesh, each
    chip's share within its memory, and no collective across chips."""
    _, _, compiled = age_grid_4chip
    _fits(compiled)
    text = compiled.as_text()
    assert " conditional(" in text
    for collective in ("all-reduce(", "all-gather(", "collective-permute(", "all-to-all("):
        assert collective not in text


_CALLEES = re.compile(r"(?:body|condition|to_apply|branch_computations|calls)="
                      r"\{?(%[\w.\-]+(?:, %[\w.\-]+)*)\}?")


def _relayout_writes(hlo: str, shapes: tuple[str, ...]) -> list[str]:
    """Names of the ``dynamic-update-slice``s into one of ``shapes`` that a
    ``while`` body runs, outside the branches of the gated reclaim and GC
    conditionals. Such a write, in a loop XLA made, is how a relayout copy
    of a whole per-slot or per-logical leaf shows in a compiled program."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            cur = line.removeprefix("ENTRY ").split()[0].lstrip("%")
            comps[cur] = []
            entry = cur if line.startswith("ENTRY") else entry
        elif line.startswith("  ") and cur is not None:
            comps[cur].append(line.strip().removeprefix("ROOT "))
    hits, seen, todo = [], set(), [(entry, False, False)]
    while todo:
        key = todo.pop()
        if key in seen:
            continue
        seen.add(key)
        comp, in_loop, gated = key
        for instr in comps[comp]:
            lhs, rhs = instr.split(" = ", 1)
            opcode = re.search(r"\s([\w\-]+)\(", rhs)
            op = opcode.group(1) if opcode else ""
            if (op == "dynamic-update-slice" and in_loop and not gated
                    and rhs.startswith(shapes)):
                hits.append(lhs)
            name = re.search(r'op_name="([^"]*)"', instr)
            into_gate = op == "conditional" and name is not None and (
                "step_reclaim" in name.group(1) or "step_gc" in name.group(1))
            for callees in _CALLEES.findall(instr):
                for c in callees.replace("%", "").split(", "):
                    if c in comps:
                        todo.append((c, in_loop or op == "while",
                                     gated or into_gate))
    return hits


@pytest.mark.parametrize("grid", ["age_grid", "age_grid_4chip"])
def test_table3_grid_scan_copies_no_state_leaf(grid, request):
    """Carried 128 wide, the per-slot and per-logical leaves are scattered
    into without the whole-leaf relayout loops XLA once put around every
    conversion: no loop outside the gated reclaim and GC branches writes a
    ``[1, lanes, n_slots]`` or ``[1, lanes, n_logical]`` leaf."""
    cfg, lanes, compiled = request.getfixturevalue(grid)
    shapes = tuple(f"{t}[1,{lanes},{n}]" for t in ("s32", "f32")
                   for n in (cfg.n_slots, cfg.n_logical))
    assert _relayout_writes(compiled.as_text(), shapes) == []


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
