"""Every operation that ``engine.step_chunk`` adds to a compiled scan loop
carries exactly one phase scope in its ``op_name`` metadata, the name the
benchmark's trace reduction (``bench/phases.py``) reads device time by.

Compiled on the CPU at the unit-test geometry: the single-drive loop
read-only and with writes, and the vmapped sweep loop for two lanes.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from bench.phases import PHASES
from repro.experiments import sweep
from repro.ssdsim import engine, geometry, policies

N_CHUNKS = 2
# instructions that move no data on the device
NO_WORK = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}
CONTROL = {"while", "conditional", "call"}


def _computations(hlo: str) -> dict[str, list[str]]:
    comps, cur = {}, None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            cur = line.removeprefix("ENTRY ").split()[0].lstrip("%")
            comps[cur] = []
        elif line.startswith("  ") and cur is not None:
            comps[cur].append(line.strip())
    return comps


def _opcode(instr: str) -> str:
    rhs = instr.split(" = ", 1)[1]
    if rhs.startswith("("):  # tuple shape: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.split(" ", 1)[1]
    return re.match(r"\s*([\w\-]+)\(", rhs).group(1)


def _loop_ops(hlo: str) -> list[tuple[str, str | None]]:
    """``(opcode, op_name)`` of each instruction the scan loop's body runs:
    the body's own and those of the loops and branches it calls."""
    comps = _computations(hlo)
    (loop,) = [i for c in comps.values() for i in c if _opcode(i) == "while"
               and "/step_" not in i]
    todo = [re.search(r"body=%?([\w.\-]+)", loop).group(1)]
    seen, out = set(todo), []
    while todo:
        for instr in comps[todo.pop()]:
            op = _opcode(instr)
            name = re.search(r'op_name="([^"]*)"', instr)
            out.append((op, name.group(1) if name else None))
            if op in CONTROL:
                for callee in re.findall(
                        r"(?:body|condition|to_apply|branch_computations)="
                        r"\{?%?([\w.\-, %]+)\}?", instr):
                    for c in callee.replace("%", "").split(","):
                        c = c.strip()
                        if c in comps and c not in seen:
                            seen.add(c)
                            todo.append(c)
    return out


def _compiled(kind: str, has_writes: bool) -> str:
    cfg = geometry.tiny_config()
    if kind == "run":
        x = jax.ShapeDtypeStruct((N_CHUNKS, cfg.chunk), jnp.int32)
        return engine._run_jit.lower(cfg, x, x, has_writes).compile().as_text()
    x = jax.ShapeDtypeStruct((2, N_CHUNKS, cfg.chunk), jnp.int32)
    k = jax.ShapeDtypeStruct((2,), jnp.int32)
    knobs = policies.RunKnobs(r1=k, r2_override=k, initial_pe=k)
    return sweep._sweep_jit.lower(cfg, x, x, has_writes, knobs).compile().as_text()


@pytest.fixture
def metadata_in_cache_key():
    """A program loaded from a persistent compilation cache keeps the
    metadata of the build that wrote the entry unless the key holds it."""
    was = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    yield
    jax.config.update("jax_compilation_cache_include_metadata_in_key", was)


@pytest.mark.parametrize("kind,has_writes", [("run", False), ("run", True),
                                             ("sweep", True)])
def test_step_ops_carry_one_phase(kind, has_writes, metadata_in_cache_key):
    ops = [(op, name) for op, name in _loop_ops(_compiled(kind, has_writes))
           if op not in NO_WORK and name]
    # the call frame of step_chunk inside the loop: everything below it was
    # traced by step_chunk; the loop's own counter, slicing and output
    # writes lie beside it
    frames = {name[:name.index("/step_")] for _, name in ops if "/step_" in name}
    assert len(frames) == 1, frames
    frame = frames.pop() + "/"
    traced = [name for _, name in ops if name.startswith(frame)]
    for name in traced:
        assert sum(p in PHASES for p in name.split("/")) == 1, name
    found = {p for name in traced for p in name.split("/") if p in PHASES}
    want = set(PHASES) if has_writes else set(PHASES) - {"step_write"}
    assert found == want


@pytest.mark.parametrize("kind", ["run", "sweep"])
def test_lane_fold_scope_only_in_the_batched_loop(kind, metadata_in_cache_key):
    """The sweep carries its large state leaves 128 wide through the scan
    and the batch-gated conds under the ``lane_fold`` scope; the single
    drive folds nothing."""
    names = {name for _, name in _loop_ops(_compiled(kind, True)) if name}
    folded = {n for n in names if "/lane_fold/" in n}
    assert bool(folded) == (kind == "sweep"), sorted(folded)[:5]
