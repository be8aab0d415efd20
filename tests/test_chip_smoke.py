"""The chip smoke script off the chip: it refuses to run without a TPU, and
its phases and checks pass on the CPU at the unit-test geometry (kernels in
interpret mode, the CPU as both the device and the reference)."""

import jax
import pytest

import chip_smoke
from repro.ssdsim import geometry


@pytest.fixture(scope="module")
def cpu():
    return jax.devices("cpu")[0]


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU present" in out.err


def test_engine_phases_at_tiny_geometry(cpu):
    clock, base = chip_smoke.Clock(), geometry.tiny_config()
    a = chip_smoke.phase_a(clock, base, cpu, cpu)
    assert a[geometry.RARO]["iops"] > a[geometry.BASELINE]["iops"]
    b = chip_smoke.phase_b(clock, base, cpu, cpu)
    assert b["bad_blocks"] <= chip_smoke.phase_b_config(base).spare_blocks
    chip_smoke.phase_c(clock, base, cpu, a[geometry.RARO])


def test_kernel_phase_interprets_on_cpu(cpu):
    chip_smoke.phase_kernels(chip_smoke.Clock(), cpu, cpu)


def test_agree_flags_count_and_float_mismatch():
    got = dict.fromkeys(chip_smoke.EXACT_KEYS + chip_smoke.FLOAT_KEYS, 1.0)
    chip_smoke.agree("same", got, dict(got))
    with pytest.raises(chip_smoke.SmokeFailure, match="reads"):
        chip_smoke.agree("count", got, {**got, "reads": 2.0})
    with pytest.raises(chip_smoke.SmokeFailure, match="iops"):
        chip_smoke.agree("float", got, {**got, "iops": 1.1})
