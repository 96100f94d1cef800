"""A run refuses (nonzero exit, no result line) off a TPU, on a device
kind the peaks table does not list, and short of the cell's chips."""
import dataclasses
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import chiplib  # noqa: E402


@dataclasses.dataclass
class FakeDevice:
    platform: str
    device_kind: str


PEAKS = chiplib.peaks_table()


def test_refuses_cpu():
    with pytest.raises(chiplib.NoChip, match="platform 'cpu'"):
        chiplib.check_device([FakeDevice("cpu", "cpu")], 1, PEAKS)


def test_refuses_unknown_kind():
    with pytest.raises(chiplib.NoChip, match="not in peaks.json"):
        chiplib.check_device([FakeDevice("tpu", "TPU v9 imaginary")], 1,
                             PEAKS)


def test_refuses_too_few_chips():
    with pytest.raises(chiplib.NoChip, match="asks for 4"):
        chiplib.check_device([FakeDevice("tpu", "TPU v5 lite")], 4, PEAKS)


def test_known_tpu_passes():
    row = chiplib.check_device([FakeDevice("tpu", "TPU v5 lite")] * 4, 4,
                               PEAKS)
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9


def test_run_exits_nonzero_without_result_on_cpu():
    cell = chiplib.benchmark_spec()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "nothing was measured" in p.stderr
