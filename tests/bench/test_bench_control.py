"""The control of every cell fails its limit: the float64 reference put in
the program's place at bfloat16 (bench/control.py), at a tiny size on the
CPU, on three seeds. On the chip the same script runs at the cells' own
sizes (PERF.md gives those readings)."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
from bench import control, manifest  # noqa: E402

CELLS = tiny.CELLS


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("control"))


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_limit(root, workload):
    cell = manifest.resolve(root, workload)
    for seed in (1, 2**33 + 5, 77):
        numbers = control.control_numbers(cell, seed)
        assert numbers
        assert any(v > cell.limits[k] for k, v in numbers.items()), numbers
