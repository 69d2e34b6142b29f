"""The lower-precision control at a size a test run can hold: the
reference's own W8A8 forward (int8 weights and activations), read at the
positions of the program's served tokens, has to read well above the
program's sound runs.  On one v5e at the cells' own sizes the same
readings come from ``bench/control.py``."""
import importlib.util

import pytest

from tiny import make_tree


@pytest.fixture(scope="module")
def control(tmp_path_factory):
    root = make_tree(tmp_path_factory.mktemp("bench"))
    spec = importlib.util.spec_from_file_location(
        "bench_control", root / "bench" / "control.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, mod.run.Cell(root, "tiny.decode")


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_w8a8_control_separates(control, seed):
    mod, cell = control
    row = mod.arm(cell, "sound", seed, 3.0, require_chip=False)
    assert row["tokens"] > 200 and row["failed"] == 0
    assert row["w8a8"]["gap_mean"] > 3 * row["gap_mean"]
    assert row["w8a8"]["gap_mean"] > 0
