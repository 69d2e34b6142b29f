"""The harness end to end on the CPU, at tiny sizes: the result line, a
cell and a metric added as files only, the refusal without a chip, and
``correct`` coming out false for each fault that a serving cell can have.
"""
import json
import subprocess
import sys

import pytest

from tiny import ROOT, make_tree, runner

# For the tiny cells only.  Sound runs read gap_max 0-0.002 and
# nucleus_excess_max -0.009 to -0.003 on the CPU; the sampler faults read
# 0.031-0.050 (seeds 1, 2, 3, 2**31 + 5).
LIMITS = {"gap_max": 0.05, "nucleus_excess_max": 0.01}
CELL_LIMITS = {"tiny-llama": {"gap_max": LIMITS["gap_max"]},
               "tiny-sc2": LIMITS}


def result(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("bench"), CELL_LIMITS)


def test_no_tpu_exits_nonzero_without_result():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(ROOT)}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "ds7b.decode", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


@pytest.mark.parametrize("cell", ["tiny.decode", "tiny.code"])
def test_result_line(tree, capsys, cell):
    rc = runner(tree).run_cell(cell, 2 ** 31 + 11, 3.0, False, root=tree,
                               require_chip=False)
    assert rc == 0
    r = result(capsys)
    assert list(r)[-1] == "checks" and r["correct"] is True
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(r)
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["checks"]["gap_max"]["value"] <= LIMITS["gap_max"]


def test_new_cell_and_metric_are_files_only(tree, capsys):
    """A traffic mix, a cell and a per-layer metric added as new files and
    new entries: the harness reads them with no edit to its own files."""
    bench = tree / "bench"
    (bench / "metrics" / "window_steps.py").write_text(
        "def read(run):\n    return len(run.steps) or None\n")
    t = json.loads((bench / "traffic" / "tiny-code.json").read_text())
    t.update(loop="open", rate=5.0, preroll_s=1.0, arrivals="poisson")
    (bench / "traffic" / "tiny-burst.json").write_text(json.dumps(t))
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.burst", "config": "tiny-sc2",
                              "traffic": "tiny-burst", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "window_steps", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine step", "moves": "setup_s",
                              "workloads": ["tiny.burst"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(spec))
    assert runner(tree).run_cell("tiny.burst", 5, 3.0, True, root=tree,
                                 require_chip=False) == 0
    r = result(capsys)
    assert r["metrics"]["window_steps"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(r["device"])


FAULTS = [("state_unchanged", "tiny.decode", "gap_max"),
          ("token_altered", "tiny.decode", "gap_max"),
          ("top_p_ignored", "tiny.code", "nucleus_excess_max"),
          ("temperature_one", "tiny.code", "nucleus_excess_max")]


@pytest.mark.parametrize("kind,cell,number", FAULTS)
def test_fault_makes_correct_false(tree, capsys, kind, cell, number):
    from benchlib import faults
    with faults.planted(kind):
        runner(tree).run_cell(cell, 77, 3.0, False, root=tree,
                              require_chip=False)
    r = result(capsys)
    assert r["correct"] is False
    assert r["checks"][number]["value"] > LIMITS[number]


@pytest.mark.parametrize("kv_quant,lost", [(False, 0), (True, 8)])
def test_kv_pool_narrower_than_stated_fails(kv_quant, lost):
    """A KV pool held below the configuration's stated dtype (the
    program's int8 path) reads ``kv_bits_lost`` over its limit of 0."""
    from benchlib import check
    from repro.configs import get_config
    from repro.models import build_model
    from tiny import LLAMA, _program
    c = dict(_program("deepseek-7b", LLAMA)["replace"], dtype="bfloat16",
             kv_quant=kv_quant)
    pool = build_model(get_config("deepseek-7b").replace(**c)).init_cache(
        4, 8)
    assert check.kv_bits_lost(pool, "bfloat16") == lost
