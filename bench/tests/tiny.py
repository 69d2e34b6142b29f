"""A copy of the benchmark with two tiny cells, for tests on the CPU.

The copy holds the committed ``bench/`` tree unchanged plus new files
only: two configurations at small widths (one of each published family),
two traffic mixes, and a ``BENCHMARK.json`` naming them.  That new cells
need nothing else is part of what the tests show.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

LLAMA = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=4, num_hidden_layers=2, vocab_size=512)
SC2 = dict(hidden_size=64, intermediate_size=256, num_attention_heads=4,
           num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)
ENGINE = dict(lanes=4, page_size=8, num_pages=64, max_len=128, chunk_size=32,
              token_buckets=[1, 4, 8, 16, 32, 36])


def _program(arch: str, c: dict) -> dict:
    h = c["num_attention_heads"]
    return {"arch": arch, "replace": dict(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=h, num_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // h, d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"])}


def make_tree(dst: Path, limits: dict = None) -> Path:
    """Copy ``bench/`` to ``dst`` and add the tiny cells ``tiny.decode``
    (llama family, greedy) and ``tiny.code`` (starcoder2, half sampled),
    both closed loops; ``limits`` maps ``tiny-llama`` and ``tiny-sc2`` to
    their correctness limits."""
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, base, arch, widths in (
            ("tiny-llama", "deepseek-7b-pp4", "deepseek-7b", LLAMA),
            ("tiny-sc2", "starcoder2-3b", "starcoder2-3b", SC2)):
        c = json.loads((BENCH / "configs" / f"{base}.json").read_text())
        c["program"]["replace"].update(_program(arch, widths)["replace"])
        c.update(widths, name=name, engine=dict(ENGINE),
                 correct=dict((limits or {}).get(name, {})))
        (dst / "bench" / "configs" / f"{name}.json").write_text(json.dumps(c))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
    t = json.loads((BENCH / "traffic" / "decode.json").read_text())
    t.update(clients=4, requests=64,
             prompt=dict(dist="lognormal", median=12, sigma=0.5, min=4,
                         max=32),
             output=dict(dist="uniform", min=16, max=48))
    (dst / "bench" / "traffic" / "tiny-decode.json").write_text(json.dumps(t))
    t = json.loads((BENCH / "traffic" / "code.json").read_text())
    t.update(clients=4, requests=64,
             prompt=dict(dist="lognormal", median=40, sigma=0.5, min=8,
                         max=96),
             output=dict(dist="lognormal", median=12, sigma=0.5, min=4,
                         max=24))
    (dst / "bench" / "traffic" / "tiny-code.json").write_text(json.dumps(t))
    spec["workloads"] += [
        {"name": "tiny.decode", "config": "tiny-llama",
         "traffic": "tiny-decode", "chips": 1, "why": "test"},
        {"name": "tiny.code", "config": "tiny-sc2", "traffic": "tiny-code",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        for real, tiny in (("ds7b.decode", "tiny.decode"),
                           ("sc2.code", "tiny.code")):
            if real in m.get("workloads", ()):
                m["workloads"].append(tiny)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst


def runner(root: Path):
    """The copied ``bench/run.py`` as a module."""
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(root))}", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
