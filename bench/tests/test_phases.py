"""The reduction by the program's spans and scopes (``benchlib.phases``):
hand-computed numbers on a made-up trace, the same results as ``xtrace``
on the recorded trace that has none, and the scope mapping on a short
trace recorded on one v5e."""
import gzip
from pathlib import Path

import pytest

from benchlib import phases, xtrace

# Device 0 (window 0..100 ns): busy 0..50, 60..70, 80..88; idle gaps
# 50..60, 70..80 and 88..100.  Two engine steps with their five phases,
# the benchmark's spans around them, and a GC pause inside the second
# step's commit.
POOL = (65, 2, 16, 128)            # pages (with the scratch page), ...
OPS = [("fusion.1", 0.0, 10.0, "f32[4]{0}", "embed"),
       ("paged_attention_varlen.3", 10.0, 40.0, "bf16[2,2,8,128]{3,2,1,0}",
        "attention"),
       ("scatter.2", 40.0, 45.0, "bf16[65,2,16,128]{3,2,1,0}", "kv_write"),
       ("copy_dynamic-update-slice_fusion.4", 45.0, 50.0,
        "bf16[8,65,2,16,128]{4,3,2,1,0:T(8,128)(2,1)}", ""),
       ("fusion.7", 60.0, 70.0, "s32[4]{0}", "sample"),
       ("fusion.9", 80.0, 88.0, "f32[4]{0}", "")]
HOST = {"bench.window": [(0.0, 100.0)],
        "engine.step": [(0.0, 73.0), (77.0, 100.0)],
        "serve.step": [(0.0, 72.0), (78.0, 100.0)],
        "serve.schedule": [(50.0, 56.0), (78.0, 80.0)],
        "serve.upload": [(56.0, 57.0), (80.0, 81.0)],
        "serve.dispatch": [(57.0, 60.0), (81.0, 82.0)],
        "serve.wait": [(60.0, 70.0), (82.0, 92.0)],
        "serve.commit": [(70.0, 72.0), (92.0, 98.0)],
        "serve.gc": [(93.0, 96.0)]}
T = phases.Trace(window=(0.0, 100.0), ops=[[o[:3] for o in OPS]],
                 host=HOST, results=[[o[3] for o in OPS]],
                 scopes=[[o[4] for o in OPS]])
PLAIN = phases.Trace(window=(0.0, 100.0), ops=[[o[:3] for o in OPS]],
                     host={k: v for k, v in HOST.items()
                           if k in xtrace.HOST_SPANS})


def test_scope_of_takes_the_innermost_named_scope():
    assert phases.scope_of("jit(ragged_fn)/while/body/attention/kv_write/"
                           "scatter") == "kv_write"
    assert phases.scope_of("jit(ragged_fn)/while/body/attention/jit("
                           "paged_attention_4d)/paged_attention_varlen/"
                           "pallas_call") == "attention"
    assert phases.scope_of("jit(ragged_fn)/while/body/dynamic_slice") == ""
    assert phases.scope_of("") == ""


HLO = """\
%region_1.2 {
  %sort.8 = s32[8]{0} sort(%p.1), dimensions={0}
  %fusion.3 = f32[8]{0} fusion(%p.2), metadata={op_name="jit(f)/sample/cond/branch_1_fun/gather"}
}
ENTRY %main.9 {
  %fusion.1 = f32[4]{0} fusion(%p.3), metadata={op_name="jit(f)/embed/mul"}
  %copy.2 = bf16[3,65,2,16,128]{4,3,2,1,0} copy(%p.4)
  ROOT %fusion.4 = f32[4]{0} fusion(%fusion.1), metadata={op_name="jit(f)/head/dot_general"}
}
"""


def test_hlo_scopes_and_module_matching():
    table = phases.hlo_scopes(HLO)
    assert table == {
        ("sort.8", "s32[8]{0}"): "sample",     # its computation's one scope
        ("fusion.3", "f32[8]{0}"): "sample",
        ("fusion.1", "f32[4]{0}"): "embed",
        ("copy.2", "bf16[3,65,2,16,128]{4,3,2,1,0}"): "",   # ENTRY is mixed
        ("fusion.4", "f32[4]{0}"): "head"}
    keys = [("fusion.1", "f32[4]{0}"), ("sort.8", "s32[8]{0}"),
            ("fusion.1", "f32[4]{0}"), ("fusion.1", "f32[9]{0}")]
    modules = [("jit_f(1)", 0.0, 10.0), ("jit_f(2)", 10.0, 20.0)]
    # Module 1 holds ops 0 and 1, both in the table; module 2 holds an op
    # whose shape is not, so none of its ops get a scope from it.
    assert phases._scopes(keys, [1.0, 2.0, 11.0, 12.0], modules,
                          [table]) == ["embed", "sample", "", ""]
    assert phases._scopes(keys, [1.0, 2.0, 11.0, 12.0], modules, []) == \
        [""] * 4
    assert phases._key("%copy.2 = bf16[3,65,2,16,128]{4,3,2,1,0} copy("
                       "bf16[3,65,2,16,128]{4,3,2,1,0} %p.4)") == \
        ("copy.2", "bf16[3,65,2,16,128]{4,3,2,1,0}")


def test_idle_in_and_steps():
    assert phases.steps(T) == [(0.0, 72.0), (78.0, 100.0)]
    # schedule 50..56 (6) + 78..80 (2)
    assert phases.idle_in(T, ["serve.schedule"]) == pytest.approx(8.0)
    # upload 56..57 (1), dispatch 57..60 (3), commit 70..72 (2), 92..98 (6)
    assert phases.idle_in(T, phases.ENGINE) == pytest.approx(12.0)
    # inside both steps: 50..60, 70..72, 78..80, 88..100
    assert phases.idle_in(T, ["serve.step"]) == pytest.approx(26.0)


def test_per_layer_numbers_by_hand():
    assert phases.sched_idle_ms(T) == pytest.approx(8.0 / 2 * 1e-6)
    assert phases.engine_idle_ms(T) == pytest.approx(12.0 / 2 * 1e-6)
    # all idle 32 ns, 26 of it inside the steps
    assert phases.loop_idle_ms(T) == pytest.approx(6.0 / 2 * 1e-6)
    # phases hold 8 + 12 + wait's 88..92 (4) of the steps' 26
    assert phases.phase_idle_share(T) == pytest.approx(100.0 * 24 / 26)
    assert phases.sampler_ms_per_step(T) == pytest.approx(10.0 / 2 * 1e-6)
    # busy 68 ns: 13 unscoped, of it no kernel; 5 of it a pool copy
    assert phases.scoped_share(T) == pytest.approx(100.0 * 55 / 68)
    assert phases.pool_write_share(T, POOL) == pytest.approx(100.0 * 10 / 68)
    assert phases.unattributed_share(T, POOL) == pytest.approx(
        100.0 * 8 / 68)
    assert phases.scope_seconds(T) == pytest.approx(
        {"embed": 10e-9, "attention": 30e-9, "kv_write": 5e-9, "": 13e-9,
         "sample": 10e-9})


def test_idle_gaps_named_by_phase():
    assert phases.idle_gaps(T) == [
        ("serve.gc", pytest.approx(12e-9)),
        ("serve.schedule", pytest.approx(10e-9)),
        ("outside engine.step", pytest.approx(10e-9))]
    assert phases.breakdown(T)["idle_gaps"][0] == ["serve.gc",
                                                   pytest.approx(12e-9)]


def test_without_program_spans_matches_xtrace():
    assert phases.idle_gaps(PLAIN) == xtrace.idle_gaps(PLAIN)
    for f in (phases.sched_idle_ms, phases.engine_idle_ms,
              phases.loop_idle_ms, phases.phase_idle_share,
              phases.sampler_ms_per_step, phases.scoped_share):
        assert f(PLAIN) is None
    assert phases.pool_write_share(PLAIN, POOL) is None


DATA = Path(__file__).parent / "data"


def _xplane(text: str, tmp_path) -> str:
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_recorded_trace_without_program_spans(tmp_path):
    """The recorded ds7b.decode trace of test_xtrace: no program
    spans, no scopes."""
    path = _xplane((DATA / "ds7b_decode.xplane.pbtxt").read_text(), tmp_path)
    old, new = xtrace.load(path), phases.load(path)
    assert (new.window, new.ops, new.host) == (old.window, old.ops, old.host)
    assert phases.idle_gaps(new) == xtrace.idle_gaps(old)
    assert phases.breakdown(new)["idle_gaps"] == \
        xtrace.breakdown(old)["idle_gaps"]
    assert phases.scope_seconds(new) == {"": pytest.approx(
        sum(xtrace.op_seconds(old).values()))}
    assert phases.sched_idle_ms(new) is None
    assert phases.scoped_share(new) is None


@pytest.mark.parametrize("metric, value", [
    ("device_idle_share", 2.0743148604198125),
    ("host_ms_per_step", 5.5953574999999995)])
def test_accepted_readers_read_the_same_on_either_load(metric, value,
                                                        tmp_path):
    """The accepted per-layer readers that read only the trace give the
    numbers they gave when the benchmark was accepted, on the recorded
    trace, whether it was loaded by ``xtrace`` or by ``phases``; so does
    the breakdown."""
    import importlib.util
    import json
    from types import SimpleNamespace
    spec = importlib.util.spec_from_file_location(
        metric, Path(__file__).parents[1] / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    path = _xplane((DATA / "ds7b_decode.xplane.pbtxt").read_text(), tmp_path)
    old, new = xtrace.load(path), phases.load(path)
    assert mod.read(SimpleNamespace(trace=old)) == value
    assert mod.read(SimpleNamespace(trace=new)) == value
    assert json.dumps(xtrace.breakdown(new)) == \
        json.dumps(xtrace.breakdown(old))


# Two steps of sc2.code on one TPU v5 lite (a 4-s traced window, cut to
# 0.716-2.072 s of it): device ops named by their instruction and result
# shape, the XLA Modules line, the benchmark's and the program's host
# spans; with the compiled step's HLO cut to the computations that hold
# those ops (name, result shape, op_name).
SC2_POOL = (8193, 2, 16, 128)


@pytest.fixture(scope="module")
def sc2(tmp_path_factory):
    text = gzip.open(DATA / "sc2_code.xplane.pbtxt.gz", "rt").read()
    hlo = gzip.open(DATA / "sc2_code.hlo.txt.gz", "rt").read()
    path = _xplane(text, tmp_path_factory.mktemp("sc2"))
    return phases.load(path, [hlo]), phases.load(path)


def test_recorded_chip_trace_scopes(sc2):
    """The numbers that the full compiled HLO gives on this trace."""
    t, bare = sc2
    assert len(phases.steps(t)) == 2
    assert phases.scope_seconds(t) == pytest.approx({
        "": 0.109146962, "attention": 1.076767921, "embed": 0.000053256,
        "kv_write": 0.016362537, "mlp": 0.028959396, "head": 0.000816676,
        "sample": 0.108994601})
    kernel = {s for (n, _, _), s in zip(t.ops[0], t.scopes[0])
              if phases.KERNEL in n}
    assert kernel == {"attention"}
    assert phases.sampler_ms_per_step(t) == pytest.approx(54.497300)
    assert phases.scoped_share(t) == pytest.approx(91.861393)
    assert phases.pool_write_share(t, SC2_POOL) == pytest.approx(9.084359)
    assert phases.unattributed_share(t, SC2_POOL) == pytest.approx(0.274329)
    # Without the HLO the ops have no scope, and the spans still read.
    assert phases.scope_seconds(bare) == {"": pytest.approx(1.341101349)}
    assert phases.sampler_ms_per_step(bare) is None
    assert phases.sched_idle_ms(bare) == phases.sched_idle_ms(t)


def test_recorded_chip_trace_phases(sc2):
    t, _ = sc2
    assert phases.sched_idle_ms(t) == pytest.approx(0.5039155)
    assert phases.engine_idle_ms(t) == pytest.approx(2.536902)
    assert phases.loop_idle_ms(t) == pytest.approx(0.32833)
    assert phases.phase_idle_share(t) == pytest.approx(98.068350)
    gaps = phases.idle_gaps(t)
    assert gaps[:2] == [("outside engine.step", pytest.approx(0.006395233)),
                        ("serve.upload", pytest.approx(0.004060952))]
    assert {name for name, _ in gaps} <= {"outside engine.step",
                                          *phases.PHASES}
