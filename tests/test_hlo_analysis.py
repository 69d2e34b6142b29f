"""HLO collective-byte accounting: synthetic text + a real lowered program."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import (collective_totals, shape_bytes)
from tests._multidevice import run_with_devices

SYNTH = """
HloModule test

%body.1 (p: (f32[8], s32[])) -> (f32[8], s32[]) {
  %p = parameter(0)
  %x = f32[8]{0} get-tuple-element(%p), index=0
  %ar = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={{0,1,2,3}}, to_apply=%sum
  ROOT %t = tuple(%ar, %i)
}

ENTRY %main (a: f32[16], b: bf16[32]) -> f32[16] {
  %a = parameter(0)
  %b = parameter(1)
  %ag = f32[64]{0} all-gather(f32[16]{0} %a), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = bf16[32]{0} collective-permute(bf16[32]{0} %b), source_target_pairs={{0,1}}
  %w = (f32[8], s32[]) while((f32[8], s32[]) %init), condition=%cond.1, body=%body.1
  ROOT %r = f32[16]{0} reduce-scatter(f32[64]{0} %ag), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""


def test_shape_bytes():
    assert shape_bytes("f32[8]") == 32
    assert shape_bytes("bf16[4,4]") == 32
    assert shape_bytes("(f32[2], s32[3])") == 8 + 12
    assert shape_bytes("pred[]") == 1


def test_synthetic_module_totals():
    t = collective_totals(SYNTH, trip_hints=[10])
    assert t["op_all-gather"] == 64          # operand f32[16]
    assert t["op_collective-permute"] == 64  # bf16[32]
    assert t["op_reduce-scatter"] == 256     # operand f32[64]
    # the while body's all-reduce runs 10× (trip hint)
    assert t["op_all-reduce"] == 32 * 10
    assert t["total_operand_bytes"] == 64 + 64 + 256 + 320


def test_wire_model_factors():
    t = collective_totals(SYNTH, trip_hints=[1])
    # ring all-reduce: 2·(n-1)/n · bytes, n=4
    assert t["wire_all-reduce"] == 2 * 3 / 4 * 32
    # all-gather counts result bytes: (n-1)/n · 256
    assert t["wire_all-gather"] == 3 / 4 * 256


def test_real_lowered_psum_counted():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, functools
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.launch.hlo_analysis import collective_totals

        mesh = make_mesh((4,), ("m",))
        f = shard_map(lambda x: jax.lax.psum(x, "m"),
                      mesh=mesh, in_specs=P("m"), out_specs=P())
        hlo = jax.jit(f).lower(jnp.zeros((64,), jnp.float32)).compile().as_text()
        t = collective_totals(hlo)
        assert t["op_all-reduce"] == 16 * 4, t   # 16 f32 per device
        print("OK")
    """, n_devices=4)
    assert "OK" in out
