"""The main path's Pallas kernels compile for one TPU v5e chip.

No chip is needed: the TPU compiler is installed and compiles for a chip
that is described, not attached.  This catches what interpret mode
accepts and Mosaic refuses (blocks that break the tiling rule, dynamic
slices of values, lane-flattening reshapes).  Shapes are deepseek-7b's:
32 KV heads of 128, pages of 16 rows, q-blocks of 32.
"""
import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.lut_exp import make_table
from repro.kernels.lut_exp.kernel import lut_exp_2d
from repro.kernels.paged_attention import paged_attention_varlen
from repro.kernels.paged_attention.kernel import KERNEL_NAME
from repro.kernels.streaming_attention.kernel import attention_3d

ops = importlib.import_module("repro.kernels.paged_attention.ops")

H, D, PS, BQ = 32, 128, 16, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                                # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("exp_mode", ["lut", "exact"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_varlen_paged_kernel_compiles(one_chip, monkeypatch, kv, exp_mode):
    # The entry point picks the kernel by the default backend, which is
    # the CPU here; the compile targets the described chip.
    monkeypatch.setattr(ops, "_use_kernel", lambda: True)
    t, n, p, lanes = 64, 64, 8, 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((n, H, PS, D), jnp.int8 if kv == "int8" else jnp.bfloat16)
    scale = sds((n, H, PS), jnp.float32) if kv == "int8" else None

    def step(q, kp, vp, ks, vs, pages, pos, cu):
        return paged_attention_varlen(
            q, kp, vp, pages, pos, cu_seqlens=cu, exp_mode=exp_mode,
            k_scale=ks, v_scale=vs, block_q=BQ, interpret=False)

    hlo = _compiled_text(step, sds((t, H, D), jnp.bfloat16), pool, pool,
                         scale, scale, sds((t, p), jnp.int32),
                         sds((t,), jnp.int32), sds((lanes + 1,), jnp.int32))
    assert "tpu_custom_call" in hlo


# (Hkv, G, Lq, table width P, q-blocks NB) of the two benchmark cells'
# steps: deepseek-7b decode (rows 16) and with a prefill chunk (rows 32),
# starcoder2-3b's mixed step (GQA 24:2, rows 12·32 = 384).
CELL_SHAPES = {
    "ds7b-rows16": (32, 1, 16, 128, 18),
    "ds7b-rows32": (32, 1, 32, 128, 18),
    "sc2-rows384": (2, 12, 32, 256, 50),
}


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("cell", list(CELL_SHAPES))
@pytest.mark.parametrize("block_pages", [8, 16])
def test_page_block_kernel_compiles_at_cell_widths(one_chip, monkeypatch,
                                                   block_pages, cell, kv):
    # 16-row pages, 8 (128 rows) or 16 (the TPU row of the kernel table)
    # per grid step.
    monkeypatch.setattr(ops, "_use_kernel", lambda: True)
    hkv, g, lq, p, nb = CELL_SHAPES[cell]
    n = 512

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((n, hkv, PS, D), jnp.int8 if kv == "int8" else jnp.bfloat16)
    scale = sds((n, hkv, PS), jnp.float32) if kv == "int8" else None

    def attend(q, kp, vp, ks, vs, tbl, lens):
        return ops.paged_attention(q, kp, vp, tbl, lens, k_scale=ks,
                                   v_scale=vs, block_pages=block_pages,
                                   interpret=False)

    hlo = _compiled_text(attend, sds((nb, hkv * g, lq, D), jnp.bfloat16),
                         pool, pool, scale, scale, sds((nb, p), jnp.int32),
                         sds((nb,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_varlen_kernel_is_named(one_chip, monkeypatch):
    # A trace finds the kernel's device ops by the substring
    # "paged_attention" of their names: the kernel's custom call carries
    # it, and no other instruction of the step does.
    monkeypatch.setattr(ops, "_use_kernel", lambda: True)
    t, n, p, lanes = 64, 64, 8, 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((n, H, PS, D), jnp.bfloat16)
    hlo = _compiled_text(
        lambda q, kp, vp, pages, pos, cu: paged_attention_varlen(
            q, kp, vp, pages, pos, cu_seqlens=cu, block_q=BQ,
            interpret=False),
        sds((t, H, D), jnp.bfloat16), pool, pool, sds((t, p), jnp.int32),
        sds((t,), jnp.int32), sds((lanes + 1,), jnp.int32))
    named = [line for line in hlo.splitlines()
             if re.match(r"\s*(ROOT )?%\S*paged_attention", line)]
    assert named and all(
        re.match(rf"\s*(ROOT )?%{KERNEL_NAME}(\.\d+)? = ", line)
        and 'custom_call_target="tpu_custom_call"' in line
        for line in named), named


def test_streaming_kernel_compiles_at_block_512(one_chip):
    x = jax.ShapeDtypeStruct((H, 512, D), jnp.bfloat16, sharding=one_chip)
    table = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip)

    def attend(q, k, v, tab):
        return attention_3d(q, k, v, tab, scale=D ** -0.5, causal=True,
                            window=None, cap=None, exp_mode="lut",
                            block_q=512, block_k=512, kv_len=512,
                            q_offset=0, group=1)

    assert "tpu_custom_call" in _compiled_text(attend, x, x, x, table)


def test_lut_exp_kernel_compiles(one_chip):
    x = jax.ShapeDtypeStruct((512, 128), jnp.float32, sharding=one_chip)
    table = jax.ShapeDtypeStruct(make_table().shape, jnp.float32,
                                 sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(
        lambda a, tab: lut_exp_2d(a, tab, block_m=256), x, table)
