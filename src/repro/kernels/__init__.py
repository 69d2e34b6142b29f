"""Pallas TPU kernels for HASTILY's compute hot-spots.

Three kernels, each ``kernel.py`` (pl.pallas_call + BlockSpec VMEM tiling) +
``ops.py`` (jit'd wrapper; interpret=True off-TPU) + ``ref.py`` (pure-jnp
oracle):

- ``lut_exp``              — the UCLM LUT exponential; table lookup as an
                             exact select over the 128 entries (paper §III).
- ``streaming_attention``  — fine-grained-pipelined flash-style attention
                             with the LUT softmax inside (paper §IV).
- ``paged_attention``      — decode attention that reads KV pages in place
                             through the page table (scalar-prefetch index
                             maps; online-softmax combine across pages).
- ``int8_matmul``          — int8×int8→int32 tiled matmul (paper §V).
"""
from repro.kernels.lut_exp import lut_exp, lut_exp_ref
from repro.kernels.streaming_attention import streaming_attention, attention_ref
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_reference)
from repro.kernels.int8_matmul import int8_matmul, int8_matmul_ref

__all__ = ["lut_exp", "lut_exp_ref",
           "streaming_attention", "attention_ref",
           "paged_attention", "paged_attention_reference",
           "int8_matmul", "int8_matmul_ref"]
