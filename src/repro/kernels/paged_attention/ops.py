"""Public paged-attention entry point: kernel on TPU, jnp reference off it.

Accepts the serving layout directly — q ``(B, Hq, Lq, D)``, page pools
``(N, Hkv, page_size, D)``, a page table ``(B, P)`` and per-lane live
lengths ``(B,)`` — so the engine hands its pool straight in with no copies.
``Lq == 1`` is decode; ``Lq > 1`` is a chunked-prefill block whose rows sit
at positions ``kv_len - Lq + i`` (causal intra-chunk mask implied).
Optional ``k_scale``/``v_scale`` pools switch on the INT8 path (per-row
dequant inside the page loop).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.lut_exp import make_table
from repro.kernels.paged_attention.ref import (default_block_pages,
                                               paged_attention_reference)


def _use_kernel() -> bool:
    return jax.default_backend() == "tpu"


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    page_table: jax.Array, kv_len: jax.Array, *,
                    scale: Optional[float] = None,
                    cap: Optional[float] = None,
                    window: Optional[int] = None,
                    exp_mode: str = "lut",
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    block_pages: Optional[int] = None,
                    dequant: str = "block",
                    interpret: Optional[bool] = None) -> jax.Array:
    """Attention through the page table (no gathered cache view).

    q: (B, Hq, Lq, D) — a single decode row (Lq == 1) or a chunked-prefill
    block (Lq > 1) whose row ``i`` holds absolute position
    ``kv_len - Lq + i``; k_pool/v_pool: (N, Hkv, page_size, D); page_table:
    (B, P) int32; kv_len: (B,) live rows per lane, query chunk included.

    ``interpret`` selects the implementation: ``None`` (default) dispatches
    by platform — the compiled Pallas kernel on TPU, the jnp page-block
    scan everywhere else; ``True`` forces the Pallas kernel in interpret
    mode (tests exercise the kernel off-TPU this way); ``False`` forces the
    natively-compiled kernel and therefore requires a TPU.
    """
    b, hq, lq, d = q.shape
    hkv = k_pool.shape[1]
    assert hq % hkv == 0, f"GQA requires Hq % Hkv == 0, got {hq} % {hkv}"
    if scale is None:
        scale = d ** -0.5
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))

    if interpret is None and not _use_kernel():
        return paged_attention_reference(
            q, k_pool, v_pool, page_table, kv_len, scale=float(scale),
            cap=cap, window=window, exp_mode=exp_mode, k_scale=k_scale,
            v_scale=v_scale, block_pages=block_pages, dequant=dequant)
    if interpret is False and not _use_kernel():
        raise ValueError(
            "paged_attention(interpret=False) forces the natively-compiled "
            "Pallas kernel, which needs a TPU (current backend: "
            f"{jax.default_backend()!r}); pass interpret=True for interpret "
            "mode or interpret=None for the platform default")

    # The Pallas kernel walks ``block_pages`` pages per grid step and
    # dequantises per KV row; the `dequant` knob only shapes the jnp scan.
    from repro.kernels.paged_attention.kernel import paged_attention_4d
    g = hq // hkv
    out = paged_attention_4d(
        q.reshape(b, hkv, g * lq, d), k_pool, v_pool, k_scale, v_scale,
        page_table, kv_len, make_table(), scale=float(scale), cap=cap,
        window=window, exp_mode=exp_mode, group=g, q_len=lq,
        block_pages=block_pages or default_block_pages(k_pool.shape[2]),
        interpret=bool(interpret) if interpret is not None
        else not _use_kernel())
    return out.reshape(b, hq, lq, v_pool.shape[-1])
