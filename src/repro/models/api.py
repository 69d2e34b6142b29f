"""Uniform model API: ``build_model(cfg)`` → init / loss / prefill / decode.

Every family exposes the same four entry points so the launcher, trainer,
serving engine, dry-run, and benchmarks are family-agnostic.  ``input_specs``
produces ShapeDtypeStruct stand-ins for every input of a given step kind —
the dry-run lowers against these (no allocation).

Step kinds (assignment shape cells):
  train    → loss+grad over (tokens, labels)            [train_4k]
  prefill  → fill KV/SSM caches for a full sequence     [prefill_32k]
  decode   → one new token against a length-L cache     [decode_32k, long_500k]
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.core.attention_api import backend_for_config, get_backend
from repro.models import encdec as ED
from repro.models import lm as LM
from repro.models.lm import cross_entropy

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], Params]
    loss: Callable[[Params, Dict[str, jax.Array]], Tuple[jax.Array, Dict]]
    init_cache: Callable[..., Params]
    prefill: Optional[Callable] = None       # (params, batch, caches) → (logits, state)
    decode_step: Optional[Callable] = None   # (params, token, state, index) → (logits, state)
    # (params, tokens (B, C), pools, page_table (B, P), kv_len (B,),
    # q_len (B,)) → (last-row logits (B, V), pools): one unified serving
    # step — right-aligned chunked prefill, decode (C == 1) and idle lanes
    # mixed in one batch, KV rows written in place through the table
    # (EngineCore.step's workhorse; there is no separate paged decode entry)
    prefill_chunk_paged: Optional[Callable] = None
    # (params, tokens (T,), pools, token_pages (T, P), pos (T,),
    # last_idx (lanes,) or (lanes, 1+k)) → (logits (lanes[, 1+k], V),
    # pools): the token-level ragged serving step — one packed stream of
    # T = Σ live tokens, no (lanes, C) padding (EngineCore mode="ragged"'s
    # workhorse; the 2-D last_idx form is the speculative verify step,
    # extracting every drafted position's logits from the same stream)
    step_ragged: Optional[Callable] = None


# --------------------------------------------------------------------------
# family wiring
# --------------------------------------------------------------------------

def _bert_loss(cfg, params, batch):
    logits, _, aux = LM.lm_apply(cfg, params, batch["tokens"], causal=False)
    ce = cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    return ce, {"ce": ce, "aux": aux}


def _bert_encode(cfg, params, batch, caches=None):
    logits, _, _ = LM.lm_apply(cfg, params, batch["tokens"], causal=False)
    return logits, caches


def _lm_loss_with_labels(cfg, params, batch):
    if "labels" in batch and batch["labels"].shape == batch["tokens"].shape:
        prefix = batch.get("prefix_embed")
        logits, _, aux = LM.lm_apply(cfg, params, batch["tokens"],
                                     prefix_embed=prefix)
        lp = 0 if prefix is None else prefix.shape[1]
        ce = cross_entropy(logits[:, lp:], batch["labels"],
                           batch.get("loss_mask"))
        loss = ce + cfg.router_aux_weight * aux
        return loss, {"ce": ce, "aux": aux}
    return LM.lm_loss(cfg, params, batch)


def _lm_prefill(cfg, params, batch, caches):
    return LM.lm_prefill(cfg, params, batch["tokens"], caches,
                         prefix_embed=batch.get("prefix_embed"))


def _encdec_prefill(cfg, params, batch, caches):
    self_c = caches["self"] if "self" in caches else caches
    logits, new_c, ckv = ED.encdec_prefill(cfg, params, batch["frames"],
                                           batch["tokens"], self_c)
    return logits, {"self": new_c, "cross": ckv}


def _encdec_decode(cfg, params, token, state, index):
    logits, caches = ED.encdec_decode_step(cfg, params, token, state["self"],
                                           state["cross"], index)
    return logits, {"self": caches, "cross": state["cross"]}


def build_model(cfg: ModelConfig) -> Model:
    # Fail fast on a mistyped backend name here rather than deep inside a
    # jitted trace (resolution itself is per-call; "auto" always resolves).
    name = backend_for_config(cfg.attn_backend, cfg.attn_impl)
    if name != "auto":
        get_backend(name)
    if cfg.family == "encdec":
        return Model(
            cfg=cfg,
            init=jax.jit(functools.partial(ED.encdec_init, cfg=cfg)),
            loss=functools.partial(ED.encdec_loss, cfg),
            init_cache=functools.partial(ED.encdec_cache_init, cfg),
            prefill=functools.partial(_encdec_prefill, cfg),
            decode_step=functools.partial(_encdec_decode, cfg),
        )
    if cfg.family == "bert":
        return Model(
            cfg=cfg,
            init=jax.jit(functools.partial(LM.lm_init, cfg=cfg)),
            loss=functools.partial(_bert_loss, cfg),
            init_cache=functools.partial(LM.trunk_cache_init, cfg),
            prefill=functools.partial(_bert_encode, cfg),
            decode_step=None,   # encoder-only: no decode step (assignment)
        )
    return Model(
        cfg=cfg,
        init=jax.jit(functools.partial(LM.lm_init, cfg=cfg)),
        loss=functools.partial(_lm_loss_with_labels, cfg),
        init_cache=functools.partial(LM.trunk_cache_init, cfg),
        prefill=functools.partial(_lm_prefill, cfg),
        decode_step=functools.partial(
            lambda cfg, params, token, state, index:
            LM.lm_decode_step(cfg, params, token, state, index), cfg),
        prefill_chunk_paged=functools.partial(LM.lm_prefill_chunk_paged, cfg),
        step_ragged=functools.partial(LM.lm_step_ragged, cfg),
    )


def init_params(cfg: ModelConfig, seed: int = 0, mesh: Any = None
                ) -> Params:
    """Seeded random params, built on the device by one jitted program.

    ``Model.init`` is jitted, so each leaf's f32 draw, scale and cast to
    the param dtype fuse: the resident peak is the finished params, not an
    f32 copy of the largest stacked leaf beside them.  With ``mesh`` the
    params are created replicated across it in place, not put on one
    device and copied out.
    """
    init = build_model(cfg).init
    if mesh is not None:
        init = jax.jit(init,
                       out_shardings=NamedSharding(mesh, PartitionSpec()))
    return init(jax.random.PRNGKey(seed))


# --------------------------------------------------------------------------
# ShapeDtypeStruct input specs (dry-run; no allocation)
# --------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg: ModelConfig, batch: int, seq: int,
                with_labels: bool = True) -> Dict[str, Any]:
    """Training/prefill batch stand-ins, incl. modality-frontend stubs."""
    specs: Dict[str, Any] = {"tokens": _sds((batch, seq), jnp.int32)}
    if with_labels:
        specs["labels"] = _sds((batch, seq), jnp.int32)
    if cfg.family == "encdec":
        specs["frames"] = _sds((batch, cfg.frontend_len, cfg.d_model),
                               jnp.bfloat16)
    if cfg.family == "vlm":
        specs["prefix_embed"] = _sds((batch, cfg.frontend_len, cfg.d_model),
                                     jnp.bfloat16)
    return specs


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    model = build_model(cfg)
    specs = jax.eval_shape(lambda: model.init_cache(batch, max_len))
    if cfg.family == "encdec":
        params = jax.eval_shape(
            lambda: ED.encdec_init(jax.random.PRNGKey(0), cfg))
        enc = _sds((batch, cfg.frontend_len, cfg.d_model), jnp.bfloat16)
        ckv = jax.eval_shape(
            lambda p, e: ED.cross_kvs_init(cfg, p, e), params, enc)
        return {"self": specs, "cross": ckv}
    return specs


def input_specs(cfg: ModelConfig, kind: str, seq: int, batch: int
                ) -> Dict[str, Any]:
    """All inputs (except params/opt-state) of the step function for ``kind``."""
    # vlm caches also hold the modality prefix rows
    cache_len = seq + (cfg.frontend_len if cfg.family == "vlm" else 0)
    if kind == "train":
        return {"batch": batch_specs(cfg, batch, seq)}
    if kind == "prefill":
        return {"batch": batch_specs(cfg, batch, seq, with_labels=False),
                "caches": cache_specs(cfg, batch, cache_len)}
    if kind == "decode":
        return {"token": _sds((batch,), jnp.int32),
                "state": cache_specs(cfg, batch, cache_len),
                "index": _sds((), jnp.int32)}
    raise ValueError(f"unknown step kind {kind!r}")
