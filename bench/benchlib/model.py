"""Weights in the published layout, drawn from the seed, and the plain
float32 reference of the dense decoder.

The reference reads the configuration's published keys (HF ``config.json``
names) and nothing of the program.  It follows the published description:

- llama (DeepSeek LLM): RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``, gated
  SiLU MLP ``down(silu(gate(x)) * up(x))``, untied head, no biases;
- starcoder2: LayerNorm with bias (``norm_epsilon``), ``gelu`` (tanh form)
  MLP with biases, biases on q/k/v, grouped KV heads, tied head.

Both use RoPE in the RoFormer form (rotated pairs ``(2i, 2i+1)``, frequency
``theta^(-2i/d_head)``), causal softmax attention scaled by
``d_head^-0.5``, pre-norm residual blocks and a final norm.

Where the program departs from the published model, the benchmark's
weights are drawn so that both compute the same function, and the
configuration file names the departure under ``assumed``; the adapter
below is the only place that knows the program's parameter layout.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    layers: int
    hq: int
    hkv: int
    dh: int
    f: int
    vocab: int
    norm: str          # rmsnorm | layernorm
    eps: float
    act: str           # silu | gelu_tanh
    gated: bool
    bias: bool
    tied: bool
    theta: float
    init_std: float    # the embedding's draw (``initializer_range``)


_ACTS = {"silu": "silu", "gelu_pytorch_tanh": "gelu_tanh"}


def arch_of(cfg: dict) -> Arch:
    """The published configuration's keys → the reference's structure."""
    mt = cfg["model_type"]
    if mt == "llama":
        norm, eps = "rmsnorm", cfg["rms_norm_eps"]
        gated, bias = True, bool(cfg.get("attention_bias", False))
    elif mt == "starcoder2":
        norm = "layernorm" if cfg["norm_type"] == "layer_norm" else "rmsnorm"
        eps = cfg["norm_epsilon"]
        gated, bias = cfg.get("mlp_type", "default") != "default", \
            bool(cfg["use_bias"])
    else:
        raise ValueError(f"the reference has no model_type {mt!r}")
    hq = cfg["num_attention_heads"]
    return Arch(d=cfg["hidden_size"], layers=cfg["num_hidden_layers"], hq=hq,
                hkv=cfg.get("num_key_value_heads", hq),
                dh=cfg.get("head_dim") or cfg["hidden_size"] // hq,
                f=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                norm=norm, eps=float(eps), act=_ACTS[cfg["hidden_act"]],
                gated=gated, bias=bias, tied=bool(cfg["tie_word_embeddings"]),
                theta=float(cfg["rope_theta"]),
                init_std=float(cfg.get("initializer_range", 0.02)))


# ------------------------------------------------------------- weights --

def leaf_shapes(a: Arch) -> Dict[str, tuple]:
    """name → (shape, kind); per-layer leaves are stacked on axis 0."""
    L, d, f = a.layers, a.d, a.f
    s = {"embed": ((a.vocab, d), "embed")}

    def norm(p, lead):
        s[p + "_w"] = (lead + (d,), "normw")
        if a.norm == "layernorm":
            s[p + "_b"] = (lead + (d,), "b")

    def dense(w, b, din, dout):
        s[w] = ((L, din, dout), "w")
        if b:
            s[b] = ((L, dout), "b")

    norm("n1", (L,))
    bq = "bq" if a.bias else None
    dense("wq", bq, d, a.hq * a.dh)
    dense("wk", bq and "bk", d, a.hkv * a.dh)
    dense("wv", bq and "bv", d, a.hkv * a.dh)
    dense("wo", None, a.hq * a.dh, d)
    norm("n2", (L,))
    if a.gated:
        dense("w_gate", None, d, f)
        dense("w_up", None, d, f)
        dense("w_down", None, f, d)
    else:
        dense("w_fc", "b_fc", d, f)
        dense("w_proj", "b_proj", f, d)
    norm("nf", ())
    if not a.tied:
        s["lm_head"] = ((d, a.vocab), "w")
    return s


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (above 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _draw(a: Arch, key):
    out = {}
    for i, (name, (shape, kind)) in enumerate(sorted(leaf_shapes(a).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if kind == "embed":
            z = z * a.init_std
        elif kind == "w":
            z = z * shape[-2] ** -0.5
        elif kind == "b":
            z = z * 0.1
        elif kind == "normw":
            z = 1.0 + 0.1 * z
        out[name] = z.astype(jnp.bfloat16)
    return out


def make_weights(a: Arch, seed: int):
    """Every weight, in bfloat16 on the default device, by one jitted call."""
    return jax.jit(functools.partial(_draw, a))(seed_key(seed))


def program_params(a: Arch, w) -> dict:
    """The same weights in the program's parameter tree (``repro.models``:
    one scanned period of one layer, ``x @ W`` dense layout).  The program's
    RMSNorm multiplies by ``1 + scale``, so its scale is ``w - 1``: exact in
    bfloat16 for weights drawn in bfloat16 near 1."""
    def norm(p):
        if a.norm == "layernorm":
            return {"scale": w[p + "_w"], "bias": w[p + "_b"]}
        return {"scale": (w[p + "_w"].astype(jnp.float32) - 1.0
                          ).astype(jnp.bfloat16)}

    def dense(name, b=None):
        out = {"w": w[name]}
        if b is not None and a.bias:
            out["b"] = w[b]
        return out

    attn = {"wq": dense("wq", "bq"), "wk": dense("wk", "bk"),
            "wv": dense("wv", "bv"), "wo": dense("wo")}
    if a.gated:
        mlp = {"up": dense("w_up"), "down": dense("w_down"),
               "gate": dense("w_gate")}
    else:
        mlp = {"up": dense("w_fc", "b_fc"), "down": dense("w_proj", "b_proj")}
    layer = {"ln1": norm("n1"), "attn": attn, "ln2": norm("n2"), "mlp": mlp}
    tree = {"embed": {"tokens": w["embed"]},
            "trunk": {"periods": {"0": layer}}, "final_norm": norm("nf")}
    if not a.tied:
        tree["lm_head"] = {"w": w["lm_head"]}
    return tree


def check_layout(params, expected) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of
    ``expected`` (the program's own ``eval_shape`` of its init)."""
    got = jax.tree.structure(params)
    want = jax.tree.structure(expected)
    if got != want:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{got}\nvs\n{want}")
    for x, e in zip(jax.tree.leaves(params), jax.tree.leaves(expected)):
        if x.shape != e.shape or x.dtype != e.dtype:
            raise ValueError(f"leaf {x.shape} {x.dtype} vs program's "
                             f"{e.shape} {e.dtype}")


# ----------------------------------------------------------- reference --

def _fq(x, axis):
    """Fake int8 quantisation with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s) * s


def _mm(x, w, lowp: bool):
    w = w.astype(jnp.float32)
    if lowp:            # W8A8: rows of x, output columns of w
        x, w = _fq(x, -1), _fq(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _norm(a: Arch, x, w, b):
    w = w.astype(jnp.float32)
    if a.norm == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + a.eps) * w + b.astype(jnp.float32)
    ms = (x * x).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + a.eps) * w


def _rope(a: Arch, x, pos):
    """x (L, H, dh); rotate pairs (2i, 2i+1) by pos * theta^(-2i/dh)."""
    freqs = a.theta ** (-jnp.arange(0, a.dh, 2, dtype=jnp.float32) / a.dh)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs       # (L, 1, dh/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)


def _attention(a: Arch, q, k, v, block: int):
    """Causal attention, query rows in blocks: q (L, hq, dh), k/v (L, hkv, dh)."""
    n = q.shape[0]
    g = a.hq // a.hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(n)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * a.dh ** -0.5
        qpos = i * block + jnp.arange(block)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(n // block))
    return out.reshape(n, a.hq * a.dh)


def _act(a: Arch, x):
    return jax.nn.silu(x) if a.act == "silu" else jax.nn.gelu(x, approximate=True)


def hidden(a: Arch, w, tokens, lowp: bool = False, block: int = 256):
    """Final-norm hidden states (L, d) in float32 for one sequence."""
    n = tokens.shape[0]
    pos = jnp.arange(n)
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    stacked = {k: w[k] for k in leaf_shapes(a)
               if k not in ("embed", "lm_head") and not k.startswith("nf")}
    zero = jnp.zeros((a.d,), jnp.float32)

    def bias(lw, k):
        return lw[k].astype(jnp.float32) if k in lw else 0.0

    def layer(x, lw):
        h = _norm(a, x, lw["n1_w"], lw.get("n1_b", zero))
        q = (_mm(h, lw["wq"], lowp) + bias(lw, "bq")).reshape(n, a.hq, a.dh)
        k = (_mm(h, lw["wk"], lowp) + bias(lw, "bk")).reshape(n, a.hkv, a.dh)
        v = (_mm(h, lw["wv"], lowp) + bias(lw, "bv")).reshape(n, a.hkv, a.dh)
        o = _attention(a, _rope(a, q, pos), _rope(a, k, pos), v,
                       min(block, n))
        x = x + _mm(o, lw["wo"], lowp)
        h = _norm(a, x, lw["n2_w"], lw.get("n2_b", zero))
        if a.gated:
            m = _act(a, _mm(h, lw["w_gate"], lowp)) * _mm(h, lw["w_up"], lowp)
            m = _mm(m, lw["w_down"], lowp)
        else:
            m = _act(a, _mm(h, lw["w_fc"], lowp) + bias(lw, "b_fc"))
            m = _mm(m, lw["w_proj"], lowp) + bias(lw, "b_proj")
        return x + m, None

    x, _ = jax.lax.scan(layer, x, stacked)
    return _norm(a, x, w["nf_w"], w.get("nf_b", zero))


def logits(a: Arch, w, h, lowp: bool = False):
    head = w["embed"].T if a.tied else w["lm_head"]
    return _mm(h, head, lowp)


@functools.partial(jax.jit, static_argnums=(0, 4))
def score(a: Arch, w, tokens, temps, control: bool = False):
    """Per position ``i`` of one sequence (L,), for the token ``tokens[i+1]``
    that follows it → ``(gap, above)``, each (L,) float32 (last row 0):

    - ``gap``: the reference's best logit minus its logit for that token;
    - ``above``: the reference's probability mass, at temperature
      ``temps[i]`` (1 where it is 0), of the tokens whose logit is strictly
      above that token's: a sampler with nucleus ``top_p`` may pick the
      token only where ``above < top_p``.

    With ``control`` the token is instead the one that the W8A8 (int8
    weights and activations) forward of the same reference puts first at
    that position: the lower-precision control."""
    with jax.default_matmul_precision("highest"):
        lg = logits(a, w, hidden(a, w, tokens))
        best = lg.max(-1)
        if control:
            lo = logits(a, w, hidden(a, w, tokens, True), True)
            pick = jnp.argmax(lo, -1)
        else:
            pick = jnp.concatenate([tokens[1:], tokens[:1]])
        got = jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        t = jnp.where(temps > 0, temps, 1.0)[:, None]
        p = jax.nn.softmax((lg - best[:, None]) / t, axis=-1)
        above = jnp.sum(jnp.where(lg > got[:, None], p, 0.0), axis=-1)
        return (best - got).at[-1].set(0.0), above.at[-1].set(0.0)


def served_scores(a: Arch, w, prompt, served, temperature: float,
                  length: int, control: bool = False):
    """``(gap, above)`` of each served token of one request, by the
    reference over ``prompt ⊕ served`` padded to ``length`` rows."""
    seq = np.zeros((length,), np.int32)
    full = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(served, np.int32)])
    seq[:len(full)] = full
    temps = jnp.full((length,), temperature, jnp.float32)
    gap, above = score(a, w, jnp.asarray(seq), temps, control)
    lp, n = len(prompt), len(served)
    return (np.asarray(gap)[lp - 1:lp - 1 + n],
            np.asarray(above)[lp - 1:lp - 1 + n])
