"""Plain reference forward of the pool's decoder members.

A pre-norm decoder as the members' published configs describe it:
RMSNorm, grouped-query causal self attention with rotary embeddings
(rotate-half convention, ``rope_theta``) and, where the config has it,
RMSNorm on each query and key head; then a SwiGLU MLP, or a mixture of
experts that routes each token to its ``top_k`` experts by softmax
probability and mixes their SwiGLU outputs with those probabilities
renormalised over the chosen experts. Every expert runs for every token
and unchosen experts get weight 0: no capacity, no dropped tokens.

No cache, no batching tricks: one full causal pass over prompt plus
served tokens, in ``jnp`` at float32 with ``highest`` matmul precision
(the TPU would otherwise round float32 operands to bfloat16). The control
runs the same function in a lower dtype: bfloat16 throughout, or weights
in float8 with a scale per tensor and the rest in bfloat16.

Parameters are read in the layout the benchmark's weights are made in
(``bench/weights.py``): ``embedding/{table,head}``, ``final_norm/scale``
and one stacked layer block under ``pattern``.

A member reference (``"reference": "decoder"``): it keeps the contract of
``bench/reference/__init__.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * scale


def _rope(x, theta):
    """x (B, S, H, D) at positions 0..S-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m: dict, x, p):
    b, s, d = x.shape
    h_q, h_kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or d // h_q
    eps = m["rms_norm_eps"]
    at = p["mixer"]
    h = _rms(x, p["norm1"]["scale"], eps)
    q = (h @ at["wq"]).reshape(b, s, h_q, hd)
    k = (h @ at["wk"]).reshape(b, s, h_kv, hd)
    v = (h @ at["wv"]).reshape(b, s, h_kv, hd)
    if m["qk_norm"]:
        q = _rms(q, at["q_norm"]["scale"], eps)
        k = _rms(k, at["k_norm"]["scale"], eps)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, h_q // h_kv, axis=2)
    v = jnp.repeat(v, h_q // h_kv, axis=2)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.asarray(
        math.sqrt(hd), x.dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jnp.where(causal, att, jnp.asarray(-1e30, jnp.float32).astype(
        x.dtype))
    att = jax.nn.softmax(att, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, h_q * hd)
    x = x + o @ at["wo"]

    h = _rms(x, p["norm2"]["scale"], eps)
    f = p["ffn"]
    if m.get("num_local_experts"):
        probs = jax.nn.softmax(h @ f["router"], axis=-1)          # (B,S,E)
        top, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
        top = top / top.sum(axis=-1, keepdims=True)
        w = jnp.sum(jax.nn.one_hot(idx, m["num_local_experts"],
                                   dtype=x.dtype)
                    * top[..., None], axis=-2)                    # (B,S,E)
        g = jnp.einsum("bsd,edf->bsef", h, f["w_gate"])
        u = jnp.einsum("bsd,edf->bsef", h, f["w_up"])
        y = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g) * u, f["w_down"])
        x = x + jnp.einsum("bse,bsed->bsd", w, y)
    else:
        x = x + (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]
    return x, None


def lower(a, dtype):
    """``a`` in ``dtype``; a float8 type keeps its values and each
    tensor's scale (largest magnitude to the type's largest), computed on
    in bfloat16, as weights quantised to float8 are served."""
    if jnp.dtype(dtype).itemsize > 1:
        return a.astype(dtype)
    top = jnp.float32(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
    q = (a / scale).astype(dtype).astype(jnp.float32) * scale
    return q.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("member", "dtype"))
def _logits_at(params, tokens, read_at, *, member, dtype):
    m = dict(member)
    p = jax.tree.map(functools.partial(lower, dtype=dtype), params)
    (block,) = p["pattern"]
    x = jnp.take(p["embedding"]["table"], tokens, axis=0)
    x, _ = jax.lax.scan(functools.partial(_layer, m), x, block)
    x = _rms(x, p["final_norm"]["scale"], m["rms_norm_eps"])
    x = jnp.take_along_axis(x, read_at[..., None], axis=1)        # (B,P,D)
    logits = x @ p["embedding"]["head"][:, :m["vocab_size"]]
    return logits.astype(jnp.float32)


def logits_at(member: dict, params, tokens, read_at, dtype=jnp.float32):
    """Logits (B, P, vocab) at positions ``read_at`` (B, P) of a causal
    pass over ``tokens`` (B, S); tokens after a row's last read position
    do not reach it. ``member`` is the member's file under
    ``bench/members``: its published ``config`` (Hugging Face key names)
    and the ``architecture`` facts that the config keys do not name."""
    flat = {**member["config"], **member["architecture"]}
    key = tuple(sorted((k, v) for k, v in flat.items()
                       if not isinstance(v, (dict, list))))
    if jnp.dtype(dtype) == jnp.float32:
        with jax.default_matmul_precision("highest"):
            return _logits_at(params, tokens, read_at, member=key,
                              dtype=jnp.float32)
    return _logits_at(params, tokens, read_at, member=key, dtype=dtype)


def n_active(member: dict) -> int:
    """Parameters that multiply each token's activations, from the
    published config: attention projections, the dense FFN or the router
    plus ``num_experts_per_tok`` experts, and the LM head. The token
    table is a lookup and not counted."""
    c = member["config"]
    d, heads, kv = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"])
    hd = c.get("head_dim") or d // heads
    attn = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    if c.get("num_local_experts"):
        ffn = (d * c["num_local_experts"]
               + c["num_experts_per_tok"] * 3 * d * c["intermediate_size"])
    else:
        ffn = 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * (attn + ffn) + d * c["vocab_size"]


def check_sizes(member: dict, cfg) -> None:
    """The program's config must hold the sizes the member's file states
    (the reference runs from the file)."""
    c = member["config"]
    got = dict(hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
               num_attention_heads=cfg.n_heads,
               num_key_value_heads=cfg.n_kv_heads,
               vocab_size=cfg.vocab_size,
               head_dim=cfg.resolved_head_dim)
    want = dict(c, head_dim=c.get("head_dim")
                or c["hidden_size"] // c["num_attention_heads"])
    bad = {k: (v, want[k]) for k, v in got.items() if want[k] != v}
    if bad:
        raise ValueError(f"{member['name']}: program sizes differ from the "
                         f"published ones: {bad}")


def smoke_member(member: dict, cfg) -> dict:
    """The member's reference sizes at the program's reduced smoke widths
    (CPU rehearsal only)."""
    conf = dict(member["config"])
    conf.update(hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
                num_attention_heads=cfg.n_heads,
                num_key_value_heads=cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, vocab_size=cfg.vocab_size,
                intermediate_size=cfg.d_ff_expert or cfg.d_ff)
    if cfg.n_experts:
        conf.update(num_local_experts=cfg.n_experts,
                    num_experts_per_tok=cfg.top_k)
    return {**member, "config": conf}
