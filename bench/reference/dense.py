"""Plain float32 reference of the dense decoder, and the weights the
benchmark makes for it.

Pre-norm decoder: x += attn(rms(x)); x += swiglu(rms(x)); logits =
rms(x)·W_head.  Attention is causal grouped-query attention (query head h
reads key/value head h // (H / Kv)) with rotate-half RoPE on positions
0..S−1, θ from the configuration, scores scaled by 1/√head_dim.  RMSNorm is
x·(mean(x²) + 1e-6)^-½·g.  The loss is the masked mean of token
cross-entropy.  Each adapted projection computes x·W + (α/r)·(x·A)·B: the
adapter is applied beside W, never merged into it.  Everything is float32;
``q`` wraps every operand of a matrix product (the identity here, a rounding
for the control).

It reads the served weights in their layout: a stack over layers under
``groups/sub_0`` and one ``{"A", "B"}`` pair per adapted projection, the
layers below the cut held by the client and the rest by the server.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

TARGETS = {"attn": ("wq", "wk", "wv", "wo"), "mlp": ("w_gate", "w_up", "w_down")}
LORA_B_STD = 0.01  # adapters mid-way through a fine-tune, not at B = 0


def init_leaf(names: tuple, shape: tuple, key) -> jax.Array:
    """Seeded float32 value of one weight, by its name in the served layout."""
    name = names[-1]
    n = jax.random.normal(key, shape, jnp.float32)
    if name == "tokens":
        return 0.02 * n
    if name == "scale":
        return 1.0 + 0.1 * n
    if name == "A":
        return n / shape[-1]
    if name == "B":
        return LORA_B_STD * n
    return n / math.sqrt(shape[-2])  # projections and the head: fan-in


def _lora_key(block: str, name: str) -> str:
    return f"['groups']['sub_0']['{block}']['{name}']"


def unpack(base, lora_c, lora_s, cfg: dict):
    """(params, lora) in float32; lora: {name: {"A": (L, d_in, r), "B": (L, r, d_out)}}."""
    f32 = lambda t: jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), t)  # noqa: E731
    g = base["groups"]["sub_0"]
    params = {"embed": f32(base["embed"]["tokens"]), "head": f32(base["embed"]["head"]),
              "final": f32(base["final_norm"]["scale"]),
              "layers": {"norm1": f32(g["norm1"]["scale"]),
                         "norm2": f32(g["norm2"]["scale"]),
                         **{n: f32(g[b][n]) for b, ns in TARGETS.items() for n in ns}}}
    lora = {}
    for b, ns in TARGETS.items():
        for n in ns:
            k = _lora_key(b, n)
            parts = [f32(side[k]) for side in (lora_c, lora_s) if k in side]
            lora[n] = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
    return params, lora


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * g


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs  # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(x, p, lo, cfg, q):
    B, S, _ = x.shape
    H, Kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    s = cfg["lora_alpha"] / cfg["lora_rank"]

    def lin(h, n):
        return q(h) @ q(p[n]) + s * (q(q(h) @ q(lo[n]["A"])) @ q(lo[n]["B"]))

    h = _rms(x, p["norm1"])
    qh = _rope(lin(h, "wq").reshape(B, S, H, hd), cfg["rope_theta"])
    k = _rope(lin(h, "wk").reshape(B, S, Kv, hd), cfg["rope_theta"])
    v = lin(h, "wv").reshape(B, S, Kv, hd)
    k, v = jnp.repeat(k, H // Kv, axis=2), jnp.repeat(v, H // Kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(k)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", q(probs), q(v)).reshape(B, S, H * hd)
    x = x + lin(o, "wo")
    h = _rms(x, p["norm2"])
    return x + lin(jax.nn.silu(lin(h, "w_gate")) * lin(h, "w_up"), "w_down")


def loss(params, lora, batch, cfg: dict, q):
    """Masked mean token cross-entropy of one client's batch."""
    x = params["embed"][batch["tokens"]]

    @jax.checkpoint
    def body(x, pl):
        p, lo = pl
        return _layer(x, p, lo, cfg, q), None

    x, _ = jax.lax.scan(body, x, (params["layers"], lora))
    logits = q(_rms(x, params["final"])) @ q(params["head"])
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    m = batch["mask"]
    return jnp.sum(nll * m) / jnp.sum(m)
