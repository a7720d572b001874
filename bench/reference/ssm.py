"""Plain float32 reference of the Mamba-2 (SSD) stack, and the weights the
benchmark makes for it.

Per layer (arXiv:2405.21060, one B/C group): x += out_proj(gnorm(y·silu(z)))
with [z, xBC, dt] = in_proj(rms(x)); xBC through a causal depthwise
convolution of width W and SiLU, split into x (H heads of P), B and C
(N each); dt = softplus(dt + dt_bias), A = −exp(A_log).  The selective scan
is written in its quadratic (attention-like) dual over the whole sequence,
not in chunks:

    y_t = Σ_{s≤t} exp(Σ_{s<u≤t} dt_u·A) · (C_t·B_s) · dt_s·x_s + D·x_t,

with the decay sums taken as differences of one cumulative sum per head.
gnorm is RMSNorm (eps 1e-6) with its own gain.  The head is tied to the
embedding.  Adapted projections compute x·W + (α/r)·(x·A)·B.  float32
throughout; ``q`` wraps every operand of a matrix product.

At 2 k tokens the cumulative decay reaches some hundreds, so the decay
exponents carry an absolute error near 1e-4: ten times below one bfloat16
rounding, which is what the program is compared at.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

TARGETS = ("in_proj", "out_proj")
LORA_B_STD = 0.01  # adapters mid-way through a fine-tune, not at B = 0


def init_leaf(names: tuple, shape: tuple, key) -> jax.Array:
    """Seeded float32 value of one weight, by its name in the served layout."""
    name = names[-1]
    n = jax.random.normal(key, shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if name == "tokens":
        return 0.02 * n
    if name in ("scale", "norm_scale", "D_skip"):
        return 1.0 + 0.1 * n
    if name == "A_log":  # A in [1, 16], as Mamba-2 initialises it
        return jnp.log(1.0 + 15.0 * u)
    if name == "dt_bias":  # softplus⁻¹ of dt log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "conv_b":
        return 0.02 * n
    if name == "A":
        return n / shape[-1]
    if name == "B":
        return LORA_B_STD * n
    return n / math.sqrt(shape[-2])  # in_proj, out_proj, conv_w: fan-in


def _lora_key(name: str) -> str:
    return f"['groups']['sub_0']['mamba']['{name}']"


def unpack(base, lora_c, lora_s, cfg: dict):
    f32 = lambda t: jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), t)  # noqa: E731
    g = base["groups"]["sub_0"]
    params = {"embed": f32(base["embed"]["tokens"]),
              "final": f32(base["final_norm"]["scale"]),
              "layers": {"norm1": f32(g["norm1"]["scale"]), **f32(g["mamba"])}}
    lora = {}
    for n in TARGETS:
        k = _lora_key(n)
        parts = [f32(side[k]) for side in (lora_c, lora_s) if k in side]
        lora[n] = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
    return params, lora


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * g


def _layer(x, p, lo, cfg, q):
    Bsz, S, D = x.shape
    N, P = cfg["ssm_state"], cfg["ssm_head_dim"]
    d_inner = cfg["ssm_expand"] * D
    H = d_inner // P
    W = cfg["ssm_conv_width"]
    s = cfg["lora_alpha"] / cfg["lora_rank"]

    def lin(h, n):
        return q(h) @ q(p[n]) + s * (q(q(h) @ q(lo[n]["A"])) @ q(lo[n]["B"]))

    zxbcdt = lin(_rms(x, p["norm1"]), "in_proj")
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner: 2 * d_inner + 2 * N]
    dt = jax.nn.softplus(zxbcdt[..., 2 * d_inner + 2 * N:] + p["dt_bias"])  # (B,S,H)
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, i: i + S] * p["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :d_inner].reshape(Bsz, S, H, P)
    Bm, Cm = xbc[..., d_inner: d_inner + N], xbc[..., d_inner + N:]
    a = dt * -jnp.exp(p["A_log"])  # (B,S,H)
    cs = jnp.cumsum(jnp.moveaxis(a, -1, 1), axis=-1)  # (B,H,S)
    causal = jnp.tril(jnp.ones((S, S), bool))
    decay = jnp.exp(jnp.where(causal, cs[..., :, None] - cs[..., None, :], -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", q(Cm), q(Bm))
    mix = decay * cb[:, None]  # (B,H,t,s)
    y = jnp.einsum("bhts,bshp->bthp", q(mix), q(xs * dt[..., None]))
    y = y + xs * p["D_skip"][:, None]
    g = _rms(y.reshape(Bsz, S, d_inner) * jax.nn.silu(z), p["norm_scale"])
    return x + lin(g, "out_proj")


def loss(params, lora, batch, cfg: dict, q):
    """Masked mean token cross-entropy of one client's batch."""
    x = params["embed"][batch["tokens"]]

    @jax.checkpoint
    def body(x, pl):
        p, lo = pl
        return _layer(x, p, lo, cfg, q), None

    x, _ = jax.lax.scan(body, x, (params["layers"], lora))
    logits = q(_rms(x, params["final"])) @ q(params["embed"]).T
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    m = batch["mask"]
    return jnp.sum(nll * m) / jnp.sum(m)
