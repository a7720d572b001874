"""Plain float32 reference of the Granite-4.0-H stack (``ssm-moe``), and the
weights the benchmark makes for it.

Per layer (granitemoehybrid): a mixer, Mamba-2 or grouped-query attention,
then the MoE, each on its own residual branch scaled by m =
``residual_multiplier``:

    x += m·mixer(rms(x));   h = rms(x);   x += m·(moe(h) + shared(h))

The Mamba-2 mixer is ``ssm.py``'s (one B/C group, the selective scan in its
quadratic dual over the whole sequence), its gated norm after the gate.
Attention is causal GQA with no position embedding, scores times
``attention_multiplier``.  The router scores all ``router_experts`` in
float32; each token keeps its top k, with gates the softmax of the chosen
logits.  This layer holds experts [expert_offset, expert_offset +
num_experts): each held expert, a SwiGLU, runs on every token, weighted by
its gate, which is 0 where the token did not choose it; pairs routed to
experts held elsewhere add nothing.  The shared SwiGLU expert is added once.
The loss is the masked mean token cross-entropy plus 0.01 times the layers'
Switch load-balancing term over the whole router, E·Σ_e mean_t p_e ·
(share of the pairs routed to e).  Embeddings × ``embedding_multiplier``;
the head is tied, logits × ``logit_scale``.  RMSNorm eps ``norm_eps``.
Adapted projections compute x·W + (α/r)·(x·A)·B.  float32 throughout: the
weights are read as stored and widened inside each layer; ``q`` wraps
every operand of a matrix product.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MIXER = {"M": ("mamba", ("in_proj", "out_proj")), "G": ("attn", ("wq", "wk", "wv", "wo"))}
FFN = ("w_gate", "w_up", "w_down")
LORA_B_STD = 0.01  # adapters mid-way through a fine-tune, not at B = 0
AUX_WEIGHT = 0.01


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
    return n / math.sqrt(max(shape[-2], 1))  # projections, experts, router, conv_w: fan-in


def _names(cfg: dict) -> dict:
    """Reference adapter name -> (block path under a sublayer, leaf)."""
    out = {n: (blk, n) for blk, ns in MIXER.values() for n in ns}
    out.update({f"moe.{n}": ("moe", n) for n in FFN})
    out.update({f"shared.{n}": ("moe.shared", n) for n in FFN})
    return out


def _layers(cfg: dict):
    """(layer, its pattern char, its group, its sublayer index)."""
    p = cfg["layer_pattern"]
    return [(l, p[l % len(p)], l // len(p), l % len(p)) for l in range(cfg["num_layers"])]


def unpack(base, lora_c, lora_s, cfg: dict):
    """(params, lora): params the served weights as stored (no copy); lora
    {name: {"A": (L, ..., d_in, r), "B": (L, ..., r, d_out)}} in float32,
    stacked over every layer, zero where a layer has no such projection."""
    params = {"embed": base["embed"]["tokens"], "final": base["final_norm"]["scale"],
              "groups": base["groups"]}
    lora = {}
    for name, (blk, leaf) in _names(cfg).items():
        per = {"A": [], "B": []}
        for _, _, g, i in _layers(cfg):
            k = f"['groups']['sub_{i}']" + "".join(f"['{b}']" for b in blk.split(".")) + f"['{leaf}']"
            stack = [side[k] for side in (lora_c, lora_s) if k in side]
            like = _like(blk, leaf, cfg)
            for ab in ("A", "B"):
                if stack:
                    full = jnp.concatenate([s[ab] for s in stack], axis=0)
                    per[ab].append(jnp.asarray(full[g], jnp.float32))
                else:
                    per[ab].append(jnp.zeros(like[ab], jnp.float32))
        lora[name] = {ab: jnp.stack(v) for ab, v in per.items()}
    return params, lora


def _like(blk, leaf, cfg) -> dict:
    """Shapes of the adapter pair a projection named (blk, leaf) would have."""
    D, r = cfg["d_model"], cfg["lora_rank"]
    H, Kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    d_inner = cfg["ssm_expand"] * D
    n_heads = d_inner // cfg["ssm_head_dim"]
    E, F, Fs = cfg["num_experts"], cfg["d_ff"], cfg["shared_d_ff"]
    io = {"in_proj": (D, 2 * d_inner + 2 * cfg["ssm_state"] + n_heads),
          "out_proj": (d_inner, D), "wq": (D, H * hd), "wk": (D, Kv * hd),
          "wv": (D, Kv * hd), "wo": (H * hd, D)}
    if blk == "moe":
        lead, (i, o) = (E,), ((F, D) if leaf == "w_down" else (D, F))
    elif blk == "moe.shared":
        lead, (i, o) = (), ((Fs, D) if leaf == "w_down" else (D, Fs))
    else:
        lead, (i, o) = (), io[leaf]
    return {"A": lead + (i, r), "B": lead + (r, o)}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _mamba(h, p, lin, cfg):
    Bsz, S, D = h.shape
    N, P, W = cfg["ssm_state"], cfg["ssm_head_dim"], cfg["ssm_conv_width"]
    d_inner = cfg["ssm_expand"] * D
    H = d_inner // P
    zxbcdt = lin(h, "in_proj")
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
    q = lin.q
    cb = jnp.einsum("btn,bsn->bts", q(Cm), q(Bm))
    y = jnp.einsum("bhts,bshp->bthp", q(decay * cb[:, None]), q(xs * dt[..., None]))
    y = y + xs * p["D_skip"][:, None]
    g = _rms(y.reshape(Bsz, S, d_inner) * jax.nn.silu(z), p["norm_scale"], cfg["norm_eps"])
    return lin(g, "out_proj")


def _attention(h, lin, cfg):
    B, S, _ = h.shape
    H, Kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = lin.q
    qh = lin(h, "wq").reshape(B, S, H, hd)
    k = jnp.repeat(lin(h, "wk").reshape(B, S, Kv, hd), H // Kv, axis=2)
    v = jnp.repeat(lin(h, "wv").reshape(B, S, Kv, hd), H // Kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(k)) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", q(probs), q(v)).reshape(B, S, H * hd)
    return lin(o, "wo")


def _moe(h, p, lo, cfg, q):
    """(held experts' part + shared expert, load-balancing term)."""
    s = cfg["lora_alpha"] / cfg["lora_rank"]
    E_all, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    e0, E = cfg["expert_offset"], cfg["num_experts"]
    logits = q(h) @ q(p["router"])  # float32 router over every expert
    top_l, top_e = jax.lax.top_k(logits, k)
    chosen = jax.nn.one_hot(top_e, E_all) * jax.nn.softmax(top_l, axis=-1)[..., None]
    gates = jnp.sum(chosen, axis=-2)[..., e0: e0 + E]  # (B, S, E): 0 where not chosen
    probs = jax.nn.softmax(logits, axis=-1)
    share = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, E_all), axis=-2), axis=(0, 1)) / k
    balance = E_all * jnp.sum(jnp.mean(probs, axis=(0, 1)) * share)

    def elin(x, n):  # every held expert at once: x (B,S,d) or (E,B,S,d)
        xs = "ebsd" if x.ndim == 4 else "bsd"
        a, b = lo[f"moe.{n}"]["A"], lo[f"moe.{n}"]["B"]
        xa = jnp.einsum(f"{xs},edr->ebsr", q(x), q(a))
        return (jnp.einsum(f"{xs},edf->ebsf", q(x), q(p[n]))
                + s * jnp.einsum("ebsr,erf->ebsf", q(xa), q(b)))

    ye = elin(jax.nn.silu(elin(h, "w_gate")) * elin(h, "w_up"), "w_down")  # (E,B,S,D)
    routed = jnp.einsum("ebsd,bse->bsd", ye, gates)

    def slin(x, n):
        return (q(x) @ q(p["shared"][n])
                + s * (q(q(x) @ q(lo[f"shared.{n}"]["A"])) @ q(lo[f"shared.{n}"]["B"])))

    shared = slin(jax.nn.silu(slin(h, "w_gate")) * slin(h, "w_up"), "w_down")
    return routed + shared, balance


def _layer(x, p, lo, ch, cfg, q):
    """One layer: (x', its load-balancing term)."""
    s = cfg["lora_alpha"] / cfg["lora_rank"]
    m, eps = cfg["residual_multiplier"], cfg["norm_eps"]
    blk = MIXER[ch][0]

    def lin(h, n):
        return q(h) @ q(p[blk][n]) + s * (q(q(h) @ q(lo[n]["A"])) @ q(lo[n]["B"]))

    lin.q = q
    h = _rms(x, p["norm1"]["scale"], eps)
    x = x + m * (_mamba(h, p["mamba"], lin, cfg) if ch == "M" else _attention(h, lin, cfg))
    ffn, balance = _moe(_rms(x, p["norm2"]["scale"], eps), p["moe"], lo, cfg, q)
    return x + m * ffn, balance


def loss(params, lora, batch, cfg: dict, q):
    """Masked mean token cross-entropy of one client's batch, plus the
    layers' load-balancing terms."""
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)  # noqa: E731
    x = f32(params["embed"])[batch["tokens"]] * cfg["embedding_multiplier"]
    balance = 0.0
    for l, ch, g, i in _layers(cfg):
        sub = jax.tree.map(lambda a: a[g], params["groups"][f"sub_{i}"])
        lo = jax.tree.map(lambda a: a[l], lora)
        x, b = jax.checkpoint(lambda x, sub, lo, ch=ch: _layer(x, f32(sub), lo, ch, cfg, q))(
            x, sub, lo)
        balance = balance + b
    emb = f32(params["embed"])
    logits = (q(_rms(x, f32(params["final"]), cfg["norm_eps"])) @ q(emb).T) * cfg["logit_scale"]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    m = batch["mask"]
    return jnp.sum(nll * m) / jnp.sum(m) + AUX_WEIGHT * balance
