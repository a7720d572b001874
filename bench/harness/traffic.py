"""The benchmark's input generator and the work a round carries.

Each cohort client reads its own rows for the round: a bigram chain over the
vocabulary (the structure of ``repro.data.tokens``: with probability
``structure`` the next token is ``perm[token]``, else uniform), keyed by
(seed, round, client) so every row of every round differs and the same seed
gives the same inputs.  One jitted call makes a whole cohort's batches on the
device, so generating inputs inside the timed window costs milliseconds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int, n: int) -> np.ndarray:
    """``n`` uint32 words from any whole-number seed (more than 32 bits
    welcome), the root of every stream a run draws."""
    return np.random.SeedSequence(int(seed) % 2**128).generate_state(n, np.uint32)


def key_from(words) -> jax.Array:
    return jax.random.wrap_key_data(jnp.asarray(np.asarray(words, np.uint32)))


def local_steps(traffic: dict, eta: float) -> int:
    """I_loc of Lemma 2 at accuracy ``eta``: ⌈v·log2(1/η)⌉, at least 1, with
    v = 2/((2 − Lδ)·δ·γ)."""
    L, gamma, delta = traffic["L_smooth"], traffic["gamma_strong"], traffic["delta"]
    v = 2.0 / ((2.0 - L * delta) * delta * gamma)
    return max(1, int(math.ceil(v * math.log2(1.0 / eta))))


def round_tokens(traffic: dict, eta: float) -> int:
    """Training tokens of one round: cohort × rows × seq × I_loc (the
    round-start pass at h = 0 is not counted)."""
    return (traffic["cohort"] * traffic["batch"] * traffic["seq"]
            * local_steps(traffic, eta))


def make_batches_fn(traffic: dict, vocab: int, key: jax.Array):
    """``batches_fn(round, client_ids) -> {"tokens", "labels", "mask"}``,
    leaves stacked (cohort, batch, seq), made on the device."""
    B, S, p = traffic["batch"], traffic["seq"], float(traffic["structure"])
    k_perm, k_rows = jax.random.split(key)
    perm = jax.random.permutation(k_perm, vocab).astype(jnp.int32)

    def rows(k):
        k0, k1, k2 = jax.random.split(k, 3)
        first = jax.random.randint(k0, (B,), 0, vocab, jnp.int32)
        rnd = jax.random.randint(k1, (S, B), 0, vocab, jnp.int32)
        det = jax.random.bernoulli(k2, p, (S, B))

        def step(tok, xs):
            r, d = xs
            nxt = jnp.where(d, perm[tok], r)
            return nxt, nxt

        _, rest = jax.lax.scan(step, first, (rnd, det))
        seq = jnp.concatenate([first[None], rest], axis=0).T  # (B, S + 1)
        return seq[:, :S], seq[:, 1:]

    @jax.jit
    def gen(r, ids):
        kr = jax.random.fold_in(k_rows, r)
        toks, labels = jax.vmap(lambda i: rows(jax.random.fold_in(kr, i)))(ids)
        return {"tokens": toks, "labels": labels,
                "mask": jnp.ones(toks.shape, jnp.float32)}

    def batches_fn(round_idx, client_ids):
        with jax.profiler.TraceAnnotation("bench.batches"):
            return gen(jnp.int32(round_idx), jnp.asarray(client_ids, jnp.int32))

    return batches_fn
