"""Plain reference of one FedsLLM global round, in float32.

Written from the paper's Algorithms 1 and 2, independent of ``src/``: for
each cohort client k the round-start gradient g_k = ∇F_k(Δw), the weighted
mean ḡ = Σ D_k g_k / Σ D_k, then I_loc steps of gradient descent on problem
(4), h ← h − δ·(∇F_k(Δw + h) − g_k + ξ·ḡ), from h = 0, and last the update
Δw ← Δw + Σ D_k h_k / Σ D_k.  Split learning does not change this
arithmetic (client forward, server forward and backward, client backward are
one chain rule), so the reference differentiates the whole model at once.

Clients run one after the other, so the reference needs one client's
activations at a time.  Every matrix product runs at ``highest`` precision.

The control is the same reference computed in float8 e4m3 where the
program computes in bfloat16: every operand of a matrix product (``q``,
gradients passed straight through), and every stored tensor (frozen
weights, adapters, gradients, the update h, the averages; ``store``), each
rounded with a per-tensor scale.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp


def exact(x):
    return x


@jax.custom_vjp
def fp8(x):
    # reduce_precision rounds where a convert pair could be elided by XLA
    # (excess precision): 4 exponent and 3 mantissa bits, e4m3's grid; its
    # IEEE layout keeps the top exponent for inf, so the largest is 240
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0)
    return jax.lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3) * s


fp8.defvjp(lambda x: (fp8(x), None), lambda _, g: (g,))

# name -> (operand quantizer, storage rounding)
PRECISIONS = {"float32": (exact, exact), "fp8": (fp8, fp8)}


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


class RoundReference:
    """The reference round for one family's loss, at one quantizer."""

    def __init__(self, loss: Callable, cfg: dict, traffic: dict,
                 quantizer: str = "float32"):
        q, store = PRECISIONS[quantizer]
        delta, xi = float(traffic["delta"]), float(traffic["xi"])
        self._store = jax.jit(lambda t: jax.tree.map(store, t))

        def vg(params, lora, batch):
            l, g = jax.value_and_grad(lambda lo: loss(params, lo, batch, cfg, q))(lora)
            return l, jax.tree.map(store, g)

        def local(params, lora, h, gk, gbar, batch):
            l, g = vg(params, jax.tree.map(lambda a, b: store(a + b), lora, h), batch)
            h = jax.tree.map(lambda h_, g_, gk_, gb: store(h_ - delta * (g_ - gk_ + xi * gb)),
                             h, g, gk, gbar)
            return l, h

        self._start = jax.jit(vg)
        self._local = jax.jit(local)

    def round(self, params, lora, batches, weights, steps: int,
              clients: Optional[list] = None):
        """One global round from ``lora``: returns (Δw', mean round-start
        loss over the clients).  ``clients`` limits the cohort (a fault)."""
        C = jax.tree.leaves(batches)[0].shape[0]
        clients = list(range(C)) if clients is None else list(clients)
        w = jnp.asarray(weights, jnp.float32)[jnp.asarray(clients)]
        w = w / jnp.sum(w)
        params, lora = self._store(params), self._store(lora)
        with jax.default_matmul_precision("highest"):
            one = [jax.tree.map(lambda x: x[k], batches) for k in clients]
            starts = [self._start(params, lora, b) for b in one]
            gbar = self._store(jax.tree.map(
                lambda *gs: sum(wk * g for wk, g in zip(w, gs)), *[g for _, g in starts]))
            hbar = jax.tree.map(jnp.zeros_like, lora)
            for wk, b, (_, gk) in zip(w, one, starts):
                h = jax.tree.map(jnp.zeros_like, lora)
                for _ in range(steps):
                    _, h = self._local(params, lora, h, gk, gbar, b)
                hbar = jax.tree.map(lambda a, x: a + wk * x, hbar, h)
        loss0 = float(jnp.mean(jnp.stack([l for l, _ in starts])))
        return self._store(tree_add(lora, self._store(hbar))), loss0
