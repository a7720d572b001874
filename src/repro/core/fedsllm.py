"""FedsLLM orchestration (paper Algorithms 1 + 2).

One *global round* (index n):
  1. broadcast global LoRA Δw = (Δw_c, Δw_s) to K clients,
  2. round-start gradients: g_k0 = ∇F_k(Δw) per client, ḡ = (1/K)Σ g_k0
     (the FEDL surrogate needs ∇F(Δw); this is the extra aggregation pass
     from ref. [11] that the paper's problem (4) inherits),
  3. local iterations i = 0..I_loc-1 on problem (4) by gradient descent
     (eq. 9):   h ← h − δ·∇G_k(h),
     ∇G_k(h) = ∇F_k(Δw+h) − ∇F_k(Δw) + ξ·∇F(Δw),
     where each ∇F_k evaluation is a *split* forward/backward (client fwd →
     smashed acts → server fwd/bwd → dA_k → client bwd),
  4. fed server + main server aggregate:  Δw ← Δw + (1/K)·Σ_k h_k
     (optionally masked for stragglers / dropped clients).

Clients are evaluated with ``jax.vmap`` over the stacked client axis, which
shards over the mesh ``data``(×``pod``) axes — client-parallelism *is* data
parallelism on the pod (DESIGN.md §3).

The number of local iterations follows Lemma 2 (v·log2(1/η)) and the number
of global rounds follows Lemma 1 (a/(1−η)); the simulated wall-clock cost of
each round comes from ``delay_model``/``resource_alloc``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FedsLLMConfig, ModelConfig
from repro.core import delay_model as dm
from repro.core import federated, lora as lora_lib, split
from repro.models import transformer as T


class FedsLLMState(NamedTuple):
    base: Any  # frozen w0
    lora_c: Any  # global client-side adapters Δw_c
    lora_s: Any  # global server-side adapters Δw_s
    round: jax.Array  # global iteration n


def init_state(cfg: ModelConfig, cut: int, key=None) -> tuple[FedsLLMState, Any]:
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    base, axes = T.init_params(cfg, key=k1)
    lora_full, lora_axes = lora_lib.init_lora(base, axes, cfg, key=k2)
    lc, ls = lora_lib.split_client_server(lora_full, cut, cfg)
    return FedsLLMState(base, lc, ls, jnp.zeros((), jnp.int32)), (axes, lora_axes)


def local_iteration_count(fcfg: FedsLLMConfig, eta: float) -> int:
    # Lemma 2 lives in delay_model.local_iters (the allocator prices the
    # same count); this is the ⌈·⌉-with-floor the training scan uses
    return max(1, int(math.ceil(dm.local_iters(fcfg, eta))))


def global_round_count(fcfg: FedsLLMConfig, eta: float) -> int:
    return max(1, int(math.ceil(dm.lemma_a(fcfg) / (1.0 - eta))))


def build_round_fn(cfg: ModelConfig, fcfg: FedsLLMConfig, cut: int, eta: float,
                   xi: Optional[float] = None, delta: Optional[float] = None,
                   remat: bool = False, dp_clip: float = 0.0,
                   dp_noise: float = 0.0, aggregator: Optional[Callable] = None,
                   compressor=None, dp_seed: int = 0,
                   two_tier: bool = False, local_algo=None) -> Callable:
    """Build the jittable global-round function (the `repro.api` engine).

    round_fn(state, batches, mask=None, key=None, weights=None, assign=None)
        -> (state', metrics)
    batches: pytree with leaves stacked (K, ...) — one micro-dataset/client.
    mask: (K,) survivors (straggler tolerance), or None.
    weights: (K,) aggregation weights, e.g. data sizes D_k (paper's weighted
    FedAvg); None = uniform.
    assign: (K, M) one-hot client→edge membership — only consumed when
    ``two_tier=True`` (the ``edge-agg`` topology): every aggregation becomes
    per-edge then cross-edge (``federated.hier_aggregate``).  Like ``mask``
    it is a value-only argument: per-round re-attachment keeps one jit trace.
    aggregator: callable (stacked, weights=None, mask=None) -> tree; default
    ``federated.fedavg``.  Applied to both the round-start gradient average ḡ
    and the uploaded update average (Algorithm 1's fed-server reduction).
    compressor: optional ``repro.api.compressors.Compressor`` applied to the
    smashed activations on the client→server uplink (straight-through).
    dp_clip/dp_noise: per-client L2 clip + Gaussian noise multiplier on the
    uploaded updates (DP-FedAvg; the paper's noise-layer counterpart at the
    fed-server uplink). 0 disables.
    dp_seed: base seed of the DP noise stream.  When the caller passes
    ``key=None`` the per-round key is ``fold_in(PRNGKey(dp_seed),
    state.round)`` — fresh noise every global round (a fixed fallback key
    would silently reuse the same noise each round), derived inside the
    trace so multi-round campaigns keep a single jit compilation.

    The returned round_fn also takes ``update_scale=None`` — an optional
    scalar server mixing rate on the aggregated update (Δw ← Δw + α·h̄),
    the FedAsync-style damping the asynchronous execution schedules drive
    with α = 1/(1+staleness)^β.  A weight-vector discount alone cannot
    express it: the weighted mean NORMALIZES, so with a single surviving
    arrival any per-client discount cancels.  Pass a jnp scalar (value-only
    — one jit trace per campaign); ``None`` keeps the exact legacy
    arithmetic (α = 1).

    local_algo: the client local-update rule (``repro.fl.local_algos``
    name or instance); None/"gd" keeps the paper's plain GD on problem (4)
    bit-identically.  For a *stateful* algorithm (``scaffold``) the round
    function gains two trailing value-only arguments and returns a triple:

        round_fn(state, batches, mask, key, weights, assign, update_scale,
                 algo_state, algo_ids) -> (state', metrics, algo_state')

    ``algo_state``: the full-population ``(K, …)``-stacked control variates
    (carried across rounds by the caller); ``algo_ids``: (C,) int array
    mapping the cohort rows of ``batches`` onto population rows of
    ``algo_state`` (None = first C users).  Both are value-only — cohort
    gather/scatter happens inside the trace, so one jit trace per η bucket
    still covers elastic cohorts.  Variates update from the *raw* local
    deviations, before any DP clip/noise (the server-side c̄ needs the
    client's true trajectory; DP applies to the uplink, not local state).
    """
    from repro.fl.local_algos import get_local_algo

    xi = fcfg.xi if xi is None else xi
    delta = fcfg.delta if delta is None else delta
    I_loc = local_iteration_count(fcfg, eta)
    aggregate = federated.fedavg if aggregator is None else aggregator
    algo = get_local_algo("gd" if local_algo is None else local_algo)

    def client_grads(base, lc, ls, batch):
        """(loss, (dlora_c, dlora_s), the held experts' loads or None)."""
        loss, dc, ds, info = split.split_value_and_grad(base, lc, ls, batch, cfg, cut,
                                                        remat=remat,
                                                        compressor=compressor)
        return loss, (dc, ds), info.get("moe_loads")

    def one_client_round(base, lc0, ls0, gk0, gbar, batch, ctrl=None,
                         ctrl_bar=None):
        """Local update on problem (4) for one client → (h_c, h_s, loss).

        The step rule is the selected local algorithm's: plain GD (eq. 9),
        FedProx's proximal pull, or SCAFFOLD's variate-corrected step
        (``ctrl``/``ctrl_bar`` carry this client's control variate and the
        population mean — None for stateless algorithms).
        """

        def grad_G(h):
            hc, hs = h
            lc = jax.tree.map(jnp.add, lc0, hc)
            ls = jax.tree.map(jnp.add, ls0, hs)
            loss, (dc, ds), _ = client_grads(base, lc, ls, batch)
            # ∇G = ∇F_k(Δw+h) − ∇F_k(Δw) + ξ∇F(Δw)
            gc = jax.tree.map(lambda a, b, c: a - b + xi * c, dc, gk0[0], gbar[0])
            gs = jax.tree.map(lambda a, b, c: a - b + xi * c, ds, gk0[1], gbar[1])
            return loss, (gc, gs)

        h0 = (jax.tree.map(jnp.zeros_like, lc0), jax.tree.map(jnp.zeros_like, ls0))

        def body(h, _):
            loss, g = grad_G(h)
            g = algo.correct(g, h, ctrl, ctrl_bar)
            h = jax.tree.map(lambda x, gx: x - delta * gx, h, g)
            return h, loss

        h, losses = jax.lax.scan(body, h0, None, length=I_loc)
        return h[0], h[1], losses[-1]

    def _round(state: FedsLLMState, batches, mask, key, weights, assign,
               update_scale, algo_state, algo_ids):
        K = jax.tree.leaves(batches)[0].shape[0]

        def agg(tree):
            with jax.named_scope("fedsllm.aggregate"):
                if two_tier and assign is not None:
                    # hierarchical fed-server role: per-edge then cross-edge
                    return federated.hier_aggregate(aggregate, tree, assign,
                                                    weights=weights, mask=mask)
                return aggregate(tree, weights=weights, mask=mask)
        # 2. round-start gradients per client (h=0)
        loss0, g0, routed = jax.vmap(lambda b: client_grads(state.base, state.lora_c,
                                                            state.lora_s, b))(batches)
        # ḡ = ∇F(Δw) — fed-server aggregation (paper: uplink s_c per client)
        gbar = (agg(g0[0]), agg(g0[1]))

        # 3. local iterations (vmapped over clients)
        new_algo_state = algo_state
        if algo.stateful:
            if algo_state is None:
                raise ValueError(
                    f"local algo {algo.name!r} is stateful: pass algo_state= "
                    f"(the (K, …)-stacked control variates)")
            # c̄ over the full stored population; cohort rows gathered by
            # algo_ids — value-only, so elastic cohorts keep one trace
            ctrl_bar = jax.tree.map(lambda x: jnp.mean(x, axis=0), algo_state)
            ids = (jnp.arange(K, dtype=jnp.int32) if algo_ids is None
                   else algo_ids)
            ctrl = jax.tree.map(lambda x: x[ids], algo_state)
            h_c, h_s, last_loss = jax.vmap(
                lambda gk_c, gk_s, b, ck: one_client_round(
                    state.base, state.lora_c, state.lora_s, (gk_c, gk_s),
                    gbar, b, ctrl=ck, ctrl_bar=ctrl_bar)
            )(g0[0], g0[1], batches, ctrl)
            # variates advance on the RAW deviations (pre-DP); stragglers
            # keep theirs (the algo masks), then scatter back to the
            # population rows
            upd = algo.update_variates(ctrl, ctrl_bar, (h_c, h_s), mask,
                                       I_loc, delta)
            new_algo_state = jax.tree.map(
                lambda full, u: full.at[ids].set(u.astype(full.dtype)),
                algo_state, upd)
        else:
            h_c, h_s, last_loss = jax.vmap(
                lambda gk_c, gk_s, b: one_client_round(state.base, state.lora_c,
                                                       state.lora_s, (gk_c, gk_s), gbar, b)
            )(g0[0], g0[1], batches)

        # 3b. optional DP on the uploaded client updates
        if dp_clip > 0.0:
            from repro.core import privacy

            if key is None:
                key = jax.random.fold_in(jax.random.PRNGKey(dp_seed),
                                         state.round)
            h_c = privacy.clip_and_noise_updates(h_c, key, clip_norm=dp_clip,
                                                 noise_multiplier=dp_noise)

        # 4. aggregate + update (fed server for Δw_c, main server for Δw_s);
        # α = 1 (the paper's rule) unless an async schedule passes its
        # staleness mixing rate
        alpha = 1.0 if update_scale is None else update_scale
        with jax.named_scope("fedsllm.aggregate"):
            new_lc = federated.apply_update(state.lora_c, agg(h_c), alpha)
            new_ls = federated.apply_update(state.lora_s, agg(h_s), alpha)
        metrics = {
            "loss_round_start": jnp.mean(loss0),
            "loss_local_final": jnp.mean(last_loss),
            # vmapped LoRA pytrees keep their dict structure, so delta_norm
            # applies directly to the stacked (K, ...) updates
            "h_c_norm": lora_lib.delta_norm(h_c),
        }
        if routed is not None:
            metrics.update(routing_counters(routed, batches, cfg))
        new_state = FedsLLMState(state.base, new_lc, new_ls, state.round + 1)
        return new_state, metrics, new_algo_state

    # stateless algorithms keep the legacy signature and 2-tuple return
    # (the Python-level branch leaves the traced computation — and for
    # ``gd`` the jaxpr itself — bit-identical to the pre-registry engine);
    # stateful ones thread the variates through two extra value-only args
    if algo.stateful:
        def round_fn(state: FedsLLMState, batches, mask=None, key=None,
                     weights=None, assign=None, update_scale=None,
                     algo_state=None, algo_ids=None):
            return _round(state, batches, mask, key, weights, assign,
                          update_scale, algo_state, algo_ids)
    else:
        def round_fn(state: FedsLLMState, batches, mask=None, key=None,
                     weights=None, assign=None, update_scale=None):
            new_state, metrics, _ = _round(state, batches, mask, key, weights,
                                           assign, update_scale, None, None)
            return new_state, metrics

    round_fn.local_algo = algo
    return round_fn


def routing_counters(loads, batches, cfg: ModelConfig) -> dict:
    """The round-start pass's routing over the cohort, from the held
    experts' loads (K, layers, E_held): ``moe_held_share``, the share of the
    routed (token, expert) pairs that landed on a held expert (E_held /
    router experts under even routing), and ``moe_max_load``, the largest
    held expert's load over the mean held load, in the layer where that is
    largest (1 = even)."""
    K, n_layers, _ = loads.shape
    tokens = math.prod(jax.tree.leaves(batches)[0].shape[1:])
    per_layer = jnp.sum(loads, axis=0)  # (layers, E_held)
    mean = jnp.maximum(jnp.mean(per_layer, axis=-1), 1e-9)
    return {"moe_held_share": jnp.sum(loads) / (K * n_layers * tokens * cfg.num_experts_per_tok),
            "moe_max_load": jnp.max(jnp.max(per_layer, axis=-1) / mean)}


# ---------------------------------------------------------------------------
# Simulated wall-clock integration (delay model + allocator)
# ---------------------------------------------------------------------------


@dataclass
class RoundTiming:
    """Per-global-round simulated wireless wall-clock (seconds)."""

    compute: np.ndarray  # (K,) eq. (10)
    uplink_fed: np.ndarray  # (K,) t_c
    uplink_main: np.ndarray  # (K,) V·t_s
    total: np.ndarray  # (K,)


def simulate_round_time(fcfg: FedsLLMConfig, net, alloc, eta: float,
                        model_params: Optional[int] = None) -> RoundTiming:
    V = dm.local_iters(fcfg, eta)
    tau = dm.compute_time(fcfg, net, eta, alloc.A, model_params)
    up_f = alloc.t_c
    up_m = V * alloc.t_s
    return RoundTiming(tau, up_f, up_m, tau + up_f + up_m)
