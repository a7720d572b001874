"""One run of one cell: set-up, the timed window, the output check.

Set-up builds the program's ``Experiment`` from the cell's files, gives it
the benchmark's weights, and drives it through its first rounds with the
window's own call (``Experiment.run`` with the benchmark's ``batches_fn``),
which compiles every program the window uses and leaves the rounds the
reference follows.  The window then runs whole rounds, one ``run()`` call
each, from a round boundary to the first boundary after ``seconds``; each
round ends on the device, since the campaign reads its metrics with
``float()``.  Once the window is closed and the peak memory read, the
program is dropped and the reference follows the checked rounds.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import check, spec, traffic, weights


@dataclass
class Checked:
    round: int
    client_ids: np.ndarray
    weights: np.ndarray  # the cohort's D_k, as the round aggregated them
    eta: float
    loss: float  # loss_round_start
    lora: tuple  # (lora_c, lora_s) after the round


@dataclass
class Session:
    cell: spec.Cell
    exp: object
    family: object
    base: object
    lora0: tuple
    batches_fn: object
    run_kw: dict
    checked: list = field(default_factory=list)
    compiles: list = field(default_factory=list)


def model_config(config: dict):
    import dataclasses

    from repro.config import LoRAConfig, ModelConfig

    names = {f.name for f in dataclasses.fields(ModelConfig)} - {"lora"}
    return ModelConfig(**{k: v for k, v in config.items() if k in names},
                       lora=LoRAConfig(rank=config["lora_rank"], alpha=config["lora_alpha"]))


def build(cell: spec.Cell, seed: int) -> Session:
    """The Experiment with the benchmark's weights; nothing has run yet."""
    from repro.api import Experiment
    from repro.config import FedsLLMConfig
    from repro.core.fedsllm import FedsLLMState
    from repro.net.topology import topologies

    tr = cell.traffic
    words = traffic.seed_words(seed, 6)
    fcfg = FedsLLMConfig(num_clients=tr["population"], xi=tr["xi"], delta=tr["delta"],
                         L_smooth=tr["L_smooth"], gamma_strong=tr["gamma_strong"])
    topo = topologies.get(tr["topology"])(**tr["topology_kw"])
    exp = Experiment(model_config(cell.config), fcfg, cut=tr["cut"], eta=tr["eta"],
                     aggregator=tr["aggregator"], allocator=tr["allocator"],
                     scenario=tr["scenario"], topology=topo, schedule=tr["schedule"],
                     seed=int(words[0] >> 1))
    family = spec.module("reference", cell.family)
    st = exp.state
    base, lc, ls = weights.make((st.base, st.lora_c, st.lora_s), family.init_leaf,
                                traffic.key_from(words[1:3]))
    exp.state = FedsLLMState(base, lc, ls, jnp.zeros((), jnp.int32))
    batches_fn = traffic.make_batches_fn(tr, cell.config["vocab_size"],
                                         traffic.key_from(words[3:5]))
    run_kw = dict(batches_fn=batches_fn, cohort=tr["cohort"],
                  resample_channel=tr["resample_channel"], reallocate=tr["reallocate"])
    return Session(cell, exp, family, base, (lc, ls), batches_fn, run_kw)


def listen_compiles(sess: Session) -> None:
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: sess.compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)


def warm(sess: Session) -> None:
    """The checked rounds, through the window's own call and feed."""
    exp = sess.exp

    def keep(rec):
        sess.checked.append(Checked(
            rec.round, np.asarray(rec.client_ids),
            np.asarray(exp.net.D_k, np.float64)[np.asarray(rec.client_ids)],
            float(rec.eta), float(rec.metrics["loss_round_start"]),
            (exp.state.lora_c, exp.state.lora_s)))

    exp.run(num_rounds=sess.cell.traffic["checked_rounds"], on_round=keep, **sess.run_kw)


@dataclass
class Window:
    seconds: float
    etas: list
    losses: list
    traces: int  # round-function traces inside the window
    compiles: int  # backend compiles inside the window


def timed(sess: Session, seconds: float) -> Window:
    exp = sess.exp
    etas, losses = [], []

    def note(rec):
        etas.append(float(rec.eta))
        losses.append(float(rec.metrics["loss_round_start"]))

    traces0, compiles0 = exp.trace_count, len(sess.compiles)
    r = int(np.asarray(exp.state.round))
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            r += 1
            with jax.profiler.TraceAnnotation("bench.run"):
                exp.run(num_rounds=r, on_round=note, **sess.run_kw)
            t = time.perf_counter()
            if t - t0 >= seconds:
                break
    return Window(t - t0, etas, losses, exp.trace_count - traces0,
                  len(sess.compiles) - compiles0)


def peak_bytes(n: int) -> Optional[int]:
    """Peak device memory of the fullest chip: the peak of the buffers in
    use plus the peak of what the runtime reserves for the programs'
    temporaries, which the first leaves out (on a TPU v5e the round's
    reservation is most of its memory)."""
    stats = [d.memory_stats() or {} for d in jax.devices()[:n]]
    peaks = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
             for s in stats if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def follow(sess: Session, quantizer: str = "float32",
           clients: Optional[list] = None) -> tuple:
    """The reference (or the control) over the checked rounds, from the same
    weights and inputs: (round-start losses, adapters after each round)."""
    from harness.fedround import RoundReference

    cfg, tr, fam = sess.cell.config, sess.cell.traffic, sess.family
    params, lora = fam.unpack(sess.base, *sess.lora0, cfg)
    ref = RoundReference(fam.loss, cfg, tr, quantizer)
    losses, snaps = [], []
    for c in sess.checked:
        batches = sess.batches_fn(c.round, c.client_ids)
        lora, loss0 = ref.round(params, lora, batches, c.weights,
                                traffic.local_steps(tr, c.eta), clients=clients)
        losses.append(loss0)
        snaps.append(lora)
    return losses, snaps


def program_side(sess: Session) -> tuple:
    fam, cfg = sess.family, sess.cell.config
    lora0 = fam.unpack(sess.base, *sess.lora0, cfg)[1]
    snaps = [fam.unpack(sess.base, *c.lora, cfg)[1] for c in sess.checked]
    return lora0, [c.loss for c in sess.checked], snaps


def compare(sess: Session, lora0, prog_losses, prog_snaps, ref) -> tuple:
    """(numbers, printed-only readings); see ``harness.check``."""
    ref_losses, ref_snaps = ref
    return check.readings(lora0, prog_losses, [prog_snaps[0], prog_snaps[-1]],
                          ref_losses, [ref_snaps[0], ref_snaps[-1]],
                          sess.cell.traffic["cut"])


def drop_program(sess: Session) -> None:
    sess.exp = None
    gc.collect()

