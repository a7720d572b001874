"""The comparison that decides ``correct``.

The program's first rounds (the set-up's, driven through the window's own
call and feed) are followed by the family's float32 reference from the same
weights and inputs.  For every layer's A and B of every adapted projection
(a leaf), the adapters' change after the first round, and after the last
checked round n, is compared by its norm: |‖Δ_program‖ − ‖Δ_reference‖| over
the larger of ‖Δ_reference‖ of that leaf and of the median leaf.  Two
medians over the leaves are compared, each with its own limit:

* ``update1_gap`` / ``update<n>_gap``: over every leaf;
* ``client_update1_gap`` / ``client_update<n>_gap``: over the client's
  leaves alone (the layers below the cut), which the client backward
  through the smashed-activation gradient produces.  They are a small share
  of all leaves, so a fault on the client side alone cannot move the first
  median.

The worst leaf of each side, and ``loss_gap`` (the largest |program −
reference| round-start loss over the checked rounds, in nats), are printed
beside them; a cell's limits file says which numbers it compares, and why
the others are not.

Leaves whose first reference update is under a thousandth of the median
leaf's are left out (they move by rounding alone).
"""

from __future__ import annotations

import numpy as np

NEGLIGIBLE = 1e-3  # of the median leaf's first reference update


def leaf_norms(lora0, lora1) -> tuple:
    """(‖lora1 − lora0‖ of every layer's A and B of every projection, the
    layer of each)."""
    norms, layers = [], []
    for name in sorted(lora0):
        for ab in ("A", "B"):
            d = np.asarray(lora1[name][ab], np.float64) - np.asarray(lora0[name][ab], np.float64)
            norms.extend(np.sqrt(np.sum(d.reshape(d.shape[0], -1) ** 2, axis=1)))
            layers.extend(range(d.shape[0]))
    return np.asarray(norms), np.asarray(layers)


def leaf_gaps(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> np.ndarray:
    med = float(np.median(ref[keep]))
    return np.abs(prog - ref) / np.maximum(ref, med)


def readings(lora0, prog_losses, prog_snaps, ref_losses, ref_snaps, cut: int) -> tuple:
    """(numbers, printed-only readings): ``prog_snaps``/``ref_snaps`` are the
    adapters after the first and after the last checked round, in the
    reference's layout; layers below ``cut`` are the client's."""
    ref1, layer = leaf_norms(lora0, ref_snaps[0])
    keep = ref1 >= NEGLIGIBLE * np.median(ref1)
    sides = {"": keep, "client_": keep & (layer < cut)}
    dl = np.abs(np.asarray(prog_losses, np.float64) - np.asarray(ref_losses, np.float64))
    gaps, info = {"loss_gap": float(np.max(dl))}, {"step_loss_gaps": dl.tolist()}
    for key, p, r in (("update1", prog_snaps[0], ref_snaps[0]),
                      (f"update{len(ref_losses)}", prog_snaps[-1], ref_snaps[-1])):
        g = leaf_gaps(leaf_norms(lora0, p)[0], leaf_norms(lora0, r)[0], keep)
        for side, m in sides.items():
            gaps[f"{side}{key}_gap"] = float(np.median(g[m]))
        info[f"{key}_worst_leaf_gap"] = {"client": float(np.max(g[sides["client_"]])),
                                         "server": float(np.max(g[keep & (layer >= cut)]))}
    return gaps, info


def judge(gaps: dict, limits: dict) -> bool:
    """Every number the cell's limits name at or under its limit (NaN
    fails)."""
    return all(k in gaps and gaps[k] <= limits[k] for k in limits)
