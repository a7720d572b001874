"""Device time per round of the ops under the scope ``model.attention``
(the attention core, forward and backward, without the projections) (ms)."""

from harness import layers


def read(m: dict):
    return layers.per_round_ms(m, "scope_s", "model.attention")
