"""Device time per round of the ops under the scope ``model.ssd`` (the
chunked SSD scan, forward and backward) (ms)."""

from harness import layers


def read(m: dict):
    return layers.per_round_ms(m, "scope_s", "model.ssd")
