"""Device time per round of the ops under the scope ``fedsllm.server``
(the server forward, loss and backward) (ms)."""

from harness import layers


def read(m: dict):
    return layers.per_round_ms(m, "scope_s", "fedsllm.server")
