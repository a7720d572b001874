"""Device time per round of the ops under the scope ``fedsllm.aggregate``
(the weighted averages and the global update) (ms)."""

from harness import layers


def read(m: dict):
    return layers.per_round_ms(m, "scope_s", "fedsllm.aggregate")
