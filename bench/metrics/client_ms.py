"""Device time per round of the ops under the scope ``fedsllm.client``
(the client forward and, as ``transpose(jvp(...))``, its backward) (ms)."""

from harness import layers


def read(m: dict):
    return layers.per_round_ms(m, "scope_s", "fedsllm.client")
