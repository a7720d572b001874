"""Device time per round of the ops under the scope ``lora.merge`` (W +
s·A·B and its gradient), inside ``client_ms`` and ``server_ms`` (ms).  A
merge fused into a consumer outside the scope counts there."""

from harness import layers


def read(m: dict):
    return layers.per_round_ms(m, "scope_s", "lora.merge")
