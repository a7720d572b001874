"""Device idle time per round whose midpoint lies in a ``fedsllm.*`` span
other than ``fedsllm.read`` (by the innermost span there) (ms): the idle
time the campaign's host steps leave."""


def read(m: dict):
    if "idle_span_s" not in m or not m.get("rounds"):
        return None
    return 1e3 * sum(s for span, s in m["idle_span_s"].items()
                     if span.startswith("fedsllm.") and span != "fedsllm.read") / m["rounds"]
