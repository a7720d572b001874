"""Host time per round in the span ``fedsllm.campaign`` less the time in
``fedsllm.read``, where the host waits for the device (ms)."""


def read(m: dict):
    spans = m.get("span_s", {})
    if "fedsllm.campaign" not in spans or not m.get("rounds"):
        return None
    return 1e3 * (spans["fedsllm.campaign"] - spans.get("fedsllm.read", 0.0)) / m["rounds"]
