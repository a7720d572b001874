"""Time per round in the traced window during which no operation ran on the
device (ms): the host's share of a round, campaign and allocator included."""


def read(m: dict):
    if not m.get("busy_s") or not m.get("rounds"):
        return None
    return 1e3 * (m["window_s"] - m["busy_s"]) / m["rounds"]
