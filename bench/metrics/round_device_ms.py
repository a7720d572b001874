"""Device-busy time per round in the traced window (ms)."""


def read(m: dict):
    if not m.get("busy_s") or not m.get("rounds"):
        return None
    return 1e3 * m["busy_s"] / m["rounds"]
