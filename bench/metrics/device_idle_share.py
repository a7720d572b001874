"""Share of the traced window in which no operation ran on the device (%)."""


def read(m: dict):
    if not m.get("busy_s"):
        return None
    return 100.0 * (1.0 - m["busy_s"] / m["window_s"])
