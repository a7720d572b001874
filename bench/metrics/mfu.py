"""Model FLOPs of the window's rounds over the window times the chips' bf16
peak (%): the whole round's share of the peak.  The FLOPs are the family's
count (``bench/flops``) of the training tokens' passes."""


def read(m: dict):
    if not m.get("busy_s") or not m.get("rounds"):
        return None
    return 100.0 * m["model_flops"] / (m["window_s"] * m["peak_flops"])
