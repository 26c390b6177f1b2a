"""Share of its roofline the decode step (one token for every slot that holds a request) reaches: the least time of its
traced calls (operations or bytes over the peak, whichever is larger;
weights at the compute dtype, the cache positions actually held, as
`bench/configs/qwen3_8b.py` counts them) over its device time."""

LAYER = "model step"
UNIT = "%"
MOVES = "lm_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    w, t = r.work.get("decode"), r.trace
    if not w or not t or not w["calls"]:
        return None
    dev = t["executables_s"].get(w["module"])
    if not dev:
        return None
    return 100.0 * w["least_s"] / dev
