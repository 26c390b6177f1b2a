"""The model's operations in the traced slice's decode steps over the
slice's length times the chip's bf16 peak: the whole decode work's share
of the peak, host gaps included."""

LAYER = "serving loop"
UNIT = "%"
MOVES = "lm_tokens_per_s"
SOURCE = "device_trace"


def read(r):
    w, t = r.work.get("decode"), r.trace
    if not w or not t or not w["calls"] or not t["window_s"]:
        return None
    return 100.0 * w["flops"] / (t["window_s"] * r.peak["bf16_flops"])
