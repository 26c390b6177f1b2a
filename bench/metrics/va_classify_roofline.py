"""Share of its roofline the classify executable reaches: the least
time its traced calls could take (operations or bytes over the peak,
whichever is larger; `bench/configs/va_cnn.py` counts them) over its
device time in the trace."""

LAYER = "fleet classify"
UNIT = "%"
MOVES = "va_segments_per_s"
SOURCE = "device_trace"


def read(r):
    w, t = r.work.get("classify"), r.trace
    if not w or not t or not w["calls"]:
        return None
    dev = t["executables_s"].get(w["module"])
    if not dev:
        return None
    return 100.0 * w["least_s"] / dev
