"""The whole fleet loop's share of the chip's bf16 peak: the VA model's
operations per segment (2 x nonzero MACs of the 16:8 model) times the
segments per second of the loop, over the peak."""

LAYER = "serving loop"
UNIT = "%"
MOVES = "va_segments_per_s"
SOURCE = "host_clock"


def read(r):
    w = r.work.get("classify")
    rate = r.end_to_end.get("va_segments_per_s")
    if not w or not rate:
        return None
    return 100.0 * w["flops_per_segment"] * rate / r.peak["bf16_flops"]
