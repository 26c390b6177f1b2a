"""Share of classified rows that were padding (`padded_total` over
`segments_total + padded_total` of the fleet metrics)."""

LAYER = "fleet scheduler and vote"
UNIT = "%"
MOVES = "va_segments_per_s"
SOURCE = "program_counter"


def read(r):
    c = r.counters
    rows = c.get("segments", 0) + c.get("padded", 0)
    if not rows:
        return None
    return 100.0 * c["padded"] / rows
