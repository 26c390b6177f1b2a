"""Fleet loop seconds per packed batch (scheduler, gather, classify and
vote together), from `simulate`'s own clock and batch count."""

LAYER = "fleet scheduler and vote"
UNIT = "ms"
MOVES = "va_segments_per_s"
SOURCE = "program_counter"


def read(r):
    c = r.counters
    if not c.get("batches"):
        return None
    return 1e3 * c["loop_s"] / c["batches"]
