"""Mean share of the engine's slots that hold a request after a tick
(`Engine.tick()`'s return over the slot count)."""

LAYER = "engine slots and admission"
UNIT = "%"
MOVES = "lm_tokens_per_s"
SOURCE = "program_counter"


def read(r):
    c = r.counters
    if not c.get("ticks"):
        return None
    return 100.0 * c["active_sum"] / (c["ticks"] * c["slots"])
