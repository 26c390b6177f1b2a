"""Pinned slot layouts for the engine's token-stream equivalence tests.

A tick's tokens reach the host as one array indexed by slot, so the
layouts that could break it are a slot finishing between two that keep
decoding, and ticks whose occupied slots are not contiguous. Random
interleavings seldom hit them; these cases fix them.
"""

import jax
from hypothesis import assume

from repro.serve import engine as E

# case -> (`max_new` of the co-admitted requests, then of the late
# arrival or None; occupied slot sets a later tick must decode).
# "eos_between": the middle request is cut by EOS on a later token
# while both neighbours keep decoding. "holes": staggered `max_new`
# leave slots {1, 3}; a late arrival then reseats slot 0.
PINNED = {
    "eos_between": ([8, 6, 8, None], [(0, 2)]),
    "holes": ([2, 6, 3, 6, 3], [(1, 3), (0, 1, 3)]),
}
LATE_AFTER_TICK = 3  # "holes": the late arrival comes after tick 3
PROMPT_LENS = (2, 3)  # short, so the paged pool seats all at once


def pinned_requests(cfg, rng, case, stream):
    """The first requests of a pinned case and its late arrival (or
    None); prompts come from `rng`, and `stream(req)` is a request's
    solo reference stream, from which the EOS cut is chosen."""
    reqs = []
    for i, m in enumerate(PINNED[case][0]):
        s_len = int(rng.choice(PROMPT_LENS))
        reqs.append(E.Request(uid=i, prompt=jax.random.randint(
            jax.random.PRNGKey(int(rng.integers(1 << 30))), (s_len,), 0,
            cfg.vocab,
        ), max_new=m))
    reqs, late = reqs[:-1], reqs[-1]
    if case == "eos_between":
        ref = stream(reqs[1])
        cuts = [k for k in range(1, len(ref)) if ref[k] not in ref[:k]]
        assume(cuts)  # a stream of one repeated token has no later cut
        reqs[1].eos = ref[cuts[0]]
    return reqs, late if late.max_new is not None else None


def drive_pinned(eng, reqs, late, case):
    """Co-admit `reqs`, submit `late` after `LATE_AFTER_TICK` ticks, and
    drain; asserts the case's slot layouts were occupied going into a
    tick. Returns every request driven."""
    for r in reqs:
        eng.submit(r)
    seen = []
    for t in range(1, 40):
        eng.tick()
        seen.append(tuple(
            i for i, r in enumerate(eng._slots) if r is not None
        ))
        if t == LATE_AFTER_TICK and late is not None:
            eng.submit(late)
        if not (eng._queue or seen[-1]) and (
            late is None or t > LATE_AFTER_TICK
        ):
            break
    for layout in PINNED[case][1]:
        assert layout in seen, (case, layout, seen)
    return reqs + ([late] if late is not None else [])


