"""LM serving under test: the paged slot `Engine` (`repro.serve.engine`)
driven by the harness's own open-loop driver.

The driver does what the `Frontend`'s driver thread does (submit what is
due, tick), and notes the wall time at which each request's `output`
grows: the `Frontend` replies only when a request completes, so it
cannot show a first token. A token's time is the end of the tick that
produced it, measured from the window's start; a request's first-token
latency counts from its intended arrival (open loop), so a stall delays
every request due behind it.
"""

from __future__ import annotations

import time

import numpy as np

# the decode executable's name in a profiler trace: the paged decode
# cell is a jitted lambda
DECODE_MODULE = "jit__lambda"


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.mix, self.ref = ctx.cfg, ctx.mix, ctx.ref
        self.tracer = ctx.tracer
        self.faults = ctx.faults
        self.span = ctx.span

    # -- set-up -------------------------------------------------------------

    def arch(self):
        from repro.configs.base import ArchConfig

        c = self.cfg
        return ArchConfig(
            name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            qk_norm=True, rope_theta=float(c["rope_theta"]),
            tie_embeddings=c["tie_word_embeddings"], act="swiglu",
            norm="rmsnorm", dtype=c["torch_dtype"],
        )

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.models import api
        from repro.serve import engine as E
        from repro.serve.paging import PagingConfig, validate_page_size

        mix = self.mix
        slots, page = mix["slots"], mix["page_size"]
        model = api.build_model(self.arch(), tp=1, max_seq=mix["max_seq"])
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        params = self.ref.make_params(self.ctx.key, shapes)
        span = validate_page_size(page, model.attn_capacities())
        self.engine = E.Engine(model, params, batch_size=slots,
                               paging=PagingConfig(page, slots * span + 1))
        del params
        self._Request = E.Request
        self.reschedule(mix, self.ctx.seed)
        plen = mix["prompt_len"]
        # every admission width 1..slots compiles its prefill and seat
        # cells, and the pool decode compiles once (as
        # repro.obs.loadlab.warm_engine does); uids above the window's
        uid = len(self.schedule) + 1
        for k in range(1, slots + 1):
            for j in range(k):
                self.engine.submit(E.Request(
                    uid=uid, prompt=jnp.full((plen,), j, jnp.int32),
                    max_new=2))
                uid += 1
            self.engine.run(max_ticks=16)
        jax.block_until_ready(self.engine.cache)
        if self.faults:
            self._plant_faults()

    def reschedule(self, mix: dict, seed: int) -> None:
        """Draw the window's requests (their prompts go to the device
        here, in set-up)."""
        import jax.numpy as jnp

        self.schedule = self.ctx.loadgen.lm_schedule(
            mix, seed, self.ctx.seconds, self.cfg["vocab_size"])
        self.prompts = [jnp.asarray(p) for p in self.schedule.prompts]

    def backlog_at(self, t: float) -> int:
        """Requests due by `t` seconds that had no first token by then."""
        due = self.schedule.arrival_s <= t
        return int(sum(1 for i in range(len(self.times))
                       if due[i] and (not self.times[i]
                                      or self.times[i][0] > t)))

    def drain(self) -> None:
        """Finish every request of the last window (the sweep's next rate
        starts from an empty engine)."""
        for r in self.reqs[: self.submitted]:
            r.max_new = min(r.max_new, len(r.output) + 1)
        self.engine.run(max_ticks=100_000)

    def _plant_faults(self) -> None:
        import jax.numpy as jnp

        eng, faults = self.engine, self.faults
        decode = eng._decode

        def broken(params, cache, tok, pos):
            logits, new = decode(params, cache, tok, pos)
            if faults.get("state_unchanged"):
                new = cache
            if faults.get("half_batch"):
                b = logits.shape[0]
                logits = logits.at[b // 2:].set(0.0)
            if faults.get("answer_altered"):
                logits = logits.at[:, 1].set(jnp.max(logits) + 1.0)
            return logits, new

        eng._decode = broken

    # -- the timed path -----------------------------------------------------

    def run_window(self, seconds: float) -> None:
        eng, sched = self.engine, self.schedule
        n = len(sched)
        reqs = [self._Request(uid=i, prompt=self.prompts[i],
                              max_new=int(sched.max_new[i]))
                for i in range(n)]
        arrive = sched.arrival_s
        times: list[list[float]] = [[] for _ in range(n)]
        live: list[int] = []
        ticks = active_sum = 0
        traced = []
        submitted = 0
        t0 = time.perf_counter()
        self.tracer.start_window(t0)
        now = 0.0
        while now < seconds:
            while submitted < n and arrive[submitted] <= now:
                eng.submit(reqs[submitted])
                live.append(submitted)
                submitted += 1
            if not live:
                nxt = arrive[submitted] if submitted < n else seconds
                time.sleep(max(0.0, min(nxt, seconds) - now))
                now = time.perf_counter() - t0
                self.tracer.poll()
                continue
            tracing = self.tracer.active
            if tracing:
                before = {i: len(reqs[i].output) for i in live}
            with self.span("bench/lm_tick"):
                active = eng.tick()
            t = time.perf_counter() - t0
            ticks += 1
            active_sum += active
            still = []
            for i in live:
                out = reqs[i].output
                k = len(out) - len(times[i])
                if k:
                    times[i].extend([t] * k)
                if not reqs[i].done:
                    still.append(i)
            if tracing:
                self._note_work(traced, reqs, live, before)
            live = still
            now = t
            self.tracer.poll()
        self.tracer.close()
        self.window_s = now
        self.reqs, self.times, self.submitted = reqs, times, submitted
        self.ticks, self.active_sum = ticks, active_sum
        self.traced = traced

    def _note_work(self, traced, reqs, live, before) -> None:
        """Work of one traced tick's decode step: the context of every
        request that got a token from it (its cache then holds its prompt
        and every token fed back; a first token comes from the prefill)."""
        plen = self.mix["prompt_len"]
        ctx = []
        for i in live:
            b, a = before[i], len(reqs[i].output)
            if a - b - (b == 0 and a > 0) > 0:
                ctx.append(plen + a - 1)
        if ctx:
            traced.append(ctx)

    # -- after the window ---------------------------------------------------

    def release(self) -> None:
        """Drop the engine (its closures hold it in cycles, so collect)."""
        import gc

        del self.engine, self.prompts
        gc.collect()

    def _stats(self) -> dict:
        arrive = self.schedule.arrival_s
        ttft, gaps, tokens = [], [], 0
        started = finished = 0
        for i, ts in enumerate(self.times):
            if not ts:
                continue
            started += 1
            tokens += len(ts)
            ttft.append(ts[0] - arrive[i])
            gaps.extend(np.diff(ts))
            finished += self.reqs[i].done
        return {"ttft": ttft, "gaps": gaps, "tokens": tokens,
                "started": started, "finished": finished}

    def end_to_end(self) -> dict:
        s, pct = self._stats(), self.ctx.loadgen.percentile
        out = {"lm_tokens_per_s": s["tokens"] / self.window_s}
        if s["ttft"]:
            out["lm_ttft_p95_ms"] = 1e3 * pct(s["ttft"], 95)
        if s["gaps"]:
            out["lm_itl_p95_ms"] = 1e3 * pct(s["gaps"], 95)
        return out

    def counters(self) -> dict:
        s = self._stats()
        return {
            "ticks": self.ticks,
            "active_sum": self.active_sum,
            "slots": self.mix["slots"],
            "tokens": s["tokens"],
            "submitted": self.submitted,
            "started": s["started"],
            "finished": s["finished"],
            "window_s": self.window_s,
        }

    def attempted_failed(self) -> tuple[int, int]:
        return self.submitted, 0

    def notes(self) -> list[str]:
        c, s = self.counters(), self._stats()
        pct = self.ctx.loadgen.percentile
        return [
            f"{c['submitted']} requests due in {c['window_s']:.3f}s, "
            f"{c['started']} got a first token, {c['finished']} finished, "
            f"{c['submitted'] - c['finished']} unfinished at the close; "
            f"{c['tokens']} tokens in {c['ticks']} ticks",
            f"ttft p50 {pct(s['ttft'], 50)} s over {len(s['ttft'])}, "
            f"itl p50 {pct(s['gaps'], 50)} s over {len(s['gaps'])}",
        ]

    def work(self) -> dict:
        """Work of the decode executable over the traced ticks."""
        cfg, ref, peak = self.cfg, self.ref, self.ctx.peak
        w = [ref.decode_work(cfg, c) for c in self.traced]
        return {"decode": {
            "module": DECODE_MODULE,
            "calls": len(w),
            "flops": sum(f for f, _ in w),
            "bytes": sum(b for _, b in w),
            "least_s": sum(max(f / peak["bf16_flops"],
                               b / peak["hbm_bytes_per_s"]) for f, b in w),
        }}

    def _pick(self) -> list:
        done = [i for i, r in enumerate(self.reqs) if r.done]
        if not done:
            return []
        longest = max(done, key=lambda i: len(self.reqs[i].output))
        return self.ctx.loadgen.sample(
            self.ctx.seed, done, self.mix["check_requests"], must=[longest])

    def control(self) -> dict:
        """The control's reading on the same sampled requests: at every
        served position, the gap of the token that the reference computed
        in fp8 (one precision step below bfloat16) puts first."""
        toks, served = self._sequences(self._pick())
        gaps = self.ref.token_gaps(self.ctx.key, self.cfg, toks, served,
                                   precision="fp8")["first_gap"]
        return {"lm_token_gap": float(gaps[served >= 0].max())}

    def check(self) -> list[dict]:
        """A sample of the finished requests, the longest among them, run
        through the reference: the widest gap by which a served token's
        logit lies below the reference's best."""
        pick = self._pick()
        if not pick:
            return [{"name": "lm_finished", "value": 0, "limit": 1,
                     "what": "no request finished in the window",
                     "ok": False}]
        toks, served = self._sequences(pick)
        out = self.ref.token_gaps(self.ctx.key, self.cfg, toks, served)
        gap = out["served_gap"][served >= 0]
        return [{
            "name": "lm_token_gap", "value": float(gap.max()),
            "limit": self.ctx.limits["lm_token_gap"],
            "what": f"widest reference-logit gap below the best of a "
                    f"served token, {gap.size} tokens of {len(pick)} "
                    f"requests",
        }]

    def _sequences(self, pick):
        """(B, S) prompt + served tokens (the last one is never fed), and
        (B, S) the served token each position predicts (-1 elsewhere)."""
        plen = self.mix["prompt_len"]
        seqs = [np.concatenate([self.schedule.prompts[i],
                                np.asarray(self.reqs[i].output[:-1])])
                for i in pick]
        s = max(len(x) for x in seqs)
        toks = np.zeros((len(pick), s), np.int32)
        served = np.full((len(pick), s), -1, np.int32)
        for b, (i, x) in enumerate(zip(pick, seqs)):
            toks[b, : len(x)] = x
            out = self.reqs[i].output
            served[b, plen - 1: plen - 1 + len(out)] = out
        return toks, served
