"""Serving cells: open-loop Poisson traffic into the program's continuous-
batching engine (greedy), on the wall clock.

Each request is submitted when its due time passes and timed from that
due time. The schedule starts ``warmup_s`` before the window, so the
window opens in steady state, and keeps arriving after it until every
request due in the window has finished. The window holds rate x seconds
requests in blocks of about ``block_s`` seconds; every seed gets the same
multiset of inter-arrival gaps, prompt lengths and output budgets
(quantiles of their distributions) in each block, in its own order.
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np
import jax
import jax.numpy as jnp

from bench import arch, harness, program, trace, weights
from bench.reference import model as ref_model


def _segment(r, tr, start, span, blocks):
    """Arrivals in [start, start + span): rate * span requests in
    ``blocks`` equal blocks; each block holds the quantiles of the
    inter-arrival, prompt-length and output-budget distributions for its
    count, in an order drawn from ``r``, its gaps scaled to fill it."""
    n = int(round(tr["rate_rps"] * span))
    w = np.asarray(tr["prompt_weights"], float)
    cdf = np.cumsum(w / w.sum())
    lo, hi = tr["output_min"], tr["output_max"]
    edges = np.linspace(0, n, blocks + 1).round().astype(int)
    out, t = [], start
    for a, b in zip(edges, edges[1:]):
        m = b - a
        if not m:
            continue
        q = (np.arange(m) + 0.5) / m
        gaps = r.permutation(-np.log1p(-q))
        gaps *= span * m / n / gaps.sum()
        lens = r.permutation(np.asarray(tr["prompt_lengths"])[
            np.searchsorted(cdf, q, side="right").clip(0, len(w) - 1)])
        outs = r.permutation(lo + np.floor(q * (hi - lo + 1)).astype(int))
        for g, p, o in zip(gaps, lens, outs):
            out.append((float(t), int(p), int(o)))
            t += g
    return out


def schedule(seed, tr, seconds):
    """[(due s from the window's start, prompt len, output budget)] for
    the warm-up before the window, the window, and the tail after it.
    Every seed gets the same work in each block, in its own order."""
    r = np.random.default_rng(weights.key_words(seed, "serve-schedule"))
    nb = lambda span: max(1, int(round(span / tr["block_s"])))
    return (_segment(r, tr, -tr["warmup_s"], tr["warmup_s"], nb(tr["warmup_s"]))
            + _segment(r, tr, 0.0, seconds, nb(seconds))
            + _segment(r, tr, seconds, tr["tail_s"], nb(tr["tail_s"])))


def prompts(seed, sched, vocab):
    r = np.random.default_rng(weights.key_words(seed, "serve-prompts"))
    return [r.integers(0, vocab, p).astype(np.int32) for _, p, _ in sched]


class Program:
    """The program's engine with the benchmark's weights, warmed up on
    the traffic's prompt lengths."""

    def __init__(self, run, seed, token_fault=None):
        from repro.serve import ContinuousEngine, Request, ServeConfig
        self.Request = Request
        conf, tr = run.config, run.traffic
        if not tr["greedy"]:
            raise ValueError("the serving check compares greedy tokens only")
        self.sizes, self.ref = program.sizes(conf), arch.of(conf)
        self.model = program.build(conf)
        for p in tr["prompt_lengths"]:
            program.check_kv_block(conf, p, tr["max_len"])
        self.params = weights.init_params(self.ref, self.sizes, seed)
        program.check_params(self.model, self.params)
        self.engine = ContinuousEngine(self.model, self.params, ServeConfig(
            max_len=tr["max_len"], n_slots=tr["slots"], temperature=0.0))
        if token_fault is not None:
            token_fault(self.engine)
        if self.sizes["mode"] == "full":
            self.audit(tr)
        self.warm(tr)

    def audit(self, tr):
        eng, m = self.engine, self.model
        n = int(eng.decode_step_mul_stats()["tensor_total"])
        for p in tr["prompt_lengths"]:
            batch = {"tokens": jnp.zeros((1, p), jnp.int32)}
            cache = m.init_cache(1, tr["max_len"])
            n += program.mul_audit(jax.make_jaxpr(m.prefill)(self.params, batch, cache))
        harness.log(audit="serve prefill (each prompt length) and decode step",
                    tensor_multiplies=n)
        if n:
            raise RuntimeError(f"the full-PA serving programs have {n} "
                               "tensor multiplies")

    def warm(self, tr):
        """Compile every program the traffic uses: a prefill per prompt
        length, the first-token pick, the slot insert, the decode step."""
        reqs = [self.Request(rid=-1 - i, prompt=np.zeros(p, np.int32),
                             max_new_tokens=2)
                for i, p in enumerate(tr["prompt_lengths"])]
        self.engine.run(reqs)
        self.engine.reset()

    def reseed(self, seed):
        self.params = weights.init_params(self.ref, self.sizes, seed)
        self.engine.params = self.params
        self.engine.reset()


def serve(prog, sched, toks, seconds, counter, tracer=None):
    """Drive the schedule; returns the record of the window. A tracer
    records the window alone."""
    eng, Request = prog.engine, prog.Request
    emits, late, ticks = {}, [], []
    in_window = [i for i, (d, _, _) in enumerate(sched) if 0 <= d < seconds]

    def on_token(rid, tok):
        emits.setdefault(rid, []).append(harness.now())

    t0 = harness.now() + (-sched[0][0] if sched[0][0] < 0 else 0.0)
    i, c0, win, closed = 0, None, None, False
    with trace.span("bench.serve"):
        while True:
            t = harness.now()
            if c0 is None and t >= t0:
                c0 = counter.n
                if tracer:
                    tracer.start()
                win = trace.span("bench.window")
                win.__enter__()
            if win is not None and not closed and t >= t0 + seconds:
                closed = True
                win.__exit__(None, None, None)
                if tracer:
                    tracer.data = tracer.stop()
            while i < len(sched) and t0 + sched[i][0] <= t:
                eng.submit(Request(rid=i, prompt=toks[i],
                                   max_new_tokens=sched[i][2],
                                   arrival=eng.scheduler.tick))
                late.append(t - (t0 + sched[i][0]))
                i += 1
            if all(r in eng.scheduler.status for r in in_window):
                break
            if eng.scheduler.idle:
                nxt = t0 + sched[i][0] if i < len(sched) else t + 0.01
                with trace.span("bench.wait_arrival"):
                    time.sleep(max(0.0, min(nxt - harness.now(), 0.01)))
                continue
            with trace.span("bench.tick"):
                eng.step(on_token)
            ticks.append((harness.now(), eng.metrics["occupancy"][-1]))
    return {"t0": t0, "emits": emits, "late": late, "ticks": ticks,
            "in_window": in_window,
            "compiles": counter.n - c0, "status": dict(eng.scheduler.status),
            "tokens": {r: list(v) for r, v in eng.scheduler.finished.items()}}


def summarize(rec, sched, seconds):
    t0, t1 = rec["t0"], rec["t0"] + seconds
    ttft, gaps = [], []
    for r in rec["in_window"]:
        e = rec["emits"].get(r)
        if not e:
            continue
        ttft.append(e[0] - (t0 + sched[r][0]))
        gaps.extend(b - a for a, b in zip(e, e[1:]))
    n_win = sum(1 for e in rec["emits"].values() for x in e if t0 <= x < t1)
    occ = [o for t, o in rec["ticks"] if t0 <= t < t1]
    return {"itl_p90_s": float(np.percentile(gaps, 90))}, {
            "requests_in_window": len(rec["in_window"]),
            "ttft_n": len(ttft), "itl_n": len(gaps),
            "ttft_pct_s": {q: float(np.percentile(ttft, q)) for q in (50, 75, 90)},
            "itl_mean_s": float(np.mean(gaps)),
            "itl_pct_s": {q: float(np.percentile(gaps, q))
                          for q in (50, 80, 85, 90, 93, 95, 97, 99)},
            "tokens_in_window": n_win,
            "generator_late_p50_s": float(np.percentile(rec["late"], 50)),
            "generator_late_p99_s": float(np.percentile(rec["late"], 99)),
            "slot_occupancy_mean": float(np.mean(occ)) if occ else None,
            "window_ticks": len(occ)}


def sample(seed, rec, sched, n):
    """Requests to check, drawn from the seed among those finished in the
    window, the longest among them."""
    done = [r for r in rec["in_window"] if rec["status"].get(r) == "ok"]
    longest = max(done, key=lambda r: sched[r][1] + len(rec["tokens"][r]))
    rest = [r for r in done if r != longest]
    r = np.random.default_rng(weights.key_words(seed, "serve-check"))
    pick = list(r.choice(rest, size=min(n - 1, len(rest)), replace=False))
    return [longest] + [int(x) for x in pick]


@functools.lru_cache(maxsize=None)
def _forward(ref, sizes_items):
    s = dict(sizes_items)
    return jax.jit(lambda p, t, st, n: ref.logits(n, s, p, t, st),
                   static_argnums=3)


def reference_gaps(run, seed, reqs, toks, served, lower=False):
    """For each checked request: the reference's logits over its prompt
    and served tokens, and each served token's gap below the best (with
    ``lower``: the gap of the token the control puts first)."""
    s, tr, ref = program.sizes(run.config), run.traffic, arch.of(run.config)
    L = tr["max_len"]
    rows, stream, spans = [], [], []
    for r in reqs:
        p, out = toks[r], served[r]
        seq = np.concatenate([p, np.asarray(out[:-1], np.int32)])
        rows.append(np.pad(seq, (0, L - len(seq))))
        stream.append(np.arange(L) < len(p))
        spans.append((len(p) - 1, len(out)))
    params = weights.init_params(ref, s, seed)
    nx = ref_model.Numerics(mode=s["mode"], act=s["compute_dtype"],
                            kv_block=s["kv_block"] or 128)
    fwd = _forward(ref, tuple(sorted(s.items())))
    tok_a, st_a = jnp.asarray(np.stack(rows)), jnp.asarray(np.stack(stream))
    lg = np.asarray(fwd(params, tok_a, st_a, nx).astype(jnp.float32))
    pick = None
    if lower:
        import dataclasses
        lo = np.asarray(fwd(params, tok_a, st_a,
                            dataclasses.replace(nx, lower=True)).astype(jnp.float32))
        pick = lo.argmax(-1)
    gaps = []
    for k, (r, (a, n)) in enumerate(zip(reqs, spans)):
        rows_lg = lg[k, a:a + n]
        chosen = (np.asarray(served[r], int) if pick is None else pick[k, a:a + n])
        gaps.extend(rows_lg.max(-1) - rows_lg[np.arange(n), chosen])
    return float(max(gaps)), len(gaps)


def run_cell(run):
    tr = run.traffic
    seconds = run.seconds
    if run.trace:
        seconds = min(seconds, tr.get("trace_seconds", seconds))
    prog = Program(run, run.seed, getattr(run, "token_fault", None))
    sched = schedule(run.seed, tr, seconds)
    toks = prompts(run.seed, sched, prog.sizes["vocab_size"])
    tracer = trace.Tracer() if run.trace else None
    run.setup_s = harness.now() - run.t_start
    rec = serve(prog, sched, toks, seconds, run.counter, tracer)
    if tracer:
        run.trace_data = tracer.data
    run.e2e, run.counts = summarize(rec, sched, seconds)
    harness.log(window_compilations=rec["compiles"], **run.counts)
    run.memory_peak = harness.peak_bytes(run.devices)
    harness.log(peak_bytes_in_use=run.memory_peak)
    run.attempted = len(rec["in_window"])
    run.failed = sum(rec["status"].get(r) != "ok" for r in rec["in_window"])
    reqs = sample(run.seed, rec, sched, tr["check_requests"])
    served = rec["tokens"]
    del prog
    gc.collect()
    gap, n = reference_gaps(run, run.seed, reqs, toks, served)
    harness.log(checked_requests=reqs, checked_tokens=n)
    run.checks = {
        "served_logit_gap": {"value": gap, "limit": run.limits["served_logit_gap"]},
        "window_compilations": {"value": rec["compiles"], "limit": 0},
        "failed_requests": {"value": run.failed, "limit": 0},
    }
