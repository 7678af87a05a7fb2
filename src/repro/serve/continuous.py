"""Slot-based continuous-batching engine over one persistent donated cache.

Architecture (DESIGN.md §6): a fixed pool of ``n_slots`` decode slots backs
one pooled KV cache (batch dim == slot index). Per tick:

  1. **degradation sweep** — deadline-expired requests are rejected (still
     queued) or evicted (mid-decode) with an error status, so one
     pathological request cannot hold a slot forever;
  2. **admission** — each free slot takes the oldest arrived request: the
     prompt is prefilled into a fresh batch-1 cache, the first token is
     sampled from the prefill logits, and the slot row of the pooled cache
     is replaced via ``model.insert_slot`` (a batch-dim
     ``dynamic_update_slice`` per leaf — kpos included, so the fresh -1
     tail resets the previous occupant's stale positions);
  3. **decode** — ONE jitted step advances every slot: ``model.decode_at``
     with per-slot positions (each row writes slot ``pos % smax`` of its
     own cache row), then per-request sampling, fused in the same jit so
     the decode+sample step is a single auditable program. With
     ``guard_nonfinite`` (default on) the same jit also emits a per-slot
     health bit — an exponent-field integer compare over the row's logits
     (``resilience/detectors.py``), so guards add zero tensor-shaped
     multiplies and the full-PA audit stays clean;
  4. **quarantine** — a slot whose logits went non-finite (poisoned cache
     row, numeric escape) evicts ONLY its own request with status
     ``evicted_nonfinite``; its garbage token is discarded, never emitted.
     Batch-mates are untouched — lockstep rows are independent, so healthy
     requests keep bit-exact token parity with an un-poisoned trace. The
     freed slot returns to the pool (the next occupant's ``insert_slot``
     overwrites the full row) and counts as ``recovered`` once it
     completes a later request cleanly;
  5. **eviction** — finished requests (EOS / stop token / length budget)
     free their slot immediately; the freed slot admits from the queue on
     the next tick. No drain-the-batch stalls.

Per-request PRNG: the sampling key for request ``rid``'s ``j``-th token is
``fold_in(fold_in(PRNGKey(seed), rid), j)`` — a pure function of
(engine seed, request id, token index), so a request's stream is
bit-reproducible regardless of which slot it lands in or which batch-mates
share the step. Greedy decode is deliberately sampler-free, which is what
makes continuous output bit-match the one-shot engine per request.

Inactive slots still flow through the lockstep decode (the batch shape is
static): they are fed token 0 at position 0, write only their own free
cache row, and their sampled output is discarded.

Profiler spans (``jax.profiler.TraceAnnotation``, always on, about a
microsecond each when no trace is active) put the tick's host phases on
the device trace's clock: ``serve.tick`` (args ``tick``, ``active``,
``admitted``) covers ``step``; inside it one ``serve.admit`` (``rid``,
``prompt_len``) per admission with children ``.prepare`` (prefill batch
and fresh batch-1 cache), ``.prefill`` (prefill and first-token
dispatch), ``.first_token`` (the host fetch of the first token) and
``.insert`` (the slot insert dispatch); then ``serve.decode.launch``
(build the step's inputs, dispatch it), ``serve.decode.fetch`` (the host
fetch of its outputs) and ``serve.emit`` (quarantine, bookkeeping,
``on_token``, releases). The spans add no synchronisation: the fetches
they name are the engine's own.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.models.registry import Model
from .engine import (ServeConfig, cache_capacity_guard, make_prefill_batch,
                     pa_categorical, scale_logits)
from .scheduler import QueueFullError, Request, Scheduler, SlotState


def _fresh_counters() -> Dict[str, int]:
    return {"submitted": 0, "completed_ok": 0, "rejected_queue_full": 0,
            "expired_in_queue": 0, "evicted_deadline": 0,
            "evicted_nonfinite": 0, "recovered_slots": 0}


class ContinuousEngine:
    """Drives a ``Scheduler`` over jitted per-slot model steps.

    ``on_token`` callbacks (``run``/``step``) receive ``(rid, token)`` as
    each token is produced — the streaming output surface.

    ``fault_plan`` (``resilience.FaultPlan``) arms deterministic chaos:
    ``poison_slot`` specs NaN the target request's cache row at an exact
    tick. None in production — the hot path pays nothing.
    """

    def __init__(self, model: Model, params, cfg: ServeConfig = ServeConfig(),
                 fault_plan=None):
        self.model, self.params, self.cfg = model, params, cfg
        self.fault_plan = fault_plan
        self.cache = model.init_cache(cfg.n_slots, cfg.max_len)
        self.reset()
        self._build()

    # -- jitted model surface ----------------------------------------------
    def _build(self):
        model, cfg = self.model, self.cfg
        pa = model.cfg.pa
        temp, seed, guard = cfg.temperature, cfg.seed, cfg.guard_nonfinite
        record = cfg.record

        def fold_key(rid, j):
            key = jax.random.PRNGKey(seed)
            return jax.random.fold_in(jax.random.fold_in(key, rid), j)

        def health(lg):
            # per-slot non-finite bit: exponent-field integer compare over
            # the row's logits (audit-exempt — no float math at all)
            from repro.resilience.detectors import nonfinite_rows
            return nonfinite_rows(lg, axis=-1)

        def digest(lg):
            # flight recorder (DESIGN.md §8): per-slot logits fingerprint
            # over the RAW pre-temperature bits — bitcast + integer mixing
            # only, so recording keeps the full-PA audit at zero
            from repro.resilience.recorder import rows_digest
            return rows_digest(lg)

        def extras(raw):
            out = ()
            if guard:
                # guard the RAW logits: 1/T scaling of an inf row can
                # only keep or lose information, never create it
                out += (health(raw),)
            if record:
                out += (digest(raw),)
            return out

        if temp <= 0:
            def step(params, cache, tok, pos):
                logits, cache = model.decode_at(params, cache, tok, pos)
                lg = logits[:, -1].astype(jnp.float32)
                nxt = jnp.argmax(lg, -1).astype(jnp.int32)
                return (nxt,) + extras(lg) + (cache,)

            def first(logits, rid):
                lg = logits[:, -1].astype(jnp.float32)
                tok = jnp.argmax(lg, -1)[0].astype(jnp.int32)
                if record:
                    return tok, digest(lg)[0]
                return tok
        else:
            if pa.nonlin_is_pa and pa.impl != "hw":
                # PA Gumbel-argmax: jax.random.categorical's Gumbel path
                # emits a native tensor multiply, which would break the
                # full-PA decode-step audit for temperature > 0.
                def draw(key, row):
                    return pa_categorical(key, row, pa.deriv)
            else:
                def draw(key, row):
                    return jax.random.categorical(key, row).astype(jnp.int32)

            def step(params, cache, tok, pos, rids, js):
                logits, cache = model.decode_at(params, cache, tok, pos)
                raw = logits[:, -1].astype(jnp.float32)
                lg = scale_logits(raw, temp, pa)
                keys = jax.vmap(fold_key)(rids, js)
                nxt = jax.vmap(draw)(keys, lg).astype(jnp.int32)
                return (nxt,) + extras(raw) + (cache,)

            def first(logits, rid):
                raw = logits[:, -1].astype(jnp.float32)
                lg = scale_logits(raw, temp, pa)
                tok = draw(fold_key(rid, 0), lg[0]).astype(jnp.int32)
                if record:
                    return tok, digest(raw)[0]
                return tok

        self._step_impl = step        # unjitted: the audit traces this
        self._step_fn = jax.jit(step, donate_argnums=(1,))
        self._first_fn = jax.jit(first)
        self._prefill_fn = jax.jit(model.prefill)
        self._insert_fn = jax.jit(model.insert_slot, donate_argnums=(0,))

    def reset(self) -> None:
        """Clear scheduler + telemetry for a fresh trace on the SAME
        compiled engine (timing rounds reuse the jitted steps; the pooled
        cache needs no clearing — admission overwrites a slot's full row
        and inactive rows are never read)."""
        self.scheduler = Scheduler(self.cfg.n_slots,
                                   max_queue=self.cfg.max_queue)
        self._tokens: Dict[int, List[int]] = {}
        # flight recorder (cfg.record): running per-request digest — every
        # emitted token id + its step's logits-row fingerprint folded in
        self._digests: Dict[int, int] = {}
        self.counters = _fresh_counters()
        self._tainted_slots: set = set()
        self.metrics = {"ticks": 0, "prefills": 0, "occupancy": [],
                        "emit_wall": {}, "visible_wall": {}}

    # -- request intake ----------------------------------------------------
    def submit(self, req: Request) -> None:
        rid = req.rid
        if (rid in self._tokens or rid in self.scheduler.status
                or any(r.rid == rid for r in self.scheduler.pending)):
            # a reused rid would silently clobber self._tokens[rid] and the
            # finished dict, corrupting per-request parity accounting
            raise ValueError(
                f"duplicate request id {rid}: already "
                f"{'pending or active' if rid not in self.scheduler.status else 'finished'} "
                f"on this engine")
        cache_capacity_guard(self.model.cfg, self.cfg.max_len,
                             len(req.prompt), req.max_new_tokens)
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: max_new_tokens must be >= 1")
        try:
            self.scheduler.submit(req)
        except QueueFullError:
            self.counters["rejected_queue_full"] += 1
            raise          # explicit backpressure: the caller sheds/retries
        self.counters["submitted"] += 1

    # -- scheduler tick ----------------------------------------------------
    def _admit(self, slot: SlotState, req: Request,
               on_token: Optional[Callable]) -> None:
        sch = self.scheduler
        with TraceAnnotation("serve.admit", rid=req.rid,
                             prompt_len=len(req.prompt)):
            with TraceAnnotation("serve.admit.prepare"):
                batch = make_prefill_batch(
                    self.model.cfg, np.asarray(req.prompt, np.int32)[None])
                one = self.model.init_cache(1, self.cfg.max_len)
            with TraceAnnotation("serve.admit.prefill"):
                logits, one = self._prefill_fn(self.params, batch, one)
                picked = self._first_fn(logits, jnp.int32(req.rid))
            with TraceAnnotation("serve.admit.first_token"):
                if self.cfg.record:
                    from repro.resilience.recorder import (
                        fold_token, request_digest_seed)
                    first, fdig = picked
                    first = int(first)
                    self._digests[req.rid] = fold_token(
                        request_digest_seed(req.rid), first, int(fdig))
                else:
                    first = int(picked)
            with TraceAnnotation("serve.admit.insert"):
                self.cache = self._insert_fn(self.cache, one,
                                             np.int32(slot.index))
            self.metrics["prefills"] += 1
            sch.activate(slot, req, first)
            self._tokens[req.rid] = [first]
            self._emit(req.rid, first, on_token)
            if sch.should_finish(slot, first, self.cfg.eos_id):
                self._release(slot)

    def _release(self, slot: SlotState, status: str = "ok") -> None:
        rid = slot.request.rid
        self.scheduler.release(slot, self._tokens[rid], status=status)
        if status == "ok":
            self.counters["completed_ok"] += 1
            if slot.index in self._tainted_slots:
                # a slot that previously evicted a poisoned request has now
                # served a healthy one end-to-end: back in full service
                self._tainted_slots.discard(slot.index)
                self.counters["recovered_slots"] += 1
        elif status == "evicted_nonfinite":
            self._tainted_slots.add(slot.index)

    def _emit(self, rid: int, token: int, on_token: Optional[Callable]) -> None:
        self.metrics["emit_wall"].setdefault(rid, []).append(
            time.perf_counter())
        if on_token is not None:
            on_token(rid, token)

    def _degrade(self) -> None:
        """Deadline sweep: reject still-queued and evict mid-decode
        requests past their tick budget (graceful degradation — partial
        output is returned with an explicit error status)."""
        sch = self.scheduler
        pend, act = sch.expired()
        for req in pend:
            sch.reject(req, "deadline_expired_in_queue")
            self.counters["expired_in_queue"] += 1
        for slot in act:
            self._release(slot, status="evicted_deadline")
            self.counters["evicted_deadline"] += 1

    def step(self, on_token: Optional[Callable] = None) -> int:
        """One scheduler tick: degrade (deadlines), admit, decode all
        active slots lockstep, quarantine non-finite slots, evict finished.
        Returns the number of tokens produced."""
        sch = self.scheduler
        with TraceAnnotation("serve.tick", tick=sch.tick) as span:
            now = time.perf_counter()
            for req in sch.pending:
                if req.arrival <= sch.tick:
                    self.metrics["visible_wall"].setdefault(req.rid, now)
            self._degrade()
            admissions = sch.admissions()
            for slot, req in admissions:
                self._admit(slot, req, on_token)

            if self.fault_plan is not None:
                spec = self.fault_plan.pop("poison_slot", sch.tick)
                if spec is not None:
                    from repro.resilience.faults import poison_cache_row
                    target = next((s for s in sch.active_slots()
                                   if s.request.rid == spec.rid), None)
                    if target is not None:
                        self.cache = poison_cache_row(self.model, self.cache,
                                                      target.index)

            active = sch.active_slots()
            span.set_metadata(active=len(active), admitted=len(admissions))
            produced = self._decode(active, on_token) if active else 0
            self.metrics["occupancy"].append(len(active) / self.cfg.n_slots)
            self.metrics["ticks"] += 1
            sch.tick += 1
        return produced

    def _decode(self, active: List[SlotState],
                on_token: Optional[Callable]) -> int:
        """The tick's lockstep decode over ``active``, then quarantine,
        bookkeeping and releases; returns the tokens emitted."""
        sch, cfg = self.scheduler, self.cfg
        with TraceAnnotation("serve.decode.launch"):
            n = cfg.n_slots
            tok = np.zeros((n, 1), np.int32)
            pos = np.zeros((n,), np.int32)
            for s in active:
                tok[s.index, 0] = s.last_token
                pos[s.index] = s.next_pos
            if cfg.temperature <= 0:
                args = (self.params, self.cache, tok, pos)
            else:
                rids = np.zeros((n,), np.int32)
                js = np.zeros((n,), np.int32)
                for s in active:
                    rids[s.index] = s.request.rid
                    js[s.index] = s.produced
                args = (self.params, self.cache, tok, pos, rids, js)
            outs = self._step_fn(*args)
            nxt, rest = outs[0], list(outs[1:-1])
            self.cache = outs[-1]
        with TraceAnnotation("serve.decode.fetch"):
            bad = np.asarray(rest.pop(0)) if cfg.guard_nonfinite else None
            digs = np.asarray(rest.pop(0)) if cfg.record else None
            nxt = np.asarray(nxt)
        produced = 0
        with TraceAnnotation("serve.emit"):
            for s in active:
                if bad is not None and bad[s.index]:
                    # quarantine: this slot's logits went non-finite — its
                    # garbage token is never emitted, only ITS request is
                    # evicted; batch-mates' rows are independent and keep
                    # bit-exact parity with an un-poisoned trace
                    self._release(s, status="evicted_nonfinite")
                    self.counters["evicted_nonfinite"] += 1
                    continue
                t = int(nxt[s.index])
                s.next_pos += 1
                s.produced += 1
                s.last_token = t
                self._tokens[s.request.rid].append(t)
                if digs is not None:
                    # fold only EMITTED tokens: a quarantined slot's garbage
                    # token never reaches the digest, matching the token
                    # stream the client actually saw
                    from repro.resilience.recorder import fold_token
                    rid = s.request.rid
                    self._digests[rid] = fold_token(
                        self._digests[rid], t, int(digs[s.index]))
                self._emit(s.request.rid, t, on_token)
                produced += 1
                if sch.should_finish(s, t, cfg.eos_id):
                    self._release(s)
        return produced

    # -- drivers -----------------------------------------------------------
    def run(self, requests: List[Request],
            on_token: Optional[Callable] = None) -> Dict[int, np.ndarray]:
        """Submit all requests and tick until the queue drains. Returns
        {rid: (n_tokens,) int32} in completion order."""
        for req in requests:
            self.submit(req)
        while not self.scheduler.idle:
            self.step(on_token)
        return {rid: np.asarray(toks, np.int32)
                for rid, toks in self.scheduler.finished.items()}

    # -- telemetry ---------------------------------------------------------
    def health_snapshot(self) -> Dict[str, float]:
        """Recovery/degradation counters (all numeric): submissions,
        clean completions, queue-full rejections, deadline
        rejections/evictions, non-finite quarantine evictions, and slots
        recovered back into service after a quarantine."""
        snap = {k: float(v) for k, v in self.counters.items()}
        snap["tainted_slots"] = float(len(self._tainted_slots))
        snap["pending"] = float(len(self.scheduler.pending))
        snap["active"] = float(len(self.scheduler.active_slots()))
        return snap

    def latency_summary(self) -> Dict[str, float]:
        """TTFT and inter-token latency percentiles (seconds) plus mean
        slot occupancy — the BENCH_serve.json methodology (DESIGN.md §6) —
        and the ``health_snapshot`` recovery counters (``recovery_*``)."""
        ttft, gaps = [], []
        for rid, emits in self.metrics["emit_wall"].items():
            vis = self.metrics["visible_wall"].get(rid, emits[0])
            ttft.append(emits[0] - vis)
            gaps.extend(b - a for a, b in zip(emits, emits[1:]))
        pct = lambda xs, q: float(np.percentile(xs, q)) if xs else 0.0
        occ = self.metrics["occupancy"]
        out = {
            "ttft_p50_s": pct(ttft, 50), "ttft_p99_s": pct(ttft, 99),
            "per_token_p50_s": pct(gaps, 50), "per_token_p99_s": pct(gaps, 99),
            "slot_occupancy_mean": float(np.mean(occ)) if occ else 0.0,
            "ticks": float(self.metrics["ticks"]),
            "prefills": float(self.metrics["prefills"]),
        }
        for k, v in self.health_snapshot().items():
            out[f"recovery_{k}"] = v
        if self.cfg.record:
            # bit-exact per-request fingerprints (token ids + logits bits):
            # two traces of the same workload must match digest-for-digest —
            # the serve-bench determinism gate compares exactly this dict
            out["request_digests"] = {
                str(rid): f"0x{d:08x}"
                for rid, d in sorted(self._digests.items())}
        return out

    def decode_step_jaxpr(self):
        """Trace the fused decode+sample step (the serving hot loop) —
        the program the audit layers (repro.analysis) inspect."""
        n = self.cfg.n_slots
        args = [self.params, self.cache, jnp.zeros((n, 1), jnp.int32),
                jnp.zeros((n,), jnp.int32)]
        if self.cfg.temperature > 0:
            args += [jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32)]
        return jax.make_jaxpr(self._step_impl)(*args)

    def decode_step_mul_stats(self) -> Dict:
        """Multiplication audit of the fused decode+sample step: count
        tensor-shaped mul-family ops (repro.analysis.jaxpr_mul_stats).
        Full-PA mode must report ``tensor_total == 0`` — including the
        non-finite guard, which is integer exponent-field compares only."""
        from repro.analysis import jaxpr_mul_stats
        return jaxpr_mul_stats(self.decode_step_jaxpr())
