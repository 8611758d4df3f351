"""Batched decode server: continuous batching over fixed decode slots,
with cost-model-informed admission.

A fixed (B, max_len) KV/SSM state is allocated once; finished sequences
free their slot, which is refilled from the request queue (prefill of the
new prompt writes into that slot's cache rows).  This is the standard
slot-based continuous-batching layout adapted to JAX's static shapes:
the *shapes* never change, only slot occupancy masks do.

Admission is where the unified cost model pays off at serving time: an
``AdmissionScorer`` compiles TWO fused basis programs once —

  * the decode-iteration program for ``WorkloadSpec(phase="decode",
    active_slots=…, cache_tokens=…)``, whose occupancy (``AS``) and
    context-load (``CT``) free variables rescore a whole sweep of
    candidate admissions as array ops, and
  * the prefill program, vectorized over prompt length ``S``,

and every refill decision scores `prefill + remaining_tokens ×
marginal-decode-cost` per queued candidate through one GEMV each.  The
``admission="model"`` policy admits the argmin (shortest-predicted-job
first); ``admission="fifo"`` keeps the arrival-order baseline.
``simulate_serving`` runs both policies through a discrete-event replay
of the model's own predictions, so the win is demonstrable without
hardware.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import transformer
from repro.obs import metrics as _obs_metrics
from repro.obs import report as _obs_report
from repro.obs import trace as _obs_trace
from repro.runtime import steps

_ADMISSIONS = _obs_metrics.REGISTRY.counter(
    "repro_admission_decisions_total",
    "admission outcomes at slot refill, by policy and outcome "
    "(admit / slo_defer)")
_SLO_VIOLATIONS = _obs_metrics.REGISTRY.counter(
    "repro_slo_violations_total",
    "measured decode iterations that exceeded the decode-latency SLO")
_DECODE_SECONDS = _obs_metrics.REGISTRY.histogram(
    "repro_decode_step_seconds", "measured decode-iteration wall seconds")


@dataclass
class Request:
    rid: int
    prompt: np.ndarray        # (S,) int32
    max_new: int = 32
    out: List[int] = field(default_factory=list)
    done: bool = False
    # --- supervised-degradation bookkeeping (runtime/supervisor.py) ---
    shed: bool = False                      # dropped to preserve the SLO
    retry_after_s: Optional[float] = None   # stamped when shed
    evictions: int = 0                      # slot evictions survived


class AdmissionScorer:
    """Scores admission candidates through the fused step programs.

    Compiled once per (cfg × slot geometry); after that every call is a
    basis-program GEMV over array environments — microseconds per sweep,
    cheap enough to run inside the serving loop on every refill.

    Single-host serving (no collectives): a cell's seconds are the fused
    step score divided over ``n_dev`` plus the model's per-dispatch
    constant, exactly the ``planspace.scores`` composition with the
    collective term dropped (DP = TP = 1 ⇒ zero collective bytes).
    """

    def __init__(self, cfg: ArchConfig, *, slots: int, max_len: int,
                 model=None, n_dev: int = 1):
        from repro.core import predictor
        from repro.core import properties as props
        from repro.core import workload as wl
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.n_dev = max(int(n_dev), 1)
        self.model = predictor.resolve_model(model)
        self._w1 = 0.0
        for k, w in zip(self.model.keys, self.model.weights):
            if k == props.CONST1:
                self._w1 = float(w)
        # occupancy-refined decode spec: AS/CT become free variables of the
        # compiled program (structure ('decode','ct','as')); the env values
        # set here are placeholders — score calls pin them per candidate
        decode = wl.WorkloadSpec(
            phase="decode", global_batch=slots, seq_len=max_len,
            active_slots=0, cache_tokens=0.0, name="admission_decode")
        self._decode_prog = predictor.step_program(cfg, decode)
        prefill = wl.WorkloadSpec(
            phase="prefill", global_batch=1, seq_len=max_len,
            name="admission_prefill")
        self._prefill_prog = predictor.step_program(cfg, prefill)

    # -- primitives --------------------------------------------------------
    def prefill_seconds(self, prompt_lens) -> np.ndarray:
        """Predicted seconds to prefill one prompt of each given length
        (vectorized over ``S``)."""
        lens = np.asarray(prompt_lens, dtype=np.float64)
        env = {"B": 1.0, "S": lens, "M": 1.0}
        s = np.asarray(self._prefill_prog.score(env, self.model),
                       dtype=np.float64)
        return self._w1 + np.broadcast_to(s, lens.shape) / self.n_dev

    def decode_step_seconds(self, active, cache_tokens) -> np.ndarray:
        """Predicted seconds of one decode iteration at the given slot
        occupancy (``AS``) and total cached context (``CT``) — both may be
        arrays (one entry per candidate admission)."""
        a = np.asarray(active, dtype=np.float64)
        ct = np.asarray(cache_tokens, dtype=np.float64)
        a, ct = np.broadcast_arrays(a, ct)
        env = {"B": float(self.slots), "S": float(self.max_len), "M": 1.0,
               "AS": a, "CT": ct, "SL": 1.0, "MI": 1.0}
        s = np.asarray(self._decode_prog.score(env, self.model),
                       dtype=np.float64)
        return self._w1 + np.broadcast_to(s, a.shape) / self.n_dev

    # -- the admission decision -------------------------------------------
    def admission_scores(self, prompt_lens, remaining_tokens, *,
                         active: int, cache_tokens: float) -> Dict[str, np.ndarray]:
        """Score each queued candidate for the next free slot.

        score_i = prefill(len_i) + remaining_i × Δdecode_i, where Δdecode_i
        is the marginal per-iteration cost of running with this candidate
        resident (occupancy +1, context +min(len_i, window)) over the
        current occupancy — i.e. the predicted serving time this admission
        ADDS.  Argmin is shortest-predicted-job-first.
        """
        lens = np.asarray(prompt_lens, dtype=np.float64)
        rem = np.asarray(remaining_tokens, dtype=np.float64)
        pf = self.prefill_seconds(lens)
        win = self.cfg.sliding_window
        ctx = np.minimum(lens, win) if win is not None else lens
        base = self.decode_step_seconds(active, cache_tokens)
        with_c = self.decode_step_seconds(active + 1, cache_tokens + ctx)
        delta = np.maximum(with_c - base, 0.0)
        return {"prefill_s": pf, "decode_delta_s": delta,
                "score_s": pf + rem * delta}


def _context_cap(cfg: ArchConfig, max_len: int) -> int:
    return min(max_len, cfg.sliding_window) if cfg.sliding_window \
        else max_len


class DecodeServer:
    def __init__(self, cfg: ArchConfig, params, *, slots: int = 4,
                 max_len: int = 512, eos_id: int = 0, seed: int = 0,
                 calibrator=None, admission: str = "fifo", model=None,
                 slo_decode_s: Optional[float] = None, injector=None):
        assert cfg.n_input_codebooks == 1, "codebook serving via examples/"
        if admission not in ("fifo", "model"):
            raise ValueError(f"admission must be 'fifo' or 'model', "
                             f"got {admission!r}")
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.rng = jax.random.PRNGKey(seed)
        self.state = transformer.init_decode_state(cfg, slots, max_len)
        self.active: List[Optional[Request]] = [None] * slots
        self.queue: List[Request] = []
        self.remaining = np.zeros(slots, np.int32)
        self._ctx = np.zeros(slots, np.int64)   # cached tokens per slot
        self.injector = injector                # FaultInjector or None
        self._iters = 0                         # decode iterations served

        self._decode = jax.jit(
            lambda p, s, t: transformer.decode_step(p, cfg, s, t))

        # ---- model-informed admission ----
        self.admission = admission
        self.slo_decode_s = slo_decode_s
        self.scorer: Optional[AdmissionScorer] = None
        if admission == "model" or slo_decode_s is not None:
            self.scorer = AdmissionScorer(cfg, slots=slots, max_len=max_len,
                                          model=model)

        # ---- online calibration: feed per-iteration decode timings ----
        self.calibrator = calibrator
        self._decode_pv = None
        if calibrator is not None:
            from repro.core import predictor
            from repro.core.workload import WorkloadSpec
            from repro.distributed.plan import Plan
            live = WorkloadSpec(phase="decode", global_batch=slots,
                                seq_len=max_len, name="decode_live")
            self._decode_pv = predictor.plan_property_vector(
                cfg, live, Plan(dp_axes=(), tp_axis=None, fsdp=False,
                                sequence_parallel=False), {"data": 1})

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _cache_tokens(self) -> float:
        """Total context tokens the next decode iteration streams — per
        occupied slot, capped at the attention window (``CT``'s meaning)."""
        cap = _context_cap(self.cfg, self.max_len)
        return float(np.minimum(self._ctx, cap)
                     [[r is not None for r in self.active]].sum())

    def _n_active(self) -> int:
        return sum(r is not None for r in self.active)

    def _prefill_slot(self, slot: int, req: Request) -> jnp.ndarray:
        """Feed the prompt token-by-token into this slot's cache rows;
        returns this slot's logits after the last token fed.

        (A production server prefills with one chunked forward; the decode
        loop here is the clear-and-correct path for the CPU example, and
        prefill_step covers the fast path in the dry-run/bench.)"""
        tracer = _obs_trace.get_tracer()
        pred = None
        if tracer.enabled and self.scorer is not None:
            pred = float(self.scorer.prefill_seconds([len(req.prompt)])[0])
        with tracer.span("prefill", predicted_s=pred, rid=req.rid,
                         plen=len(req.prompt), slot=slot):
            # re-admission after a supervisor eviction resumes from the
            # generated prefix: feed prompt + already-produced tokens, owe
            # only the still-missing ones
            for t in list(req.prompt) + list(req.out):
                tok = np.zeros((self.slots, 1), np.int32)
                tok[slot, 0] = t
                logits, self.state = self._decode(
                    self.params, self.state, jnp.asarray(tok))
        self.active[slot] = req
        self.remaining[slot] = req.max_new - len(req.out)
        self._ctx[slot] = len(req.prompt) + len(req.out)
        return logits[slot, -1]

    def evict_slot(self, slot: int) -> Optional[Request]:
        """Evict ``slot``'s request back to the FRONT of the queue (it has
        seniority) — the supervisor's degradation primitive.  The request
        keeps its generated prefix and resumes from it on re-admission."""
        req = self.active[slot]
        if req is None:
            return None
        req.evictions += 1
        self.active[slot] = None
        self.remaining[slot] = 0
        self._ctx[slot] = 0
        self.queue.insert(0, req)
        return req

    def _pick(self) -> Optional[int]:
        """Index into ``self.queue`` of the next request to admit, or None
        to defer admission this iteration (SLO guard)."""
        if self.admission == "fifo" or self.scorer is None:
            if not self.queue:
                return None
            _ADMISSIONS.inc(1, policy="fifo", outcome="admit")
            return 0
        if not self.queue:
            return None
        active, ct = self._n_active(), self._cache_tokens()
        sc = self.scorer.admission_scores(
            [len(r.prompt) for r in self.queue],
            [r.max_new for r in self.queue],
            active=active, cache_tokens=ct)
        i = int(np.argmin(sc["score_s"]))
        if self.slo_decode_s is not None and active > 0:
            cap = _context_cap(self.cfg, self.max_len)
            nxt = self.scorer.decode_step_seconds(
                active + 1, ct + min(len(self.queue[i].prompt), cap))
            if float(nxt) > self.slo_decode_s:
                _ADMISSIONS.inc(1, policy="model", outcome="slo_defer")
                _obs_trace.get_tracer().instant(
                    "slo_defer", rid=self.queue[i].rid,
                    predicted_next_s=float(nxt), slo_s=self.slo_decode_s)
                return None     # admitting would break the decode SLO
        req = self.queue[i]
        _ADMISSIONS.inc(1, policy="model", outcome="admit")
        _obs_report.emit("admit", {
            "rid": req.rid, "plen": len(req.prompt),
            "pred_prefill": f"{sc['prefill_s'][i]*1e3:.3f}ms",
            "decode_delta": f"{sc['decode_delta_s'][i]*1e6:.3f}us",
            "score": f"{sc['score_s'][i]*1e3:.3f}ms", "policy": "model"})
        return i

    def _refill(self) -> None:
        for s in range(self.slots):
            if self.active[s] is None and self.queue:
                i = self._pick()
                if i is None:
                    break
                self._prefill_slot(s, self.queue.pop(i))

    def step(self) -> float:
        """One decode iteration across all occupied slots; returns the
        measured (injector-perturbed, when armed) wall seconds — the
        supervisor's watchdog currency."""
        tok = np.zeros((self.slots, 1), np.int32)
        for s, req in enumerate(self.active):
            if req is not None:
                tok[s, 0] = req.out[-1] if req.out else req.prompt[-1]
        tracer = _obs_trace.get_tracer()
        pred = None
        active = self._n_active()
        if tracer.enabled and self.scorer is not None and active:
            pred = float(self.scorer.decode_step_seconds(
                active, self._cache_tokens()))
        t0 = time.perf_counter()
        with tracer.span("decode_step", predicted_s=pred, active=active):
            logits, self.state = self._decode(self.params, self.state,
                                              jnp.asarray(tok))
            if self.calibrator is not None or tracer.enabled \
                    or self.slo_decode_s is not None:
                jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        if self.injector is not None:
            dt = self.injector.perturb_decode_time(self._iters, dt)
        self._iters += 1
        _DECODE_SECONDS.observe(dt)
        if self.slo_decode_s is not None and active \
                and dt > self.slo_decode_s:
            _SLO_VIOLATIONS.inc()
        if self.calibrator is not None:
            self.calibrator.observe(self._decode_pv, dt, tag="decode",
                                    phase="decode")
        self.rng, sub = jax.random.split(self.rng)
        nxt = np.asarray(jax.random.categorical(
            sub, jnp.asarray(logits[:, -1], jnp.float32), axis=-1))
        for s, req in enumerate(self.active):
            if req is None:
                continue
            t = int(nxt[s])
            req.out.append(t)
            self.remaining[s] -= 1
            self._ctx[s] += 1
            if t == self.eos_id or self.remaining[s] <= 0:
                req.done = True
                self.active[s] = None
                self._ctx[s] = 0
        return dt

    def run(self, max_iters: int = 10_000) -> List[Request]:
        """Serve until queue + slots drain; returns completed requests."""
        done: List[Request] = []
        pending = lambda: self.queue or any(self.active)
        it = 0
        while pending() and it < max_iters:
            self._refill()
            before = [r for r in self.active if r]
            self.step()
            done.extend(r for r in before if r.done)
            it += 1
        return done


# ---------------------------------------------------------------------------
# Discrete-event serving simulation — the admission policies compared under
# the cost model's own physics (no hardware, no weights, deterministic)
# ---------------------------------------------------------------------------


def simulate_serving(cfg: ArchConfig, prompt_lens: Sequence[int],
                     max_new: int = 32, *, slots: int = 4,
                     max_len: int = 512, policy: str = "model",
                     model=None, scorer: Optional[AdmissionScorer] = None,
                     seed: int = 0, noise: float = 0.0
                     ) -> Dict[str, object]:
    """Replay the slot server's schedule with the scorer's predictions as
    the clock: prefills serialize (the example server feeds prompts through
    the decode step), decode iterations cost ``decode_step_seconds`` at the
    instantaneous (occupancy, context) point.  All requests arrive at t=0,
    so a request's completion time IS its latency and the policies differ
    only in admission order — exactly the decision the scorer ranks.

    Returns mean/max latency, makespan and the admission order; run with
    ``policy="model"`` and ``policy="fifo"`` (sharing one ``scorer``) to
    compare.

    ``seed``/``noise`` make perturbed replays deterministic (ISSUE 9
    satellite): with ``noise > 0`` every event duration is scaled by
    ``exp(noise · z)``, z standard normal from ``default_rng(seed)`` —
    same seed, same trajectory, every CI run.  ``noise=0`` (default) is
    the exact predicted-time replay, bit-identical to the pre-seed
    behavior.
    """
    if policy not in ("fifo", "model"):
        raise ValueError(f"policy must be 'fifo' or 'model', got {policy!r}")
    scorer = scorer or AdmissionScorer(cfg, slots=slots, max_len=max_len,
                                       model=model)
    rng = np.random.default_rng(seed)
    jit = (lambda: float(np.exp(noise * rng.standard_normal()))) \
        if noise > 0.0 else (lambda: 1.0)
    cap = _context_cap(cfg, max_len)
    queue = list(range(len(prompt_lens)))          # rids in arrival order
    lens = [int(l) for l in prompt_lens]
    slot_rid = [None] * slots
    slot_rem = np.zeros(slots, np.int64)
    slot_ctx = np.zeros(slots, np.int64)
    t = 0.0
    latency: Dict[int, float] = {}
    order: List[int] = []

    def occupancy():
        act = [s for s in range(slots) if slot_rid[s] is not None]
        return len(act), float(np.minimum(slot_ctx[act], cap).sum())

    while queue or any(r is not None for r in slot_rid):
        for s in range(slots):
            if slot_rid[s] is not None or not queue:
                continue
            if policy == "fifo":
                i = 0
            else:
                active, ct = occupancy()
                sc = scorer.admission_scores(
                    [lens[r] for r in queue], [max_new] * len(queue),
                    active=active, cache_tokens=ct)
                i = int(np.argmin(sc["score_s"]))
            rid = queue.pop(i)
            t += float(scorer.prefill_seconds([lens[rid]])[0]) * jit()
            slot_rid[s], slot_rem[s], slot_ctx[s] = rid, max_new, lens[rid]
            order.append(rid)
        active, ct = occupancy()
        if active == 0:
            break
        t += float(scorer.decode_step_seconds(active, ct)) * jit()
        for s in range(slots):
            if slot_rid[s] is None:
                continue
            slot_rem[s] -= 1
            slot_ctx[s] += 1
            if slot_rem[s] <= 0:
                latency[slot_rid[s]] = t
                slot_rid[s] = None
                slot_ctx[s] = 0

    lat = np.asarray([latency[r] for r in sorted(latency)])
    return {"policy": policy, "order": order,
            "mean_latency_s": float(lat.mean()) if len(lat) else 0.0,
            "max_latency_s": float(lat.max()) if len(lat) else 0.0,
            "makespan_s": t, "n_done": len(lat)}
