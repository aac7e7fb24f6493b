"""Continuous-batching request scheduler with SLO telemetry.

The serving tier's control loop: requests are admitted into the in-flight
decode batch at TOKEN granularity — between any two decode steps a waiting
request can take a free slot (vLLM/Orca-style continuous batching), instead
of waiting for the whole batch to drain (static batching, kept here as the
measured baseline). With nothing in flight its prompt runs through one
bucketed prefill; beside rows that decode it enters in CHUNKS that ride the
decode step: one engine program a step for the decode rows and up to
`engine.chunk_width` tokens of ONE prompt, the oldest with prompt left, the
weights read once for both (`engine.decode_with_chunk`). When the paged KV pool runs
dry, the scheduler PREEMPTS: the youngest running request is evicted, its
pages freed, and it re-queues at the FRONT of the waiting line with its
generated prefix folded into the prompt (recompute-on-resume — the pages
are rebuilt by a fresh prefill when capacity returns).

Per-request SLO latency flows through the PR 1 telemetry registry:
time-to-first-token (arrival -> first prefill logit) and
time-per-output-token (mean decode interval) histograms, plus
admitted/completed/preempted counters and running/waiting gauges. The
clock is injectable so admission/preemption order is testable under a
seeded synthetic arrival trace.

Round 13 (replica fleet): requests carry an optional TTL
(`Request.deadline_s` — expiry frees pool pages immediately,
outcome="expired") and can be client-cancelled (`cancel(rid)`,
outcome="cancelled"); the scheduler drains (`drain()` /
`resume_admission()` — stop admissions, finish in-flight) and evacuates
(`evacuate()` — the preemption-resume path applied to every request at
once) for the fleet's hot-swap and failure-survival protocols
(inference/fleet.py).

Round 17 — prefix sharing + speculative decoding:

- Admission consults the pool's prefix index (`prefix_cache=True`,
  default): a prompt whose leading FULL pages match a resident chain
  shares those pages ref-counted (the last prompt token is always
  recomputed — its logits emit the first generated token) and chunks only
  the suffix, so prefill work drops to O(new suffix) and shared system
  prompts occupy the pool once. Every running request publishes its
  committed full pages back into the index; completion retains them
  (refcount-zero LRU), while preemption/evacuation frees with
  retain=False so a recycled page can never serve a stale chain.
- `spec_decode=SpecDecodeConfig(...)` turns decode steps into
  draft-then-verify: an n-gram self-draft proposer guesses up to
  `draft_len` continuation tokens from the request's own context, and ONE
  engine.extend() call (the multi-query paged-attention program) verifies
  the whole chain — each position's greedy argmax either matches the next
  draft (accept, keep reading) or replaces it (reject; later drafts'
  stale K/V writes sit past seq_len, masked and overwritten, and surplus
  tail pages are rolled back to the pool). Greedy verify emits EXACTLY
  the tokens plain decode would — byte-identical outputs, fewer steps.
  With `spec_decode` on, prompts stream through the same program
  `draft_len + 1` tokens a row a step, and no chunk is planned.
- A model with recurrent layers (the pool then holds a state slot a
  sequence, `pool.has_recurrent_state`) gets neither of the two: prefix
  lookup and registration are skipped and `spec_decode` is refused, because
  each goes BACK over a sequence and would need snapshots of the state. A
  prompt beside decode rows enters in chunks like any other: a chunk moves
  its sequence's state forward only, from what its slot holds. The slot is
  bound with the request's first page and released with it: finish, expiry,
  shed and preemption free the pages, and a preempted request, between two
  chunks too, enters again from position 0, from the zero state.

Round 19 — overload protection & multi-tenant QoS (inference/qos.py):

- Requests carry `tenant` + `priority` (0 = highest class). With a
  `qos=QoSPolicy(...)`, submit() gates through per-tenant token buckets
  and the brownout ladder, dequeue order is strict-priority then
  deficit-round-robin over token debt, and a blocked high-priority head
  may PREEMPT a strictly lower-class running request through the same
  pool-dry preempt-resume machinery (exact-output resume guarantee
  intact). Overload sheds work EXPLICITLY: `outcome="shed"` with a
  `retry_after_s` hint and a reason label on the lifecycle counter —
  bounded waiting line (lowest eligible class loses the slot), queue-wait
  bound, rate limit, deadline-unmeetable (TTL shorter than the provable
  minimum service time at the measured EWMA step latency), and brownout
  step 3. The ladder (spec off -> cap low-priority max_new -> shed lowest
  class) degrades only in output-exact ways: greedy spec-off is
  byte-identical, a capped budget is an exact prefix.

One step ahead: a plain or a chunk step is dispatched BEFORE the last one's
tokens are read. The program chooses each row's token on the device, the next
program takes it from there (`engine.RowToken`), and what the scheduler needs
to plan a step is known without the token's value: who goes on (a row ends by
`max_new_tokens`), each row's next position and page, the step's one chunk.
So `step()` call j dispatches program j + 1, THEN reads program j's ids,
emits and finishes: the device has the next program queued behind the one
that runs, and the host's work of a step runs beside it. A request carries
at most one token not read yet (`Request.unread`). Whatever frees or moves
the pages of a row in flight, or needs a token's value now, reads the step
in flight out first (`sync`): a preemption, a cancellation or an expiry of
a running request, evacuation and adoption, `drain`, and every step under
`spec_decode`; the step after it is dispatched with nothing in flight, as
every step was before. With `eos_id` a row can end in step j when step j + 1
already holds it: that row's extra result is dropped.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry
from ..profiler.utils import RecordEvent, record_span
from ..telemetry import metrics as _metrics
from ..telemetry import request_trace as _rt
from ..telemetry import timeline as _tl
from .kv_cache import PoolExhausted, chain_extend, prefix_chain_keys
from .qos import BROWNOUT_STEPS, QoSPolicy

__all__ = [
    "Request",
    "ContinuousBatchingScheduler",
    "SpecDecodeConfig",
    "StaticBatchingScheduler",
    "replay",
    "percentiles",
]

_TTFT_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
    5.0, 10.0, 30.0, 60.0,
)


def _ttft_hist():
    return _metrics.histogram(
        "paddle_tpu_serving_ttft_seconds",
        "time-to-first-token: request arrival -> first prefill logit",
        buckets=_TTFT_BUCKETS,
    )


def _tpot_hist():
    return _metrics.histogram(
        "paddle_tpu_serving_tpot_seconds",
        "time-per-output-token: mean decode interval per request",
        buckets=_TTFT_BUCKETS,
    )


def _req_counter():
    return _metrics.counter(
        "paddle_tpu_serving_requests_total",
        "request lifecycle events; `reason` distinguishes shed/reject "
        "causes (empty on plain lifecycle transitions)",
        label_names=("event", "reason"),
    )


def _brownout_step_gauge():
    return _metrics.gauge(
        "paddle_tpu_qos_brownout_step",
        "current brownout ladder rung (0 = normal, 3 = shedding lowest class)",
    )


def _brownout_transitions(direction: str, to: str):
    return _metrics.counter(
        "paddle_tpu_qos_brownout_transitions_total",
        "brownout ladder transitions by direction and destination rung",
        label_names=("direction", "to"),
    ).labels(direction=direction, to=to)


def _queue_gauge(state: str):
    return _metrics.gauge(
        "paddle_tpu_serving_queue",
        "scheduler occupancy by state",
        label_names=("state",),
    ).labels(state=state)


def _spec_counter(event: str):
    return _metrics.counter(
        "paddle_tpu_spec_decode_tokens_total",
        "speculative-decode tokens by event (drafted = proposed by the "
        "n-gram self-draft, accepted = verified equal to the greedy chain)",
        label_names=("event",),
    ).labels(event=event)


@dataclass
class SpecDecodeConfig:
    """Speculative decoding knobs: `draft_len` tokens are proposed per
    decode step by an n-gram self-draft (the most recent earlier occurrence
    of the context's final `ngram` tokens proposes its continuation — the
    zero-extra-model proposer that exploits the repetition heavy serving
    traffic actually has) and verified in one engine.extend() call."""

    draft_len: int = 3
    ngram: int = 2

    def __post_init__(self):
        if self.draft_len < 1:
            raise ValueError("SpecDecodeConfig.draft_len must be >= 1")
        if self.ngram < 1:
            raise ValueError("SpecDecodeConfig.ngram must be >= 1")


@dataclass(eq=False)
class Request:
    """One generation request. `prompt` is token ids; the scheduler fills
    the runtime fields. A request is itself and no other (`eq=False`): the
    step asks `req in self.running` several times a row, and compared by
    value every miss built two tuples of all fields — 16,000 of them, 22 ms
    of host time, a step over 128 rows."""

    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    arrival_time: float = 0.0
    # per-request TTL in scheduler-clock seconds from submit(); an expired
    # request frees its pool pages IMMEDIATELY instead of pinning them for
    # a client that will never read the answer (outcome="expired")
    deadline_s: Optional[float] = None
    # fleet session-affinity key: follow-on requests of one conversation
    # carry the same session so the router sends them to the replica that
    # (may) hold their warm KV pages; None = no affinity
    session: Optional[object] = None
    # QoS identity: tenant keys the token bucket + fair-share debt;
    # priority is the preemption/shed class (0 = highest — a P0 may evict
    # a strictly larger-priority victim's pages, brownout acts on
    # priorities >= the configured low class)
    tenant: str = "default"
    priority: int = 1

    # runtime (scheduler-owned)
    generated: List[int] = field(default_factory=list)
    pages: List[int] = field(default_factory=list)
    preemptions: int = 0
    # terminal disposition: "completed" | "expired" | "cancelled" |
    # "shed" (None while in flight); the fleet also reads it for
    # zero-loss accounting. A shed request carries the retry hint.
    outcome: Optional[str] = None
    # a shed request carries WHY (one of qos.SHED_REASONS) and when to
    # retry — the client-facing half of the explicit-backpressure contract
    shed_reason: Optional[str] = None
    retry_after_s: Optional[float] = None
    # brownout step 2 bookkeeping: the pre-cap generation budget (None =
    # never capped) — recovery tests pin that a capped survivor's output
    # is an exact prefix of its uncapped greedy chain
    qos_orig_max_new: Optional[int] = None
    # absolute clock at submit() — arrival_time is a REPLAY-relative offset
    # and must never be differenced against absolute timestamps
    submitted_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    # (scheduler clock, admission mode, cached tokens) of the FIRST time
    # the request held a decode slot: where its `request.queue` span ends
    # and its `request.prompt` span starts
    slot: Optional[tuple] = None
    token_times: List[float] = field(default_factory=list)
    # chunked or streamed admission: prompt tokens already written to the
    # cache (cursor == len(prompt) once the request is generating), and the
    # steps that carried a chunk of this prompt
    cursor: int = 0
    chunks: int = 0
    # tokens of this request that a dispatched step computes and the host has
    # not read yet (0 or 1 between two calls of `step`): they count as context
    unread: int = 0
    # recompute-on-resume: prompt tokens re-prefilled after a preemption
    # include the already-generated prefix; `_prompt_len` keeps the original
    _prompt_len: Optional[int] = None
    # prefix cache: prompt tokens served from shared pages instead of
    # recomputed (cumulative across resumes); speculative decoding: tokens
    # proposed by the draft / verified equal to the greedy chain
    cached_tokens: int = 0
    drafted: int = 0
    accepted: int = 0
    # committed full pages already published into the prefix index, and
    # the chain digest AFTER them (== the last registered page's key) so
    # each new page's key costs O(block_size), not O(context)
    _registered_pages: int = 0
    _chain_digest: bytes = b""
    # request-scoped trace handle (telemetry.request_trace) — None unless
    # FLAGS_request_trace sampled this request; travels WITH the request
    # across preemption/evacuation/re-dispatch so the phase chain stays
    # unbroken end to end
    trace: Optional[object] = field(default=None, repr=False, compare=False)

    @property
    def prompt_len(self) -> int:
        return self._prompt_len if self._prompt_len is not None else len(self.prompt)

    @property
    def context_len(self) -> int:
        return len(self.prompt) + len(self.generated) + self.unread

    @property
    def done(self) -> bool:
        return self.finish_time is not None

    def ttft(self) -> Optional[float]:
        """submit -> first token, scheduler-clock seconds (replay computes
        its arrival-inclusive TTFT itself — arrival_time is an offset on a
        different time base)."""
        if self.first_token_time is None or self.submitted_time is None:
            return None
        return self.first_token_time - self.submitted_time

    def tpot(self) -> Optional[float]:
        """Mean decode interval; None until a second token exists."""
        if len(self.token_times) < 2:
            return None
        return (self.token_times[-1] - self.token_times[0]) / (len(self.token_times) - 1)


class _Abandon(Exception):
    """Raised inside the plan of a step AHEAD by what cannot happen beside a
    step in flight (`sync`): the plan is given up, the step in flight is read
    out, and the same call plans again with nothing in flight."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


@dataclass(eq=False)
class _Flight:
    """A dispatched step whose ids the host has not read: the engine's result,
    the requests it carries (rows and chunk), those among them whose token it
    computes, each with its `RowToken` (the step after takes it from there,
    the read-out reads it), and how it was dispatched (`ahead` or `sync`, for
    the span of the call that reads it)."""

    result: object
    held: List[Request]
    emits: Dict[int, Tuple[Request, object]]
    how: Dict[str, object]


class ContinuousBatchingScheduler:
    """Token-level admission into the in-flight decode batch.

    step() = [complete finished] -> [admit waiting while slots + pages
    allow] -> [grow running sequences' page allocation, preempting when the
    pool is dry] -> [one engine step: a token for everyone who decodes and,
    beside them, a chunk of the oldest prompt still to come]; planned and
    dispatched one step ahead of the tokens it reads (the module docstring).
    """

    def __init__(self, engine, *, max_running: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 prefix_cache: bool = True,
                 spec_decode: Optional[SpecDecodeConfig] = None,
                 qos: Optional[QoSPolicy] = None,
                 admission_mode: str = "auto"):
        self.engine = engine
        self.max_running = int(max_running or engine.max_batch)
        if self.max_running > engine.max_batch:
            raise ValueError("max_running exceeds the engine's decode capacity")
        self.eos_id = eos_id
        self.clock = clock
        # a recurrent layer's state holds a sequence's whole prefix in one
        # value: no prefix of a prompt can be skipped because its pages are
        # resident, and no draft chain can be verified and rolled back, until
        # the pool keeps state snapshots — the scheduler acts on what the
        # engine's pool holds
        recurrent = engine.pool.has_recurrent_state
        if recurrent and spec_decode is not None:
            raise ValueError(
                "spec_decode: the engine's pool holds recurrent-layer state; verifying a draft "
                "chain needs engine.extend and a rollback of the state, which need snapshots")
        self.prefix_cache = bool(prefix_cache) and not recurrent
        self.spec = spec_decode
        # "auto" (default): idle-scheduler admissions run a bucketed prefill
        # program, busy ones enter in chunks beside the decode rows (or
        # stream, where no chunk can be planned). "streamed": NEVER bucketed —
        # the disaggregated fleet's decode tier runs this, so it takes prompts
        # in (tier-degradation intake) without ever compiling a prefill
        # bucket, keeping its compile family to the decode steps' programs
        if admission_mode not in ("auto", "streamed"):
            raise ValueError(
                f"admission_mode {admission_mode!r} is not 'auto' or 'streamed'")
        self.admission_mode = admission_mode
        # shared across a fleet's replicas: buckets/debt/ladder are
        # fleet-wide state, the scheduler only consults it
        self.qos = qos
        self.waiting: List[Request] = []
        self.running: List[Request] = []
        self.finished: List[Request] = []
        self.preempted_total = 0
        self.shed_total = 0
        # measured per-step latency (same 0.8/0.2 blend the fleet router
        # drains by) — the deadline-shed and retry-after estimates
        self.ewma_step_s: Optional[float] = None
        # drain mode (fleet hot-swap protocol): admissions stop, in-flight
        # work keeps decoding to completion, submit() still accepts (the
        # caller is expected to route elsewhere; anything queued here just
        # waits out the drain)
        self.draining = False
        self._entered = {"prompt_tokens": 0, "chunk_tokens": 0}  # of the steps this call dispatched
        self._flight: Optional[_Flight] = None  # the step dispatched and not read
        self._planning = False    # inside the plan of a step ahead
        self._why: Optional[str] = None  # what the last read-out was for: the next step's `sync`
        self._carried = 0         # tokens a read-out outside `step` emitted: the next call returns them
        self._ran: Dict[str, object] = {}  # of the program this call read: ahead or sync, rows_dropped

    # ---- queue surface ----
    def drain(self) -> None:
        """Stop admitting new work into decode slots (in-flight requests
        run to completion). The fleet swap protocol: drain -> swap weights
        -> resume_admission."""
        self.sync("drain")
        self.draining = True

    def sync(self, reason: str) -> None:
        """Read the step in flight out (its tokens emitted, its ended
        requests finished; the next `step` returns their count), before
        something that frees or moves the pages of a row in flight or needs
        a token's value now. Nothing in flight: nothing to do. Inside the plan
        of a step ahead the plan is given up instead (`_Abandon`)."""
        if self._flight is None:
            return
        if self._planning:
            raise _Abandon(reason)  # `_step_inner` reads the step out, then plans again
        with RecordEvent("sched.sync", args={"reason": reason}):
            self._carried += self._read_out()
            self._why = reason

    def resume_admission(self) -> None:
        self.draining = False

    def submit(self, req: Request) -> None:
        max_ctx = self.engine.max_seq_len
        # prompt_len, not len(prompt): a preempted/evacuated request folds
        # its generated prefix into the prompt, but its FINAL context is
        # still original-prompt + max_new (re-validating the folded length
        # would reject a legal request mid-recovery)
        total = req.prompt_len + req.max_new_tokens
        if total > max_ctx:
            self._count_reject("context_overflow")
            raise ValueError(
                f"request {req.rid}: prompt_len {req.prompt_len} + "
                f"max_new_tokens {req.max_new_tokens} = {total} "
                f"exceeds max_seq_len {max_ctx}"
            )
        pool = self.engine.pool
        if pool.blocks_for_tokens(total) > pool.num_blocks - 1:
            # would deadlock at its final preemption-resume: even an empty
            # pool could never hold the full context
            self._count_reject("pool_capacity")
            raise ValueError(
                f"request {req.rid}: full context of {total} tokens "
                f"(prompt_len {req.prompt_len} + max_new_tokens "
                f"{req.max_new_tokens}) needs {pool.blocks_for_tokens(total)} "
                f"pages; the pool has {pool.num_blocks - 1} usable "
                f"(num_blocks {pool.num_blocks} minus the reserved page)"
            )
        # preserved across re-dispatch (like _prompt_len): a request
        # evacuated off a dead replica keeps its ORIGINAL submit clock, so
        # its TTL and client-perceived TTFT never silently restart
        if req.submitted_time is None:
            req.submitted_time = self.clock()
        if self.qos is not None and self._qos_submit_gate(req):
            return  # shed: terminal, counted, retryable
        self.waiting.append(req)
        if req.trace is None:
            req.trace = _rt.start(
                req.rid, req.submitted_time,
                prompt_len=req.prompt_len, max_new=req.max_new_tokens,
            )
            if req.trace is not None:
                req.trace.phase("queue", self.clock())
        elif req.trace.phase_name != "preempt":
            # re-dispatch of an already-traced request (fleet migration off
            # a draining replica): it queues again; an open "preempt" span
            # (evacuation/preemption) instead runs until re-admission
            req.trace.phase("queue", self.clock(), cause="requeue")
        if telemetry.enabled():
            _req_counter().labels(event="submitted", reason="").inc()
            self._sync_gauges()

    @staticmethod
    def _count_reject(reason: str) -> None:
        """Validation rejections (the ValueError paths) get the same
        reason-labeled visibility as sheds — a dashboard must be able to
        tell WHY requests bounce, not just that they did."""
        if telemetry.enabled():
            _req_counter().labels(event="rejected", reason=reason).inc()

    def _qos_submit_gate(self, req: Request) -> bool:
        """Admission-time QoS gates in cheapest-first order; returns True
        when the request was shed (terminal — caller must not queue it)."""
        qos = self.qos
        now = self.clock()
        # brownout step 3: new lowest-class work is refused while the
        # ladder is at the top rung; retry after the recovery cooldown
        if qos.brownout.sheds(req.priority):
            self._shed_submit(req, now, "brownout",
                              retry_after=qos.brownout.cfg.cooldown_s)
            return True
        ok, retry = qos.rate_gate(req, now)
        if not ok:
            self._shed_submit(req, now, "rate_limit", retry_after=retry)
            return True
        emit_bound = (self.spec.draft_len + 1) if self.spec is not None else 1
        if qos.deadline_unmeetable(req, self.ewma_step_s, emit_bound):
            # no retry hint: a TTL the engine provably cannot meet will
            # not be meetable a bucket-refill later either
            self._shed_submit(req, now, "deadline_unmeetable")
            return True
        if qos.queue_full(len(self.waiting)):
            victim = qos.queue_full_victim(self.waiting, req)
            retry = (round(self.ewma_step_s * max(1, len(self.waiting)), 6)
                     if self.ewma_step_s else None)
            if victim is req:
                self._shed_submit(req, now, "queue_full", retry_after=retry)
                return True
            # the newcomer strictly outranks the lowest queued class:
            # the victim sheds, the newcomer takes its slot
            self.waiting.remove(victim)
            self._shed(victim, now, "queue_full", retry_after=retry)
        return False

    def _shed_submit(self, req: Request, now: float, reason: str,
                     retry_after: Optional[float] = None) -> None:
        """Shed at the submit boundary: the request still counts as
        submitted (offered load) and gets a trace so the span chain
        contract holds for EVERY terminal path."""
        if req.trace is None:
            req.trace = _rt.start(
                req.rid, req.submitted_time,
                prompt_len=req.prompt_len, max_new=req.max_new_tokens,
            )
            if req.trace is not None:
                req.trace.phase("queue", now)
        if telemetry.enabled():
            _req_counter().labels(event="submitted", reason="").inc()
        self._shed(req, now, reason, retry_after=retry_after)

    def _shed(self, req: Request, now: float, reason: str,
              retry_after: Optional[float] = None) -> None:
        """Terminal overload rejection: explicit, counted, retryable.
        Waiting/new requests hold no pages, so _finish's free is a no-op;
        the request lands in `finished` with outcome="shed" (zero-loss
        fleet accounting sees it like any other terminal outcome)."""
        req.outcome = "shed"
        req.shed_reason = reason
        req.retry_after_s = retry_after
        self.shed_total += 1
        _tl.emit("qos", "shed", severity="warn", rid=req.rid, reason=reason,
                 priority=req.priority, retry_after_s=retry_after)
        if self.qos is not None:
            self.qos.note_shed(reason)
        self._finish(req, now, reason=reason)
        if telemetry.enabled():
            self._sync_gauges()

    def idle(self) -> bool:
        return not self.waiting and not self.running

    def _sync_gauges(self) -> None:
        _queue_gauge("running").set(len(self.running))
        _queue_gauge("waiting").set(len(self.waiting))

    # ---- lifecycle ----
    def _finish(self, req: Request, now: float, reason: str = "") -> None:
        req.finish_time = now
        req.outcome = req.outcome or "completed"
        # retain=True: a finished request's registered (committed, full)
        # pages stay resident at refcount zero, LRU-evictable — the warm
        # prefix cache a follow-on request with the same system prompt hits
        self.engine.pool.free(req.pages, owner=req.rid, retain=True)
        req.pages = []
        self.finished.append(req)
        if req.trace is not None:
            extra = {"reason": reason} if reason else {}
            if req.retry_after_s is not None:
                extra["retry_after_s"] = req.retry_after_s
            req.trace.close(
                now, req.outcome,
                generated=(len(req.prompt) - req.prompt_len) + len(req.generated),
                preemptions=req.preemptions,
                cached_tokens=req.cached_tokens,
                drafted=req.drafted,
                accepted=req.accepted,
                **extra,
            )
        if telemetry.enabled():
            _req_counter().labels(event=req.outcome, reason=reason).inc()
            tpot = req.tpot()
            if tpot is not None:
                _tpot_hist().observe(tpot)
        # every terminal disposition lands on the incident timeline: the
        # completed ones are the denominator, the shed/expired/cancelled
        # ones are what an SLO-burn triage window needs to see
        _tl.emit("scheduler", "request.finish",
                 severity="info" if req.outcome == "completed" else "warn",
                 rid=req.rid, outcome=req.outcome, reason=reason,
                 generated=len(req.generated), preemptions=req.preemptions)

    def cancel(self, rid: int) -> bool:
        """Client-side cancellation: drop the request wherever it is and
        free its pages IMMEDIATELY (a stuck/gone client must not pin pool
        pages for the rest of the process). Returns False when `rid` is not
        in flight (already finished or never submitted)."""
        if any(r.rid == rid for r in self.running):
            self.sync("cancel")  # its row may be in the step in flight
        for queue in (self.waiting, self.running):
            for req in queue:
                if req.rid == rid:
                    queue.remove(req)
                    req.outcome = "cancelled"
                    self._finish(req, self.clock())
                    if telemetry.enabled():
                        self._sync_gauges()
                    return True
        return False

    def _expire_due(self, now: float) -> None:
        """Per-request TTL: requests past their deadline_s (scheduler-clock
        seconds since submit) finish with outcome="expired" and free their
        pages right now — the serving-tier analogue of a dead client."""
        def sweep():
            return [
                (queue, req)
                for queue in (self.waiting, self.running) for req in queue
                if (
                    req.deadline_s is not None
                    and req.submitted_time is not None
                    and now - req.submitted_time > req.deadline_s
                )
            ]

        due = sweep()
        if not due:
            return  # a sweep that finds nothing is no phase of the step
        if self._flight is not None and any(queue is self.running for queue, _ in due):
            self.sync("expire")
            due = sweep()  # the read-out may have finished one of them
        with RecordEvent("sched.expire", args={"expired": len(due)}):
            for queue, req in due:
                queue.remove(req)
                req.outcome = "expired"
                self._finish(req, now)

    def _reset_for_resume(self, req: Request) -> Request:
        """Recompute-on-resume bookkeeping shared by preemption and fleet
        evacuation: generated tokens fold into the prompt (their K/V is
        rebuilt by a fresh prefill/stream on whatever engine resumes the
        request) and the streaming cursor rewinds. Pages must already be
        freed by the caller."""
        if req._prompt_len is None:
            req._prompt_len = len(req.prompt)
        req.prompt = req.prompt + req.generated
        req.generated = []
        req.cursor = 0
        req.unread = 0
        req._registered_pages = 0
        req._chain_digest = b""
        return req

    def _preempt_one(self, cause: str = "pool_dry",
                     below_priority: Optional[int] = None) -> bool:
        """Evict the lowest-class request with the least sunk work
        (priority descending, then still-streaming first, then youngest)
        back to the front of the waiting queue, recompute-on-resume.
        `below_priority` restricts victims to strictly lower classes —
        the QoS priority-preemption path; equal-priority traffic (the
        default) keeps the original pool-dry victim order exactly."""
        def candidates():
            return ([r for r in self.running if r.priority > below_priority]
                    if below_priority is not None else self.running)

        if not candidates():
            return False
        self.sync("preempt")  # the victim's row may be in the step in flight
        if not candidates():
            return False
        victim = max(
            candidates(),
            key=lambda r: (r.priority, r.first_token_time is None,
                           r.first_token_time or 0.0, r.rid),
        )
        self.running.remove(victim)
        # retain=False: an evicted context is conceptually discarded — its
        # refcount-zero pages go straight back to the free list and their
        # index entries drop, so a preemption-freed page can NEVER serve a
        # later prefix hit after being overwritten by a new owner
        self.engine.pool.free(victim.pages, owner=victim.rid, retain=False)
        victim.pages = []
        self._reset_for_resume(victim)
        victim.preemptions += 1
        self.preempted_total += 1
        self.waiting.insert(0, victim)
        if victim.trace is not None:
            # the preempt span runs until re-admission (recompute resumes)
            victim.trace.phase("preempt", self.clock(), cause=cause)
        if telemetry.enabled():
            _req_counter().labels(
                event="preempted",
                reason="" if cause == "pool_dry" else cause,
            ).inc()
        _tl.emit("scheduler", "preempt", severity="warn", rid=victim.rid,
                 cause=cause, preemptions=victim.preemptions)
        return True

    def evacuate(self) -> List[Request]:
        """Pull EVERY in-flight and queued request out of this scheduler,
        reset for recompute-on-resume (the preemption path generalized to
        the whole replica), and return them in resume order (running
        first — they have the most sunk work — then waiting). The fleet
        calls this when a replica's circuit breaker opens: the requests are
        re-submitted to a healthy replica and their K/V pages are rebuilt
        from the folded prompt there."""
        try:
            self.sync("handoff")
        except Exception:  # noqa: BLE001 — a replica that died with a step in flight
            # the tokens of that step are computed again where the requests resume
            self._flight = None
        evacuated: List[Request] = []
        now = self.clock()
        for req in self.running:
            # same retain=False contract as preemption (the PR 11 path):
            # evacuated pages leave the index before they can be recycled
            self.engine.pool.free(req.pages, owner=req.rid, retain=False)
            req.pages = []
            evacuated.append(self._reset_for_resume(req))
        # waiting requests hold no pages; a preemption-requeued one is
        # already in resume form
        evacuated.extend(self.waiting)
        for req in evacuated:
            if req.trace is not None:
                # cause-labeled: distinguishable from pool_dry preemption
                req.trace.phase("preempt", now, cause="evacuation")
        self.running = []
        self.waiting = []
        if telemetry.enabled():
            self._sync_gauges()
        return evacuated

    def adopt_running(self, req: Request) -> None:
        """Attach an in-flight request whose KV pages are ALREADY resident
        in this scheduler's pool (the fleet's prefill->decode KV migration):
        no re-validation, no clock re-stamping — the request keeps decoding
        exactly where it left off. The caller owns the page handoff (pages
        allocated here, CRC-verified) and the prefix-registration reset so
        this pool republishes the chain itself."""
        self.sync("handoff")
        if len(self.running) >= self.max_running:
            raise RuntimeError(
                f"adopt_running: no free decode slot for request {req.rid}")
        self.running.append(req)
        if telemetry.enabled():
            self._sync_gauges()

    def _emit_token(self, req: Request, logits: np.ndarray, now: float) -> None:
        """A token chosen on the host, where the logits are here: a bucketed
        prefill's, a verify step's."""
        self._emit(req, int(np.argmax(logits)), now)

    def _emit(self, req: Request, token: int, now: float) -> None:
        req.generated.append(token)
        req.token_times.append(now)
        # every emitted token belongs to the decode phase — keyed on the
        # trace's own phase, not first_token_time, because a mid-decode
        # preemption re-opens a prefill span on resume (first_token_time
        # stays set) and the post-resume tokens must flip back to decode
        if req.trace is not None and req.trace.phase_name != "decode":
            req.trace.phase("decode", now)
        if req.first_token_time is None:
            req.first_token_time = now
            if req.slot is not None:
                t_slot, mode, cached = req.slot
                record_span("request.prompt", t_slot, now, ident=req.rid,
                            args={"mode": mode, "prompt_len": req.prompt_len,
                                  "cached": cached, "chunks": req.chunks})
            if telemetry.enabled() and req.submitted_time is not None:
                # both timestamps from the scheduler clock: queue wait
                # inside the scheduler is included, replay-offset arrival
                # bookkeeping is not (it lives on a different time base)
                _ttft_hist().observe(max(0.0, now - req.submitted_time))
        total_generated = (len(req.prompt) - req.prompt_len) + len(req.generated)
        if total_generated >= req.max_new_tokens or (
            self.eos_id is not None and token == self.eos_id
        ):
            self._finish(req, now)

    @staticmethod
    def _tokens_needed(req: Request) -> int:
        """Cache slots the next one-token write for `req` must be covered
        for: streaming writes prompt[cursor] at position cursor; generation
        writes generated[-1] at position context_len - 1."""
        if req.cursor < len(req.prompt):
            return req.cursor + 1
        return req.context_len

    def _chunk_width(self) -> int:
        """Prompt tokens a step may carry beside its decode rows; 0 where a
        prompt streams instead: speculative decoding is on (its plans stream
        the prompt through `engine.extend`)."""
        return 0 if self.spec is not None else getattr(self.engine, "chunk_width", 0)

    def _chunkable(self, req: Request) -> bool:
        """Whether `req`'s next prompt tokens can enter as a chunk: it has
        prompt left, on a page's edge (admission starts it on one and a chunk
        is whole pages; only a step under `spec_decode` leaves it elsewhere,
        and then it streams a token a step up to the next edge)."""
        return (self._chunk_width() > 0 and req.cursor < len(req.prompt)
                and req.cursor % self.engine.pool.block_size == 0)

    def _try_admit(self) -> Optional[int]:
        """Admit the oldest waiting request into a free decode slot;
        returns the number of tokens emitted by the admission (1 for a
        bucketed prefill, 0 otherwise), or None when blocked.

        Two admission paths (the continuous-batching TPOT trade): with
        NOTHING in flight there is no one to stall, so the prompt runs
        through a bucketed prefill program in one shot (TTFT-optimal).
        (A prompt longer than the engine's largest prefill bucket has no
        such program and enters by the second path.)
        With decode in flight, a monolithic prefill between two decode
        steps would stretch every in-flight request's inter-token interval
        — instead the request takes a slot with its prompt still to come
        ("chunked"), and the steps that follow carry it in beside the decode
        rows, up to `engine.chunk_width` tokens of ONE prompt a step, oldest
        first (`_step_inner`); meanwhile it holds no decode row, and it
        emits its first token in the step that carries its last chunk.
        Where no chunk can be planned (`_chunk_width()` 0: `spec_decode`)
        the prompt is "streamed" through the request's own row, `draft_len +
        1` tokens a step.

        Round 17: admission consults the prefix index first. A hit shares
        the resident pages (refcounted) and NEVER takes the bucketed prefill
        (which writes every prompt position, and must not touch shared
        pages) — only the un-cached suffix enters, from the page's edge
        where the shared pages end. The last prompt token is never served
        from cache: its logits emit the first generated token, so at least
        one position always recomputes.
        """
        if self.draining or not self.waiting or len(self.running) >= self.max_running:
            return None
        # QoS dequeue order: strict priority, then deficit-round-robin
        # over token debt (single-tenant equal-priority traffic selects
        # index 0 — the pre-QoS FIFO, preemption-requeue order included)
        idx = self.qos.select(self.waiting) if self.qos is not None else 0
        req = self.waiting[idx]
        pool = self.engine.pool
        shared: List[int] = []
        if self.prefix_cache and req.cursor == 0:
            n_shareable = (len(req.prompt) - 1) // pool.block_size
            if n_shareable > 0:
                keys = prefix_chain_keys(req.prompt, pool.block_size)[:n_shareable]
                shared = pool.acquire_prefix(keys, owner=req.rid)
        if (not self.running and not shared and self.admission_mode == "auto"
                and len(req.prompt) <= max(getattr(self.engine, "prefill_buckets", None) or (len(req.prompt),))):
            need = pool.blocks_for_tokens(len(req.prompt) + 1)
            if need <= pool.available():
                self.waiting.pop(idx)
                self._qos_on_admit(req)
                req.pages = pool.alloc(need, owner=req.rid)
                self._note_slot(req, "bucketed")
                if req.trace is not None:
                    self._trace_admit(req, mode="bucketed")
                logits = self.engine.prefill(req.prompt, req.pages)
                req.cursor = len(req.prompt)
                self._entered["prompt_tokens"] += len(req.prompt)
                if telemetry.enabled():
                    _req_counter().labels(event="admitted", reason="").inc()
                self._emit_token(req, logits, self.clock())
                if not req.done:
                    self.running.append(req)
                self._register_committed(req)
                return 1
            # bucketed allocation doesn't fit: fall through and let the
            # prompt enter page by page instead (the pool-constrained path)
        # chunked or streamed admission: one fresh page holds the first
        # uncached write, the steps grow the rest
        if pool.available() < 1:
            if shared:
                # admission blocked after the lookup took refs — hand them
                # back (retained, still indexed) so nothing leaks
                pool.free(shared, owner=req.rid, retain=True)
            return None
        self.waiting.pop(idx)
        self._qos_on_admit(req)
        cached = len(shared) * pool.block_size
        req.pages = list(shared) + pool.alloc(1, owner=req.rid)
        req.cursor = cached
        req.cached_tokens += cached
        # shared pages are already indexed; the chain digest resumes from
        # the last hit page's key (keys ARE the chain digests)
        req._registered_pages = len(shared)
        req._chain_digest = keys[len(shared) - 1] if shared else b""
        self.running.append(req)
        mode = "chunked" if self._chunk_width() else "streamed"
        self._note_slot(req, mode, cached)
        if req.trace is not None:
            self._trace_admit(req, mode=mode, cached=cached)
        if telemetry.enabled():
            _req_counter().labels(event="admitted", reason="").inc()
        return 0

    def _note_slot(self, req: Request, mode: str, cached: int = 0) -> None:
        """The request holds a decode slot from now. On its first admission
        its wait in the queue ends: one `request.queue` span, submit to
        here, on the scheduler's clock (`perf_counter` in a server, so the
        span lies beside the others of the ring)."""
        if req.slot is not None:
            return
        now = self.clock()
        req.slot = (now, mode, cached)
        if req.submitted_time is not None:
            record_span("request.queue", req.submitted_time, now, ident=req.rid)

    def _trace_admit(self, req: Request, mode: str, cached: int = 0) -> None:
        """Open the prefill span; `recompute_tokens` counts the generated
        prefix folded into the prompt by preemption/evacuation — the K/V
        this prefill rebuilds rather than computes for the first time —
        and `cached_tokens` the prompt tokens served from shared prefix
        pages (never recomputed at all)."""
        req.trace.phase(
            "prefill", self.clock(), mode=mode,
            recompute_tokens=len(req.prompt) - req.prompt_len,
            cached_tokens=cached,
        )

    def _qos_on_admit(self, req: Request) -> None:
        """Dequeue accounting + brownout step-2 budget cap. The cap is an
        exact PREFIX of the uncapped greedy chain (greedy decode is
        deterministic), and recovery keeps the original budget in
        `qos_orig_max_new` so tests can pin prefix-exactness."""
        if self.qos is None:
            return
        self.qos.charge(req)
        cap = self.qos.brownout.max_new_cap(req.priority)
        if cap is not None and req.max_new_tokens > cap:
            # never cap below what a resume has already folded/generated
            # (+1 so the request still terminates on its next token)
            already = (len(req.prompt) - req.prompt_len) + len(req.generated)
            budget = max(cap, already + 1)
            if budget < req.max_new_tokens:
                if req.qos_orig_max_new is None:
                    req.qos_orig_max_new = req.max_new_tokens
                req.max_new_tokens = budget
                if req.trace is not None:
                    req.trace.event("qos_max_new_capped", self.clock(),
                                    cap=budget, orig=req.qos_orig_max_new)

    def _qos_priority_preempt(self) -> bool:
        """A blocked high-priority head may evict ONE strictly
        lower-class running request through the pool-dry preempt-resume
        machinery (the victim resumes later with the exact-output
        guarantee). Returns True when a victim was evicted — the caller
        retries admission."""
        if (self.qos is None or self.draining or not self.waiting
                or not self.running):
            return False
        head = self.waiting[self.qos.select(self.waiting)]
        return self._preempt_one(cause="priority",
                                 below_priority=head.priority)

    # ---- prefix-index registration ----
    def _kv_committed(self, req: Request) -> int:
        """Cache positions holding FINAL K/V: a streaming request has
        written [0, cursor); a generating one everything except the newest
        token (whose K/V lands when it is fed back in)."""
        if req.cursor < len(req.prompt):
            return req.cursor
        return req.context_len - 1

    def _register_committed(self, req: Request) -> None:
        """Publish the request's committed FULL pages into the prefix
        index (idempotent; shared pages are already registered). Draft
        positions are never committed, so a speculatively-written page can
        only register after its tokens are verified."""
        if not self.prefix_cache or not req.pages:
            return
        pool = self.engine.pool
        bs = pool.block_size
        full = self._kv_committed(req) // bs
        if full <= req._registered_pages:
            return
        tokens = req.prompt + req.generated
        h = req._chain_digest
        for i in range(req._registered_pages, full):
            h = chain_extend(h, tokens[i * bs:(i + 1) * bs])
            pool.register_prefix(h, req.pages[i])
        req._chain_digest = h
        req._registered_pages = full

    # ---- speculative decoding ----
    def _propose_ngram(self, req: Request, k: int) -> List[int]:
        """n-gram self-draft: the most recent earlier occurrence of the
        context's final `ngram` tokens proposes the k tokens that followed
        it. Zero extra model weights; exact greedy verify makes a bad guess
        cost only wasted FLOPs, never a wrong token."""
        n = self.spec.ngram
        seq = req.prompt + req.generated
        if k <= 0 or len(seq) <= n:
            return []
        tail = seq[-n:]
        for i in range(len(seq) - n - 1, -1, -1):
            if seq[i:i + n] == tail:
                return list(seq[i + n:i + n + k])
        return []

    def _plan_row(self, req: Request) -> Tuple[str, List[int], List[int]]:
        """One request's extend-row plan: (kind, tokens, positions).
        Streaming rows chunk up to Q prompt tokens per step (chunked
        prefill at chunk granularity); generating rows carry the committed
        last token plus up to draft_len n-gram drafts to verify."""
        Q = self.spec.draft_len + 1
        if req.cursor < len(req.prompt):
            take = min(Q, len(req.prompt) - req.cursor)
            toks = list(req.prompt[req.cursor:req.cursor + take])
            poss = list(range(req.cursor, req.cursor + take))
            return "stream", toks, poss
        ctx = req.context_len
        total_gen = (len(req.prompt) - req.prompt_len) + len(req.generated)
        rem = req.max_new_tokens - total_gen
        # a chain of d drafts can emit d+1 tokens and writes K/V through
        # position ctx-1+d — cap by the generation budget AND the table
        budget = min(self.spec.draft_len, rem - 1,
                     self.engine.max_seq_len - ctx)
        drafts = self._propose_ngram(req, budget) if budget > 0 else []
        if drafts:
            req.drafted += len(drafts)
            if telemetry.enabled():
                _spec_counter("drafted").inc(len(drafts))
        toks = [req.generated[-1]] + drafts
        poss = list(range(ctx - 1, ctx - 1 + len(toks)))
        return "draft", toks, poss

    def _spec_decode_step(self, alive: List[Request], plans: Dict) -> int:
        """One verify/extend tick: every alive row's plan runs through a
        single engine.extend() call; draft rows commit their greedy-
        verified chain (byte-identical to plain decode — each emitted token
        IS the argmax the plain path would have produced), then roll back
        surplus tail pages the rejected drafts grew."""
        pool = self.engine.pool
        Q = self.spec.draft_len + 1
        logits = self.engine.extend(
            [plans[r.rid][1] for r in alive],
            [plans[r.rid][2] for r in alive],
            [r.pages for r in alive],
            q_len=Q,
        )
        now = self.clock()
        produced = 0
        for i, r in enumerate(alive):
            kind, toks, _poss = plans[r.rid]
            if kind == "stream":
                r.cursor += len(toks)
                self._entered["prompt_tokens"] += len(toks)
                if r.cursor == len(r.prompt):
                    # the last prompt token's logits ARE the first
                    # generated token
                    self._emit_token(r, logits[i, len(toks) - 1], now)
                    produced += 1
                continue
            drafts = toks[1:]
            j = 0
            while j < len(toks):
                self._emit_token(r, logits[i, j], now)
                produced += 1
                if r.done:
                    break
                if j < len(drafts) and drafts[j] == r.generated[-1]:
                    # draft j matches the greedy chain: its K/V is already
                    # written and logits[i, j+1] verified it — keep reading
                    r.accepted += 1
                    if telemetry.enabled():
                        _spec_counter("accepted").inc()
                    j += 1
                else:
                    break
            if not r.done and drafts:
                # rollback: rejected drafts' stale K/V sits past seq_len
                # (masked, overwritten on commit); surplus TAIL pages the
                # draft chain grew go back to the pool now — they are
                # exclusively owned and never registered (only committed
                # full pages enter the index)
                keep = pool.blocks_for_tokens(self._tokens_needed(r))
                while len(r.pages) > keep:
                    pool.free([r.pages.pop()], owner=r.rid, retain=False)
        return produced

    def step(self) -> int:
        """One scheduler tick; returns the number of tokens produced: those of
        ONE program, which this call reads (and of a read-out since the last
        call, `sync`). The span's `ahead` is 1 where that program was
        dispatched before the step before it was read, else `sync` says why
        not: `first` (nothing was in flight), `prefill` (a bucketed prefill
        ran, the engine idle), `spec`, or what the read-out before it was for
        (`preempt`, `cancel`, `expire`, `handoff`, `drain`); `rows_dropped`
        counts rows computed one step past their end by `eos_id`.

        With QoS: sweep the queue-wait bound, feed measured pressure into
        the brownout ladder (transitions counted + trace-annotated), gate
        speculative decoding off at rung >= 1 (greedy verify is
        byte-identical, so this degrades only step count), and blend this
        tick's wall into `ewma_step_s` — the drain estimate the
        deadline/retry-after hints run on."""
        with RecordEvent("sched.step") as span:
            t_start = self.clock()
            if self.qos is not None:
                self._qos_pre_step(t_start)
            spec_saved = self.spec
            if (self.spec is not None and self.qos is not None
                    and not self.qos.brownout.spec_allowed()):
                self.spec = None
            # prompt tokens that enter in this call (it dispatches their step): by any path, and in a chunk
            self._entered = {"prompt_tokens": 0, "chunk_tokens": 0}
            self._ran = {}
            # a model that selects cached tokens: what its selector scored and chose in this step
            index_before = self.engine.index_totals.copy() if getattr(self.engine, "index_topk", 0) else None
            try:
                produced = self._step_inner()
            finally:
                self.spec = spec_saved
            dt = self.clock() - t_start
            if dt > 0.0:
                self.ewma_step_s = (dt if self.ewma_step_s is None
                                    else 0.8 * self.ewma_step_s + 0.2 * dt)
            span.args = {"produced": produced, "running": len(self.running),
                         "waiting": len(self.waiting), **self._entered, **self._ran}
            if self.engine.pool.has_recurrent_state:
                span.args["state_slots"] = self.engine.pool.state_slots_used()
            if index_before is not None:
                span.args.update(zip(("index_positions_live", "index_positions_selected", "sparse_queries"),
                                     (int(v) for v in self.engine.index_totals - index_before)))
        return produced

    def _qos_pre_step(self, now: float) -> None:
        qos = self.qos
        bound = qos.config.max_queue_wait_s
        if bound is not None:
            for req in list(self.waiting):
                if (req.submitted_time is not None
                        and now - req.submitted_time > bound):
                    self.waiting.remove(req)
                    self._shed(req, now, "queue_wait")
        pool = self.engine.pool
        pool_frac = pool.occupancy()
        if qos.config.max_waiting:
            queue_frac = len(self.waiting) / qos.config.max_waiting
        else:
            # unbounded line: scale depth against a few batches' worth so
            # sustained backlog still reads as pressure
            queue_frac = len(self.waiting) / float(4 * self.max_running)
        for direction, to_step in qos.update_pressure(now, pool_frac, queue_frac):
            if telemetry.enabled():
                _brownout_step_gauge().set(to_step)
                _brownout_transitions(direction, BROWNOUT_STEPS[to_step]).inc()
            _rt.record_event(
                "qos", "brownout", now, direction=direction, step=to_step,
                rung=BROWNOUT_STEPS[to_step],
                pressure=round(qos.last_pressure, 4),
            )
            _tl.emit("qos", "brownout",
                     severity="warn" if direction == "up" else "info",
                     direction=direction, step=to_step,
                     rung=BROWNOUT_STEPS[to_step],
                     pressure=round(qos.last_pressure, 4))

    def _step_inner(self) -> int:
        produced, self._carried = self._carried, 0
        if self._flight is not None and self.spec is not None:
            # the brownout ladder gave speculation back: its steps go through
            # `engine.extend`, with nothing in flight
            self._why = "spec"
            return produced + self._read_out()
        if self._flight is None:
            # nothing in flight: this call's own program first, as every step was
            emitted, plan = self._plan()
            produced += emitted
            if plan is None:
                if self.running or self._ran:  # a step under `spec_decode`, or one that ran no program
                    self._publish()
                return produced
            reason, self._why = self._why or ("prefill" if emitted else "first"), None
            self._flight = self._dispatch(plan, {"sync": reason})
        nxt, gave_up = None, None
        self._planning = True
        try:
            _, plan = self._plan()
            if plan is not None:
                nxt = self._dispatch(plan, {"ahead": 1})
        except _Abandon as e:
            gave_up = e.reason
        finally:
            self._planning = False
        produced += self._read_out()
        if gave_up is not None:
            # what could not happen beside a step in flight happens now, and the
            # step after it is dispatched behind it: one synchronous step
            emitted, plan = self._plan()
            produced += emitted
            if plan is not None:
                nxt = self._dispatch(plan, {"sync": gave_up})
        elif nxt is not None and not self._lives(nxt):
            nxt = None
        self._flight = nxt
        self._publish()
        return produced

    def _ends_in_flight(self, req: Request) -> bool:
        """Whether the token the step in flight computes is the request's
        last by `max_new_tokens`: known without its value."""
        return req.unread > 0 and (
            (len(req.prompt) - req.prompt_len) + len(req.generated) + req.unread >= req.max_new_tokens)

    def _plan(self):
        """Everything of a step before the engine's call: the TTL sweep,
        admission, page growth, the step's chunk, its rows. Returns (tokens
        admission emitted, the plan or None where no program is to run); a
        step under `spec_decode` runs whole in here. With a step in flight
        (`_planning`) it plans the step after it: over the requests that go
        on, each from where the step in flight leaves it."""
        produced = 0
        staying = [r for r in self.running if not self._ends_in_flight(r)]
        if self._planning and not staying:
            return 0, None  # the next call starts over, and may take a bucketed prefill
        # TTL sweep first: an expired request must not consume an admission
        # slot or grow pages this very tick
        self._expire_due(self.clock())
        # admission: fill free decode slots from the waiting line; a
        # blocked high-priority head may preempt a strictly lower-class
        # running victim (its pages free, admission retries)
        if self.waiting:  # an empty line is no phase of the step
            with RecordEvent("sched.admit"):
                while True:
                    emitted = self._try_admit()
                    if emitted is not None:
                        produced += emitted
                        continue
                    if not self._qos_priority_preempt():
                        break

        if not self.running:
            if telemetry.enabled():
                self._sync_gauges()
            return produced, None

        with RecordEvent("sched.grow"):
            staying = [r for r in self.running if not self._ends_in_flight(r)]
            # speculative plans first: growth must cover every position the
            # draft chain will write, not just the next token
            plans: Dict[int, Tuple[str, List[int], List[int]]] = {}
            if self.spec is not None:
                for req in staying:
                    plans[req.rid] = self._plan_row(req)
            # the step's ONE chunk: the oldest request with prompt left (the
            # order of admission, which is `_try_admit`'s); the others with
            # prompt left keep their slot, wait their turn and write nothing
            chunk_req = next((r for r in staying if self._chunkable(r)), None)
            take = 0 if chunk_req is None else min(
                self._chunk_width(), len(chunk_req.prompt) - chunk_req.cursor)

            # growth: every running sequence needs pages covering the K/V slots
            # this step writes; allocate at block boundaries, preempting until
            # the pool yields one
            pool = self.engine.pool
            for req in staying:
                if req not in self.running:
                    # evicted by an earlier iteration's preemption — allocating
                    # into it now would leak the page at re-admission
                    continue
                # lo..hi: the positions this step writes for the request
                if self.spec is not None:
                    _, _, poss = plans[req.rid]
                    lo, hi = poss[0], poss[-1]
                elif req is chunk_req:
                    lo, hi = req.cursor, req.cursor + take - 1
                elif self._chunkable(req):
                    continue  # waits its turn
                else:
                    lo = hi = self._tokens_needed(req) - 1
                if hi + 1 > self.engine.max_seq_len:
                    # capacity guard (submit() bounds this; belt-and-braces)
                    self.sync("first")
                    self._finish(req, self.clock())
                    continue
                while pool.blocks_for_tokens(hi + 1) > len(req.pages):
                    try:
                        req.pages.extend(pool.alloc(1, owner=req.rid))
                    except PoolExhausted:
                        self.sync("preempt")  # a step ahead is given up here
                        if req in self.running and len(self.running) == 1:
                            raise  # nothing left to evict but ourselves
                        if not self._preempt_one():
                            raise
                        if req not in self.running:
                            break  # we were the victim
                # copy-on-write guard: no position this step writes may land in
                # a page another request still reads. Full-page-aligned sharing
                # makes this structurally unreachable in steady state, but the
                # evacuate/resume and rollback races are exactly where a silent
                # scribble would corrupt a neighbor — clone instead.
                if req in self.running and req.pages:
                    for pi in range(lo // pool.block_size,
                                    min(hi // pool.block_size, len(req.pages) - 1) + 1):
                        if pool.refcount(req.pages[pi]) > 1:
                            req.pages[pi] = pool.make_private(req.pages[pi], owner=req.rid)
            alive = [r for r in staying if r.pages and r in self.running]
            if chunk_req not in alive:
                chunk_req = None  # finished by the guard, or a later row's growth evicted it

        if not alive:
            return produced, None
        if self.spec is not None:
            with RecordEvent("sched.spec"):
                produced += self._spec_decode_step(alive, plans)
                self.running = [r for r in self.running if not r.done]
            self._ran = {"sync": "spec"}
            self._why = None
            return produced, None
        with RecordEvent("sched.rows"):
            rows = []
            for r in alive:
                if r is chunk_req or self._chunkable(r):
                    continue  # the chunk, or a prompt that waits its turn: no decode row
                if r.cursor < len(r.prompt):  # streaming its prompt in
                    rows.append((r, r.prompt[r.cursor], r.cursor))
                elif r.unread:  # the step in flight chooses it: taken on the device
                    rows.append((r, self._flight.emits[id(r)][1], r.context_len - 1))
                else:
                    rows.append((r, r.generated[-1], r.context_len - 1))
        if chunk_req is None and not rows:
            return produced, None
        return produced, (rows, chunk_req, take)

    def _dispatch(self, plan, how: Dict[str, object]) -> _Flight:
        """One engine call for the plan, not waited for. What the step does to
        its requests without its tokens' values happens here: a prompt's
        cursor moves past what the step writes, and a request whose token the
        step computes carries it as `unread`."""
        rows, chunk_req, take = plan
        tokens = [t for _, t, _ in rows]
        positions = [p for _, _, p in rows]
        seq_lens = [p + 1 for _, _, p in rows]
        page_rows = [r.pages for r, _, _ in rows]
        if chunk_req is not None:
            result, _ = self.engine.decode_with_chunk(
                tokens, positions, seq_lens, page_rows,
                chunk_req.prompt[chunk_req.cursor:chunk_req.cursor + take],
                chunk_req.cursor, chunk_req.pages)
        else:
            result = self.engine.decode(
                tokens=tokens, positions=positions, seq_lens=seq_lens,
                page_rows=page_rows,
            )
        flight = _Flight(result, [r for r, _, _ in rows], {}, how)
        for i, (r, _, _) in enumerate(rows):
            if r.cursor < len(r.prompt):  # streaming its prompt in
                r.cursor += 1
                self._entered["prompt_tokens"] += 1
            if r.cursor == len(r.prompt):
                # the last prompt token's logits ARE the first generated token
                flight.emits[id(r)] = (r, result.token(i))
        if chunk_req is not None:
            flight.held.append(chunk_req)
            chunk_req.cursor += take
            chunk_req.chunks += 1
            self._entered["prompt_tokens"] += take
            self._entered["chunk_tokens"] += take
            if chunk_req.cursor == len(chunk_req.prompt):
                # the step that carries a prompt's last chunk emits its first token
                flight.emits[id(chunk_req)] = (chunk_req, result.chunk.token())
        for r, _ in flight.emits.values():
            r.unread += 1
        return flight

    def _lives(self, flight: _Flight) -> bool:
        """Whether a dispatched step still serves someone. Its every request
        may have ended by `eos_id` in the step before it: then nobody reads
        it, and nothing is in flight."""
        if any(not r.done for r in flight.held):
            return True
        for r, _ in flight.emits.values():
            r.unread -= 1
        self._ran["rows_dropped"] += len(flight.emits)
        return False

    def _read_out(self) -> int:
        """The step in flight, read: the ONE wait for the device (ids, a few
        bytes). Emits each row's token and finishes who ends; a row whose
        request ended in the step before (by `eos_id`), or left `running`,
        is dropped. Returns the tokens emitted; nothing is in flight after."""
        flight = self._flight
        flight.result.ids()  # a step that cannot be read stays in flight
        self._flight = None
        with RecordEvent("sched.emit"):
            now = self.clock()
            produced = 0
            here = {id(r) for r in self.running}
            for r, token in flight.emits.values():
                r.unread -= 1
                if not r.done and id(r) in here:
                    self._emit(r, int(token), now)
                    produced += 1
            self.running = [r for r in self.running if not r.done]
        self._ran = {**flight.how, "rows_dropped": len(flight.emits) - produced}
        return produced

    def _publish(self) -> None:
        with RecordEvent("sched.publish"):
            if self.prefix_cache:
                for r in self.running:
                    self._register_committed(r)
            if telemetry.enabled():
                self._sync_gauges()
                active_tokens = sum(self._tokens_needed(r) for r in self.running)
                self.engine.pool.note_fragmentation(active_tokens)


class StaticBatchingScheduler:
    """The baseline continuous batching is measured against: requests are
    taken in arrival order in fixed groups of `batch_size`; a group decodes
    until EVERY member hits its budget (finished slots idle), and no new
    request enters until the whole group drains."""

    def __init__(self, engine, *, batch_size: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.engine = engine
        self.batch_size = int(batch_size or engine.max_batch)
        self.eos_id = eos_id
        self.clock = clock
        self.waiting: List[Request] = []
        self.running: List[Request] = []
        self.finished: List[Request] = []
        self.preempted_total = 0

    def submit(self, req: Request) -> None:
        req.submitted_time = self.clock()
        self.waiting.append(req)

    def idle(self) -> bool:
        return not self.waiting and not self.running

    def _emit(self, req: Request, logits: np.ndarray, now: float) -> None:
        token = int(np.argmax(logits))
        req.generated.append(token)
        req.token_times.append(now)
        if req.first_token_time is None:
            req.first_token_time = now

    def _done(self, req: Request) -> bool:
        return len(req.generated) >= req.max_new_tokens or (
            self.eos_id is not None and req.generated
            and req.generated[-1] == self.eos_id
        )

    def step(self) -> int:
        produced = 0
        pool = self.engine.pool
        if not self.running and self.waiting:
            group, self.waiting = self.waiting[: self.batch_size], self.waiting[self.batch_size:]
            for req in group:
                req.pages = pool.alloc(
                    pool.blocks_for_tokens(len(req.prompt) + req.max_new_tokens)
                )
                logits = self.engine.prefill(req.prompt, req.pages)
                self._emit(req, logits, self.clock())
                produced += 1
            self.running = group
        if not self.running:
            return produced
        live = [r for r in self.running if not self._done(r)]
        if live:
            logits = self.engine.decode(
                tokens=[r.generated[-1] for r in live],
                positions=[r.context_len - 1 for r in live],
                seq_lens=[r.context_len for r in live],
                page_rows=[r.pages for r in live],
            )
            now = self.clock()
            for r, lg in zip(live, logits):
                self._emit(r, lg, now)
                produced += 1
        if all(self._done(r) for r in self.running):
            now = self.clock()
            for r in self.running:
                r.finish_time = now
                pool.free(r.pages)
                r.pages = []
                self.finished.append(r)
            self.running = []
        return produced


def replay(scheduler, requests: Sequence[Request], *,
           clock: Callable[[], float] = time.monotonic,
           max_wall_s: float = 600.0) -> Dict:
    """Feed `requests` to `scheduler` honoring their arrival_time offsets
    (seconds from replay start) and run until everything drains. Returns
    aggregate serving stats: tokens/s over generated tokens + p50/p99
    TTFT/TPOT in milliseconds."""
    pending = sorted(requests, key=lambda r: (r.arrival_time, r.rid))
    t0 = clock()
    i = 0
    while i < len(pending) or not scheduler.idle():
        now = clock() - t0
        if clock() - t0 > max_wall_s:
            raise TimeoutError(f"replay exceeded {max_wall_s}s wall budget")
        while i < len(pending) and pending[i].arrival_time <= now:
            scheduler.submit(pending[i])
            i += 1
        if scheduler.idle():
            # nothing in flight: don't burn a step, wait for the next arrival
            if i < len(pending):
                time.sleep(min(0.001, max(0.0, pending[i].arrival_time - now)))
            continue
        scheduler.step()
    wall = clock() - t0

    done = list(scheduler.finished)
    # arrival_time is an offset from t0; ttft/token_times are absolute clock
    # values — normalize before differencing
    ttfts = [r.first_token_time - (t0 + r.arrival_time) for r in done
             if r.first_token_time is not None]
    # TPOT percentiles over POOLED inter-token intervals (vLLM's ITL
    # convention): a per-request-mean p99 degenerates to "worst request's
    # mean", which one OS/GC blip in a short request dominates
    tpots = [iv for r in done for iv in np.diff(r.token_times)]
    total_tokens = sum(
        (len(r.prompt) - r.prompt_len) + len(r.generated) for r in done
    )
    out = {
        "n_requests": len(done),
        "generated_tokens": int(total_tokens),
        "wall_s": round(wall, 4),
        "tokens_per_sec": round(total_tokens / wall, 2) if wall > 0 else None,
        "preempted": getattr(scheduler, "preempted_total", 0),
    }
    out.update(percentiles("ttft_ms", [t * 1000 for t in ttfts]))
    out.update(percentiles("tpot_ms", [t * 1000 for t in tpots]))
    return out


def percentiles(name: str, values: Sequence[float]) -> Dict[str, Optional[float]]:
    if not values:
        return {f"p50_{name}": None, f"p99_{name}": None}
    arr = np.asarray(values, np.float64)
    return {
        f"p50_{name}": round(float(np.percentile(arr, 50)), 3),
        f"p99_{name}": round(float(np.percentile(arr, 99)), 3),
    }
