"""Continuous-batching serving engine: slot scheduler + masked chunked
prefill + per-row-position decode, with an optional paged block-table KV
cache and a production fault model.

Requests are ``submit()``-ed into a queue and admitted MID-FLIGHT into a
fixed pool of decode slots: a freed slot (eos / max_new) is refilled from
the queue on the next ``step()``, so the decode batch stays full under
streaming arrivals instead of draining to the slowest request. Admission
runs prompts through the chunked prefill step — and it is BATCHED: up to
``admit_k`` queued requests run their chunks in ONE stacked call per step
(per-row offsets/masks keep every row exact), so bursty arrivals no longer
serialize one prefill per request. Decoding advances every live slot at its
OWN position (vector positions, donated cache, live-slot mask). Mixed-length
batches are EXACT: pad/tail tokens are masked out of attention and are
identity steps in the SSM scan (MoE layers remain subject to per-chunk
capacity routing, the standard batched-MoE caveat).

With ``page_size > 0`` the K/V cache is PAGED (serving/paged_cache.py):
K/V live in shared fixed-size page pools, each request owns just enough
pages for its ``prompt + max_new`` budget through a block table, and pages
return to the free list at eos — so admission is gated on the FREE-PAGE
budget, not on ``slots × max_seq`` regions.

ROBUSTNESS MODEL (mirrors the trainer's checkpoint/restart + straggler
machinery for the serving workload):

* Every request carries a terminal ``status`` — ``ok / rejected /
  cancelled / expired / quarantined / failed`` — and malformed submissions
  raise a typed :class:`RejectedRequest` (reason enum) instead of killing
  the engine with an assert.
* Per-request DEADLINES (TTFT + total latency) are checked at step
  boundaries; a bounded queue (``max_queue``) sheds load via a pluggable
  policy (reject-new, or deadline-aware drop of the least-slack request).
* ``cancel(rid)`` works on queued AND live requests, freeing the slot and
  its pages immediately.
* Non-finite logits are QUARANTINED per row: the poisoned request retires
  with ``status="quarantined"`` and the rest of the batch is untouched.
* ``snapshot()/restore()`` capture the full scheduler state (queue,
  slot↔request map, positions, page allocator) together with the KV/SSM
  pools through checkpoint/manager.py's atomic writer; on a step failure
  the engine restores the last snapshot and REPLAYS — an in-memory event
  log of post-snapshot submits/cancels closes the gap, and a monotonic
  per-request emission watermark makes token delivery EXACTLY-ONCE
  (replayed tokens below the watermark are regenerated bit-identically
  but never re-emitted).
* A :class:`~repro.serving.faults.FaultInjector` plugs into a narrow hook
  in ``step()`` to drive all of the above deterministically.

``generate(prompts, ...)`` remains as a convenience wrapper: submit all,
run to completion, return a batch result. Any number of prompts works —
more prompts than slots simply queue. Prompts may be raw token sequences
or typed :class:`RequestSpec` values; a malformed prompt surfaces its
:class:`RejectedRequest` per-row instead of aborting the batch.

WORKER API (the disaggregated topology in serving/disagg.py builds on
these — they are first-class engine API, not internals):

* ``prefill_step()`` — queued-deadline expiry + one stacked chunk-
  admission call; ``decode_step()`` — one decoded token per live slot +
  live-deadline expiry. ``step()`` is exactly ``prefill_step(); decode_
  step()`` under the fault/snapshot envelope; a ``role``-restricted
  engine (``role="prefill"`` / ``"decode"``) builds only the step it
  runs and skips the other entirely.
* ``export_handoff(slot)`` / ``migrate(handoff)`` — KV handoff as paged-
  page MIGRATION: a finished prefill's page contents (+ per-slot SSM
  carry) move into another engine's pool through a :class:`Handoff`
  record, so the decode worker resumes at the prefill position without
  re-prefill, bit-exact vs the single-engine path.
* :class:`EngineConfig` — one construction surface (config groups:
  engine / paging / robustness / chaos / disagg) shared by the CLI and
  the benchmarks; ``EngineConfig.build()`` returns a ServeEngine, or the
  Router topology when ``disagg`` is set.
"""
from __future__ import annotations

import dataclasses
import enum
import time
from collections import deque
from typing import (Callable, Dict, List, Optional, Sequence, Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.train_step import (build_decode_step,
                                     build_prefill_chunk_step)
from repro.models import lm
from repro.obs import Tracer
from repro.serving.paged_cache import BlockAllocator, pages_for
from repro.training.trainer import StragglerMonitor


def stitch_prefill_cache(cfg, decode_cache, prefill_cache, prompt_len: int):
    """Insert prefill cache entries — stacked (n_periods, B, S, ...) from the
    layer scan — into the fixed-size decode cache at positions [0, S).
    Used by the batched (non-chunked) prefill path in tests/tools."""
    out = []
    for entry, pre in zip(decode_cache, prefill_cache):
        e = {}
        for k in entry:
            if k in ("k", "v"):
                e[k] = entry[k].at[:, :, :prompt_len].set(
                    pre[k].astype(entry[k].dtype))
            elif k in ("xk", "xv"):
                src = pre[k]
                e[k] = entry[k].at[:, :, :src.shape[2]].set(
                    src.astype(entry[k].dtype))
            elif k == "conv":
                e[k] = pre[k].astype(entry[k].dtype)
            else:                                   # ssm state (fp32)
                e[k] = pre[k]
        out.append(e)
    return tuple(out)


# ---------------------------------------------------------------------------
# Request lifecycle types
# ---------------------------------------------------------------------------


class RequestStatus(str, enum.Enum):
    """Lifecycle states. QUEUED/RUNNING are transient; the rest terminal."""
    QUEUED = "queued"
    RUNNING = "running"
    OK = "ok"
    REJECTED = "rejected"
    CANCELLED = "cancelled"
    EXPIRED = "expired"
    QUARANTINED = "quarantined"
    FAILED = "failed"


TERMINAL_STATUSES = frozenset({
    RequestStatus.OK, RequestStatus.REJECTED, RequestStatus.CANCELLED,
    RequestStatus.EXPIRED, RequestStatus.QUARANTINED, RequestStatus.FAILED})


class RejectReason(str, enum.Enum):
    EMPTY_PROMPT = "empty_prompt"
    TOO_LONG = "too_long"               # prompt + max_new > max_seq
    OVER_CAPACITY = "over_capacity"     # page budget beyond the whole pool
    QUEUE_FULL = "queue_full"           # bounded queue, shed policy said no
    INVALID = "invalid"                 # spec field failed validation


class RejectedRequest(Exception):
    """Typed submission rejection. Carries the reason enum and the
    (terminal, status=rejected) request record; the engine stays fully
    serviceable after raising this."""

    def __init__(self, reason: RejectReason, msg: str, request=None):
        super().__init__(f"{reason.value}: {msg}")
        self.reason = reason
        self.msg = msg
        self.request = request


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    """Typed submission: everything ``submit()`` accepts, as ONE validated
    value object — replacing the growing kwarg sprawl (``max_new`` /
    ``eos_id`` / ``ttft_deadline_s`` / ``deadline_s`` / routing hints).
    The kwargs path on ``submit()``/``generate()`` still works and builds
    the spec internally, so both doors validate identically.

    Validation runs in ``__post_init__`` and raises
    :class:`RejectedRequest` (reason ``EMPTY_PROMPT`` / ``INVALID``) for
    anything malformed in ISOLATION; engine-relative checks (``TOO_LONG``
    / ``OVER_CAPACITY`` / ``QUEUE_FULL``) stay in ``submit()``, where the
    engine geometry is known. Deadlines of None inherit the engine
    defaults at submit time. ``route_hint`` is a disaggregated-topology
    hint — preferred prefill-worker index (best-effort; the Router wraps
    it into range, a single engine ignores it)."""
    prompt: Tuple[int, ...]
    max_new: int = 32
    eos_id: Optional[int] = None
    ttft_deadline_s: Optional[float] = None
    deadline_s: Optional[float] = None
    route_hint: Optional[int] = None

    def __post_init__(self):
        if isinstance(self.prompt, (str, bytes)):
            raise RejectedRequest(
                RejectReason.INVALID,
                "prompt must be a sequence of token ids, not text")
        try:
            prompt = tuple(int(t) for t in self.prompt)
        except (TypeError, ValueError) as e:
            raise RejectedRequest(
                RejectReason.INVALID,
                f"prompt must be a sequence of token ids ({e})") from e
        object.__setattr__(self, "prompt", prompt)
        if not prompt:
            raise RejectedRequest(RejectReason.EMPTY_PROMPT, "empty prompt")
        if not isinstance(self.max_new, (int, np.integer)) or \
                self.max_new < 1:
            raise RejectedRequest(
                RejectReason.INVALID,
                f"max_new must be a positive int, got {self.max_new!r}")
        if self.eos_id is not None and \
                not isinstance(self.eos_id, (int, np.integer)):
            raise RejectedRequest(
                RejectReason.INVALID,
                f"eos_id must be an int or None, got {self.eos_id!r}")
        for name in ("ttft_deadline_s", "deadline_s"):
            v = getattr(self, name)
            if v is not None and (not isinstance(v, (int, float))
                                  or isinstance(v, bool) or v <= 0):
                raise RejectedRequest(
                    RejectReason.INVALID,
                    f"{name} must be a positive number or None, got {v!r}")
        if self.route_hint is not None and \
                (not isinstance(self.route_hint, (int, np.integer))
                 or self.route_hint < 0):
            raise RejectedRequest(
                RejectReason.INVALID,
                f"route_hint must be a worker index >= 0 or None, "
                f"got {self.route_hint!r}")

    @property
    def budget_tokens(self) -> int:
        """Cache budget this request admits against (prompt + max_new)."""
        return len(self.prompt) + self.max_new


@dataclasses.dataclass
class GenerateResult:
    tokens: np.ndarray          # (B, max_new) generated ids
    lengths: np.ndarray         # (B,) tokens before eos/max
    prefill_tokens: int
    decode_steps: int
    # per-row terminal status values + the typed rejection for each row
    # that never entered the engine (malformed prompt); appended after the
    # original fields so positional construction stays compatible
    statuses: List[str] = dataclasses.field(default_factory=list)
    rejected: Dict[int, RejectedRequest] = dataclasses.field(
        default_factory=dict)


@dataclasses.dataclass
class Request:
    """One in-flight generation request (streaming API handle)."""
    rid: int
    prompt: List[int]
    max_new: int
    eos_id: Optional[int]
    tokens: List[int] = dataclasses.field(default_factory=list)
    length: int = -1            # tokens before eos; -1 while running
    slot: int = -1
    submit_t: float = 0.0
    first_token_t: float = 0.0  # TTFT = first_token_t - submit_t
    done_t: float = 0.0
    status: RequestStatus = RequestStatus.QUEUED
    error: str = ""
    ttft_deadline_s: Optional[float] = None   # first token within this
    deadline_s: Optional[float] = None        # whole request within this
    route_hint: Optional[int] = None          # preferred prefill worker

    @property
    def done(self) -> bool:
        return self.status in TERMINAL_STATUSES

    @property
    def ttft_s(self) -> float:
        return self.first_token_t - self.submit_t


_REQ_FIELDS = ("rid", "prompt", "max_new", "eos_id", "tokens", "length",
               "slot", "submit_t", "first_token_t", "done_t", "error",
               "ttft_deadline_s", "deadline_s", "route_hint")


def _req_to_json(r: Request) -> Dict:
    d = {k: getattr(r, k) for k in _REQ_FIELDS}
    d["status"] = r.status.value
    return d


def _req_from_json(d: Dict) -> Request:
    # .get: route_hint is absent from pre-disagg snapshots/logs
    kw = {k: d.get(k) if k == "route_hint" else d[k] for k in _REQ_FIELDS}
    kw["prompt"] = list(kw["prompt"])
    kw["tokens"] = list(kw["tokens"])
    return Request(status=RequestStatus(d["status"]), **kw)


@dataclasses.dataclass(frozen=True)
class Handoff:
    """One finished prefill crossing the worker boundary — everything a
    decode pool needs to resume the request at its prefill position
    WITHOUT re-prefill. The page CONTENTS ride the handoff as immutable
    gathered arrays (detached from the exporting pool, which reclaims its
    pages the moment the export returns), so the record stays valid even
    if the exporting worker crashes, restores, or reuses the pages — the
    router re-migrates from the same record after a decode-worker loss.

    ``pages`` is the SOURCE pool's page-id list for the request's full
    ``prompt + max_new`` budget (what admission allocated); only the
    ``n_content_pages`` prefix holds written K/V and travels in ``kv`` —
    the tail pages' contents are garbage on both sides, masked by
    position validity exactly like a reused contiguous slot."""
    rid: int
    req_json: Dict              # request state at handoff (tokens=[first])
    pos: int                    # cache position = prompt length
    last_tok: int               # feeds the first decode step
    budget_tokens: int          # prompt + max_new (import page budget)
    pages: Tuple[int, ...]      # source page ids, block-table order
    block_table: Tuple[int, ...]  # source row (import cross-check)
    n_content_pages: int        # written prefix actually copied
    kv: Tuple                   # per cache entry: K/V page gather | SSM row


def _span_total(name: str, key: str, doc: str) -> property:
    return property(lambda self: self.tracer.total(name, key), doc=doc)


class ServeEngine:
    # per-phase accounting (the CLI summary prints these), read off the
    # engine's own spans: one ``serve.admit`` per admission round, one
    # ``serve.decode`` per decode step
    prefill_s = _span_total("serve.admit", "seconds",
                            "host seconds in admission rounds")
    prefill_tokens = _span_total("serve.admit", "prompt_tokens",
                                 "prompt tokens admitted")
    admissions = _span_total("serve.admit", "rows",
                             "requests admitted (parking rows don't count)")
    admit_rounds = _span_total("serve.admit", "spans",
                               "stacked chunk-admission rounds")
    decode_s = _span_total("serve.decode", "seconds",
                           "host seconds in decode steps")
    decode_steps = _span_total("serve.decode", "spans", "decode steps")
    decode_tokens = _span_total("serve.decode", "live",
                                "tokens decoded (live rows summed)")
    decode_kv_pages = _span_total("serve.decode", "kv_pages",
                                  "K/V pages read per layer, steps summed")

    def __init__(self, cfg: ModelConfig, params=None, mesh=None,
                 max_seq: int = 256, batch_size: int = 4, seed: int = 0,
                 plan_cache: Optional[str] = None, plan_hw: str = "",
                 chunk: int = 0, page_size: int = 0, n_pages: int = 0,
                 admit_k: int = 0, max_queue: int = 0,
                 shed_policy: Union[str, Callable] = "reject",
                 ttft_deadline_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 snapshot_dir: Optional[str] = None, snapshot_every: int = 8,
                 max_restarts: int = 3, recover: Optional[bool] = None,
                 faults=None, straggler_factor: float = 2.5,
                 clock: Optional[Callable[[], float]] = None,
                 on_token: Optional[Callable[[int, int, int], None]] = None,
                 role: str = "both", tracer: Optional[Tracer] = None):
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"role must be both|prefill|decode, got {role!r}")
        self.cfg = cfg
        self.mesh = mesh
        self.role = role
        self.max_seq = max_seq
        self.B = batch_size                       # decode slots
        self.plan_cache = plan_cache
        # legalize the chunk to a divisor of max_seq: the chunk grid then
        # tiles the cache exactly and the last chunk of any admissible
        # prompt stays inside [0, max_seq) — otherwise the tail chunk's
        # dynamic_update_slice would CLAMP its start and silently corrupt
        # earlier chunks' K/V
        chunk = max(1, min(chunk or min(32, max_seq), max_seq))
        while max_seq % chunk:
            chunk -= 1
        self.chunk = chunk
        # paged block-table KV cache: page_size > 0 pools K/V as shared
        # fixed-size pages and admits against the free-page budget. The
        # page is legalized to a divisor of max_seq the same way (a block
        # table must tile [0, max_seq) exactly).
        if page_size:
            page_size = max(1, min(page_size, max_seq))
            while max_seq % page_size:
                page_size -= 1
        self.page_size = page_size
        self.paged = page_size > 0
        self.max_blocks = (max_seq // page_size) if self.paged else 0
        if self.paged and not n_pages:
            # parity capacity by default: every slot can still hold max_seq
            n_pages = batch_size * self.max_blocks + 1
        self.n_pages = n_pages if self.paged else 0
        # how many queued requests one step() may admit in ONE stacked
        # chunk call (0 = up to every free slot)
        self.admit_k = admit_k
        # -- robustness knobs ------------------------------------------------
        self.max_queue = max_queue               # 0 = unbounded
        self.shed_policy = shed_policy           # "reject"|"deadline"|callable
        self.ttft_deadline_s = ttft_deadline_s   # per-request defaults
        self.deadline_s = deadline_s
        self.max_restarts = max_restarts         # consecutive step failures
        self.faults = faults                     # FaultInjector or None
        self.monitor = StragglerMonitor(straggler_factor)
        self._clock = clock or time.perf_counter
        self.on_token = on_token                 # exactly-once emission cb
        # host spans of the admission and decode phases; the per-phase
        # counters (the class's properties) are their totals
        self.tracer = tracer if tracer is not None else Tracer()
        self.snapshot_every = snapshot_every
        self.ckpt = (CheckpointManager(snapshot_dir, keep=3,
                                       async_save=False)
                     if snapshot_dir else None)
        # recovery on step failure: restore last snapshot (or reset empty)
        # + replay the post-snapshot event log. Default on iff snapshots
        # are configured; force with recover=True/False.
        self.auto_recover = (recover if recover is not None
                             else snapshot_dir is not None)
        # ONE shape describes the shared donated cache: both steps derive
        # identical cache shardings from it on a mesh (paged: the K/V page
        # pools + per-slot SSM state)
        dshape = ShapeConfig("serve_decode", seq_len=max_seq,
                             global_batch=batch_size, kind="decode",
                             page_size=self.page_size, n_pages=self.n_pages)
        # a role-restricted worker builds ONLY the step it runs: a decode
        # worker never compiles prefill plans and vice versa
        self.prefill = (build_prefill_chunk_step(cfg, dshape, mesh,
                                                 chunk=self.chunk,
                                                 plan_cache=plan_cache,
                                                 plan_hw=plan_hw)
                        if role != "decode" else None)
        self.decode = (build_decode_step(cfg, dshape, mesh,
                                         plan_cache=plan_cache,
                                         plan_hw=plan_hw)
                       if role != "prefill" else None)
        ctx = (self.decode or self.prefill)["ctx"]
        if params is None:
            params = lm.init_params(cfg, jax.random.PRNGKey(seed), ctx)
        self.params = params
        # device state: the decode cache, donated through every chunk/decode
        # call — contiguous: one region (batch row) per slot; paged: shared
        # K/V page pools + dense per-slot SSM entries
        if self.paged:
            self.cache = lm.init_paged_cache(cfg, batch_size, self.n_pages,
                                             page_size, ctx)
            self.alloc = BlockAllocator(self.n_pages, page_size,
                                        self.max_blocks)
            self.block_tables = np.zeros((batch_size, self.max_blocks),
                                         np.int32)
        else:
            self.cache = lm.init_cache(cfg, batch_size, max_seq, ctx)
            self.alloc = None
            self.block_tables = None
        # host scheduler state
        self.slot_req: List[Optional[Request]] = [None] * batch_size
        self.pos = np.zeros((batch_size,), np.int32)      # next write index
        self.live = np.zeros((batch_size,), bool)
        self.last_tok = np.zeros((batch_size,), np.int32)
        self.queue: deque = deque()
        self.finished: Dict[int, Request] = {}
        self._next_rid = 0
        # exactly-once delivery ledger: rid -> tokens emitted so far. Never
        # rolled back by restore — replayed tokens below the watermark are
        # regenerated (bit-identically) but not re-emitted.
        self.emitted: Dict[int, int] = {}
        # write-ahead event log since the last committed snapshot: replayed
        # after a restore so post-snapshot submits/cancels are never lost
        self._log: List[Tuple] = []
        # fault/recovery accounting
        self.step_idx = 0           # monotonic; NEVER rolled back by restore
        self.failures = 0           # total step failures
        self.recoveries = 0         # successful restore+replay cycles
        self.shed = 0               # queued requests dropped by load shedding
        self.expired = 0
        self.quarantined = 0
        self._consec_failures = 0
        # page-migration accounting (disaggregated handoff)
        self.handoffs_out = 0       # finished prefills exported
        self.migrations_in = 0      # handoffs imported into this pool
        self.pages_exported = 0     # content pages copied out
        self.pages_imported = 0     # content pages copied in

    # -- streaming API ------------------------------------------------------

    def _reject(self, req: Request, reason: RejectReason, msg: str):
        req.status = RequestStatus.REJECTED
        req.error = f"{reason.value}: {msg}"
        req.done_t = self._clock()
        raise RejectedRequest(reason, msg, request=req)

    def _coerce_spec(self, request, max_new, eos_id, ttft_deadline_s,
                     deadline_s) -> RequestSpec:
        """Kwargs → :class:`RequestSpec` (a spec passes through). A spec
        validation failure is re-raised with a terminal (status=rejected)
        Request record attached, so the kwargs door keeps its contract:
        every rejection carries an inspectable request."""
        if isinstance(request, RequestSpec):
            return request
        try:
            return RequestSpec(prompt=request, max_new=max_new,
                               eos_id=eos_id,
                               ttft_deadline_s=ttft_deadline_s,
                               deadline_s=deadline_s)
        except RejectedRequest as e:
            try:
                prompt = ([] if isinstance(request, (str, bytes))
                          else [int(t) for t in request])
            except Exception:
                prompt = []
            rec = Request(self._next_rid, prompt,
                          max_new if isinstance(max_new, int) else 0,
                          None, submit_t=self._clock())
            self._next_rid += 1            # rids stay unique on reject
            rec.status = RequestStatus.REJECTED
            rec.error = f"{e.reason.value}: {e.msg}"
            rec.done_t = self._clock()
            raise RejectedRequest(e.reason, e.msg, request=rec) from e

    def submit(self, request: Union[RequestSpec, Sequence[int]],
               max_new: int = 32, eos_id: Optional[int] = None,
               ttft_deadline_s: Optional[float] = None,
               deadline_s: Optional[float] = None) -> int:
        """Queue a request; returns its id. ``request`` is a
        :class:`RequestSpec` or a raw prompt (token sequence) plus the
        legacy kwargs, which build a spec internally. Admission happens on
        the next ``step()`` (or immediately inside ``run()``). Malformed
        requests raise :class:`RejectedRequest` (typed reason, engine
        untouched); a full bounded queue applies the shedding policy
        first."""
        if self.role == "decode":
            raise RuntimeError(
                "decode-role worker takes migrated requests only "
                "(migrate()); submit through the router")
        spec = self._coerce_spec(request, max_new, eos_id,
                                 ttft_deadline_s, deadline_s)
        req = Request(self._next_rid, list(spec.prompt), spec.max_new,
                      spec.eos_id, submit_t=self._clock(),
                      ttft_deadline_s=(self.ttft_deadline_s
                                       if spec.ttft_deadline_s is None
                                       else spec.ttft_deadline_s),
                      deadline_s=(self.deadline_s if spec.deadline_s is None
                                  else spec.deadline_s),
                      route_hint=spec.route_hint)
        self._next_rid += 1                    # rids stay unique on reject
        if spec.budget_tokens > self.max_seq:
            self._reject(req, RejectReason.TOO_LONG,
                         f"prompt {len(req.prompt)} + max_new "
                         f"{spec.max_new} exceeds engine max_seq "
                         f"{self.max_seq}")
        if self.paged:
            # a budget beyond the POOL capacity would never fit, and the
            # FIFO admission gate would stall on it (and everything queued
            # behind it) forever — reject it at the door instead
            need = pages_for(spec.budget_tokens, self.page_size)
            if need > min(self.n_pages - 1, self.max_blocks):
                self._reject(req, RejectReason.OVER_CAPACITY,
                             f"request needs {need} pages, pool holds "
                             f"{min(self.n_pages - 1, self.max_blocks)}")
        if self.max_queue and len(self.queue) >= self.max_queue:
            victim = self._shed_victim(req)
            if victim is None:
                self._reject(req, RejectReason.QUEUE_FULL,
                             f"queue at max_queue={self.max_queue}")
            self._drop_queued(victim, RequestStatus.EXPIRED,
                              "shed: queue full")
            self.shed += 1
        self.enqueue(req)
        return req.rid

    def enqueue(self, req: Request) -> None:
        """Append an ALREADY-VALIDATED Request to this engine's queue and
        write-ahead log (the router dispatches through this after doing
        its own admission; ``submit()`` lands here too). The log entry
        makes the request crash-durable on THIS engine: a post-snapshot
        restore replays it from token 0, watermark-deduped."""
        req.status = RequestStatus.QUEUED
        self.queue.append(req)
        self._log.append(("submit", _req_to_json(req)))

    def _shed_victim(self, new_req: Request) -> Optional[Request]:
        """Pick the queued request to drop when the bounded queue is full
        (None = reject the new request instead). The "deadline" policy
        drops whichever request has the LEAST deadline slack — it is the
        one most likely to miss anyway; requests without deadlines have
        infinite slack and are never shed."""
        if callable(self.shed_policy):
            return self.shed_policy(self, new_req)
        if self.shed_policy == "reject":
            return None
        if self.shed_policy == "deadline":
            now = self._clock()

            def slack(r: Request) -> float:
                dls = [d for d in (r.ttft_deadline_s, r.deadline_s)
                       if d is not None]
                if not dls:
                    return float("inf")
                return min(dls) - (now - r.submit_t)

            if not self.queue:
                return None
            victim = min(self.queue, key=slack)
            return victim if slack(victim) < slack(new_req) else None
        raise ValueError(f"unknown shed_policy {self.shed_policy!r}")

    def _drop_queued(self, req: Request, status: RequestStatus, error: str):
        """Remove a queued request and retire it terminally (shed/cancel/
        deadline); logged so crash replay re-applies the drop."""
        self.queue.remove(req)
        req.status = status
        req.error = error
        req.done_t = self._clock()
        if req.length < 0:
            req.length = len(req.tokens)
        self.finished[req.rid] = req
        self._log.append(("drop", req.rid, status.value, error))

    def cancel(self, rid: int) -> bool:
        """Cancel a request by id: queued requests leave the queue, LIVE
        requests retire immediately (slot + pages freed, partial tokens
        kept). Returns False if the rid is unknown or already terminal."""
        for r in self.queue:
            if r.rid == rid:
                self._drop_queued(r, RequestStatus.CANCELLED, "cancelled")
                return True
        for slot, r in enumerate(self.slot_req):
            if r is not None and r.rid == rid:
                self._retire(slot, RequestStatus.CANCELLED, "cancelled")
                self._log.append(("drop", rid,
                                  RequestStatus.CANCELLED.value, "cancelled"))
                return True
        return False

    @property
    def pending(self) -> bool:
        return bool(self.queue) or bool(self.live.any())

    @property
    def free_pages(self) -> int:
        """Free pages in the pool (paged mode; contiguous reports 0)."""
        return self.alloc.free_pages if self.paged else 0

    def _record_token(self, req: Request, tok: int, t_idx: int) -> bool:
        """Append a generated token; returns True when the request is done
        (eos — possibly on its very FIRST decoded token — or max_new).
        Emission is exactly-once: tokens at an index below the request's
        watermark (regenerated during crash replay) are recorded but NOT
        re-emitted through ``on_token``."""
        req.tokens.append(tok)
        idx = len(req.tokens) - 1
        if idx >= self.emitted.get(req.rid, 0):
            self.emitted[req.rid] = idx + 1
            if self.on_token is not None:
                self.on_token(req.rid, idx, tok)
        if req.eos_id is not None and tok == req.eos_id:
            req.length = t_idx
            return True
        if t_idx + 1 >= req.max_new:
            req.length = req.max_new
            return True
        return False

    def _retire(self, slot: int, status: RequestStatus = RequestStatus.OK,
                error: str = ""):
        req = self.slot_req[slot]
        req.done_t = self._clock()
        req.slot = -1
        req.status = status
        req.error = error
        if req.length < 0:
            req.length = len(req.tokens)
        self.finished[req.rid] = req
        self.slot_req[slot] = None
        self.live[slot] = False
        if self.paged:
            # pages back to the free list; the zeroed table row steers any
            # write from this (now dead) decode row into the null page
            self.alloc.free_slot(slot)
            self.block_tables[slot] = 0

    # -- deadlines ----------------------------------------------------------

    def _expire_queued(self):
        now = self._clock()
        for r in list(self.queue):
            age = now - r.submit_t
            if r.ttft_deadline_s is not None and age > r.ttft_deadline_s:
                self._drop_queued(r, RequestStatus.EXPIRED,
                                  f"ttft deadline {r.ttft_deadline_s:.3f}s "
                                  f"exceeded in queue")
                self.expired += 1
            elif r.deadline_s is not None and age > r.deadline_s:
                self._drop_queued(r, RequestStatus.EXPIRED,
                                  f"deadline {r.deadline_s:.3f}s exceeded "
                                  f"in queue")
                self.expired += 1

    def _expire_live(self):
        now = self._clock()
        for slot in range(self.B):
            r = self.slot_req[slot]
            if r is None or not self.live[slot]:
                continue
            if r.deadline_s is not None and now - r.submit_t > r.deadline_s:
                self._retire(slot, RequestStatus.EXPIRED,
                             f"deadline {r.deadline_s:.3f}s exceeded "
                             f"after {len(r.tokens)} tokens")
                self.expired += 1

    # -- admission ----------------------------------------------------------

    def _gather_admissions(self) -> List[Tuple[int, Request]]:
        """Pop queued requests (FIFO) into free slots, gating on the free-
        page budget in paged mode. Pages are claimed here, before the
        stacked chunk call, so the batch can never oversubscribe the pool.
        Admission stays in arrival order: when the head does not fit, we
        wait for pages rather than admitting around it."""
        k = self.admit_k or self.B
        free = [s for s in range(self.B) if not self.live[s]
                and self.slot_req[s] is None]
        pairs: List[Tuple[int, Request]] = []
        while self.queue and free and len(pairs) < k:
            req = self.queue[0]
            budget = len(req.prompt) + req.max_new
            if self.paged:
                if not self.alloc.can_admit(budget):
                    break
                slot = free.pop(0)
                pages = self.alloc.allocate(slot, budget)
                row = np.zeros((self.max_blocks,), np.int32)
                row[:len(pages)] = pages
                self.block_tables[slot] = row
            else:
                slot = free.pop(0)
            self.queue.popleft()
            pairs.append((slot, req))
        return pairs

    def _admit_batch(self, pairs: List[Tuple[int, Request]]):
        """Chunked prefill of every (slot, request) pair in ONE stacked call
        per chunk step: per-row offsets and tail masks keep rows exact, rows
        whose prompt already ended ride along as identity rows (their K/V
        writes are masked — paged: steered to the null page). Each request's
        first generated token comes from its LAST chunk's logits row.

        The stacked row count is padded UP to the next power of two using
        leftover FREE slots as all-identity parking rows (valid_len 0, so
        a parking row only scribbles on a free slot's region — scrubbed at
        its next admission anyway — or the null page): distinct XLA
        compiles stay O(log slots) instead of one per admission count."""
        C = self.chunk
        A = len(pairs)
        taken = {s for s, _ in pairs}
        parking = [s for s in range(self.B)
                   if not self.live[s] and self.slot_req[s] is None
                   and s not in taken]
        n_pad = min(len(parking),
                    (1 << max(0, A - 1).bit_length()) - A)
        slots = np.array([s for s, _ in pairs] + parking[:n_pad], np.int32)
        plens = np.array([len(r.prompt) for _, r in pairs] + [0] * n_pad,
                         np.int32)
        nchunks = np.maximum(1, -(-plens // C))
        with self.tracer.span("serve.admit", rows=A, pad_rows=n_pad,
                              chunks=int(nchunks.max()),
                              prompt_tokens=int(plens.sum())):
            first_tok, row_ok = self._prefill_chunks(pairs, slots, plens,
                                                     nchunks)
            self._seat(pairs, plens, first_tok, row_ok)
        return pairs

    def _prefill_chunks(self, pairs, slots, plens, nchunks):
        """The stacked chunk calls of one admission round; returns each
        row's first token and whether its last chunk's logits were
        finite."""
        C = self.chunk
        A = len(slots)
        fn = self.prefill["jit"]
        first_tok = np.zeros((A,), np.int32)
        row_ok = np.ones((A,), bool)
        for j in range(int(nchunks.max())):
            valids = np.clip(plens - j * C, 0, C).astype(np.int32)
            with self.tracer.span("serve.admit.chunk", rows=A,
                                  valid_tokens=int(valids.sum())):
                toks = np.zeros((A, C), np.int32)
                for a, (_, r) in enumerate(pairs):
                    part = r.prompt[j * C:(j + 1) * C]
                    toks[a, :len(part)] = part
                offs = np.full((A,), j * C, np.int32)
                args = (self.params, self.cache, jnp.asarray(toks),
                        jnp.asarray(offs), jnp.asarray(valids),
                        jnp.asarray(slots))
                if self.paged:
                    bt = jnp.asarray(self.block_tables[slots])
                    logits, self.cache = fn(*args, bt)
                else:
                    logits, self.cache = fn(*args)
                nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
                finite = np.asarray(jnp.isfinite(logits).all(axis=-1))
            last = nchunks == j + 1
            first_tok[last] = nxt[last]
            row_ok[last] = finite[last]
        return first_tok, row_ok

    def _seat(self, pairs, plens, first_tok, row_ok):
        """Admitted requests take their slots and record their first
        token (or are quarantined)."""
        now = self._clock()
        for a, (slot, req) in enumerate(pairs):
            req.slot = slot
            req.status = RequestStatus.RUNNING
            if req.first_token_t <= 0:              # preserve TTFT on replay
                req.first_token_t = now
            self.slot_req[slot] = req
            self.pos[slot] = int(plens[a])
            self.last_tok[slot] = int(first_tok[a])
            self.live[slot] = True
            if not row_ok[a]:
                # non-finite prefill logits: quarantine THIS request only;
                # its garbage first token is never recorded
                self._retire(slot, RequestStatus.QUARANTINED,
                             "non-finite prefill logits")
                self.quarantined += 1
            elif self._record_token(req, int(first_tok[a]), 0):
                self._retire(slot)                # finished on token 0

    # -- the scheduler step -------------------------------------------------

    def step(self) -> bool:
        """One scheduler iteration: fault hooks fire first, then queued
        deadline expiry, queue refill (one stacked chunk-admission call,
        gated on the free-page budget when paged), one decoded token per
        live slot (non-finite rows quarantined), live deadline expiry, and
        a periodic snapshot. On a step failure the engine recovers
        (restore + replay) when ``auto_recover`` is on, re-raising only
        after ``max_restarts`` consecutive failures. Returns whether any
        work remains."""
        self.step_idx += 1
        t0 = self._clock()
        try:
            self._step_inner()
        except RejectedRequest:
            raise
        except Exception as e:
            self.failures += 1
            self._consec_failures += 1
            if not self.auto_recover or \
                    self._consec_failures > self.max_restarts:
                self._fail_all(e)
                raise
            self._recover(e)
            return self.pending
        self._consec_failures = 0
        self.monitor.observe(self.step_idx, self._clock() - t0)
        return self.pending

    def _step_inner(self):
        if self.faults is not None:
            self.faults.begin_step(self)   # latency / pressure / crash hook
        if self.role != "decode":
            self.prefill_step()
        if self.role != "prefill":
            self.decode_step()
        self._after_phases()
        if self.ckpt is not None and self.snapshot_every and \
                self.step_idx % self.snapshot_every == 0:
            self.snapshot()

    # -- worker API: the two phases of step(), callable separately ----------

    def prefill_step(self) -> List[Tuple[int, Request]]:
        """The admission phase of one scheduler iteration: queued-deadline
        expiry, then ONE stacked chunk-admission call (free-page gated
        when paged). Returns the admitted (slot, request) pairs. This is
        the entire step of a ``role="prefill"`` worker."""
        self._expire_queued()
        pairs = self._gather_admissions()
        if pairs:
            self._admit_batch(pairs)
        return pairs

    def decode_step(self) -> int:
        """The decode phase of one scheduler iteration: one decoded token
        per live slot (non-finite rows quarantined), then live-deadline
        expiry. Returns how many rows decoded. This is the entire step of
        a ``role="decode"`` worker."""
        n = int(self.live.sum())
        if n:
            self._decode_once()
        self._expire_live()
        return n

    def _after_phases(self):
        """Post-phase hook between the scheduler phases and the periodic
        snapshot — the PrefillWorker overrides this to export finished
        prefills as page-migration handoffs. Base engine: no-op."""

    def _decode_once(self):
        counts = {"live": int(self.live.sum()), "slots": self.B}
        if self.paged:
            # the K/V pages each layer's decode attention reads: every live
            # slot's pages up to the one holding its position
            counts["kv_pages"] = int(
                (self.pos[self.live] // self.page_size + 1).sum())
        with self.tracer.span("serve.decode", **counts):
            with self.tracer.span("serve.decode.inputs"):
                args = (self.params, self.cache,
                        jnp.asarray(self.last_tok[:, None]),
                        jnp.asarray(self.pos), jnp.asarray(self.live))
                if self.paged:
                    args += (jnp.asarray(self.block_tables),)
            with self.tracer.span("serve.decode.call"):
                nxt, logits, self.cache = self.decode["jit"](*args)
            with self.tracer.span("serve.decode.readback"):
                nxt = np.asarray(nxt)[:, 0]
            # per-row health: a poisoned request must retire alone instead
            # of taking the engine (or its batch neighbours) down
            with self.tracer.span("serve.decode.finite"):
                row_ok = np.asarray(jnp.isfinite(logits).all(axis=-1))
            with self.tracer.span("serve.decode.emit"):
                self._emit(nxt, row_ok)

    def _emit(self, nxt, row_ok):
        """Record each live slot's decoded token (quarantining rows whose
        logits were not finite) and retire finished requests."""
        poisoned = (set(self.faults.poison_rows(self))
                    if self.faults is not None else set())
        for slot in range(self.B):
            if not self.live[slot]:
                continue
            req = self.slot_req[slot]
            if slot in poisoned or not row_ok[slot]:
                self._retire(slot, RequestStatus.QUARANTINED,
                             f"non-finite logits after {len(req.tokens)} "
                             f"tokens")
                self.quarantined += 1
                continue
            self.pos[slot] += 1
            self.last_tok[slot] = int(nxt[slot])
            if self._record_token(req, int(nxt[slot]), len(req.tokens)):
                self._retire(slot)

    # -- page-migration handoff (disaggregated prefill/decode) --------------

    def export_handoff(self, slot: int) -> Handoff:
        """Detach a live request from this engine as a :class:`Handoff`:
        gather its written K/V page contents (and per-slot SSM carry) out
        of the pools into immutable arrays, free the slot and its pages,
        and return the record. The request is NOT retired — it continues
        on whichever engine imports the handoff; this engine forgets it
        entirely (its capacity is back immediately)."""
        if not self.paged:
            raise RuntimeError("page-migration handoff needs a paged cache")
        req = self.slot_req[slot]
        if req is None or not self.live[slot]:
            raise RuntimeError(f"export_handoff({slot}): slot is not live")
        pos = int(self.pos[slot])
        n_content = pages_for(pos, self.page_size)
        owned = self.alloc.owned(slot)
        content = jnp.asarray(np.asarray(owned[:n_content], np.int32))
        kv = []
        for e in self.cache:
            if "k" in e:     # shared page pool: gather the written prefix
                kv.append({k: jnp.take(e[k], content, axis=1)
                           for k in ("k", "v")})
            else:            # dense per-slot SSM carry: copy the slot row
                kv.append({k: e[k][:, slot] for k in e})
        hand = Handoff(rid=req.rid, req_json=_req_to_json(req), pos=pos,
                       last_tok=int(self.last_tok[slot]),
                       budget_tokens=len(req.prompt) + req.max_new,
                       pages=tuple(owned),
                       block_table=tuple(int(p) for p in
                                         self.block_tables[slot]),
                       n_content_pages=n_content, kv=tuple(kv))
        self.alloc.export_pages(slot)
        self.block_tables[slot] = 0
        self.slot_req[slot] = None
        self.live[slot] = False
        self.pos[slot] = 0
        req.slot = -1
        self.handoffs_out += 1
        self.pages_exported += n_content
        return hand

    def can_import(self, hand: Handoff) -> bool:
        """Whether :meth:`migrate` would succeed RIGHT NOW (a free slot
        and the handoff's full page budget). The router's backpressure
        gate — a False keeps the handoff queued at the router."""
        free = any(not self.live[s] and self.slot_req[s] is None
                   for s in range(self.B))
        return (self.paged and free
                and self.alloc.can_admit(hand.budget_tokens))

    def migrate(self, hand: Handoff) -> bool:
        """Import a migrated prefill into this engine: bind a free slot,
        allocate the destination page budget (``import_pages`` — fresh
        ids, handoff metadata cross-checked), scatter the content pages
        and SSM carry into the pools, and resume the request at its
        handoff position. Returns False WITHOUT side effects when no slot
        or pages are available (backpressure); raises AllocatorError only
        on a genuinely torn handoff."""
        if not self.paged:
            raise RuntimeError("page-migration handoff needs a paged cache")
        if self.role == "prefill":
            raise RuntimeError("prefill-role worker cannot import decodes")
        if not self.can_import(hand):
            return False
        slot = next(s for s in range(self.B)
                    if not self.live[s] and self.slot_req[s] is None)
        dst = self.alloc.import_pages(slot, hand.pages, hand.block_table)
        row = np.zeros((self.max_blocks,), np.int32)
        row[:len(dst)] = dst
        self.block_tables[slot] = row
        dst_content = jnp.asarray(
            np.asarray(dst[:hand.n_content_pages], np.int32))
        cache = []
        for e, h in zip(self.cache, hand.kv):
            if "k" in e:
                cache.append({k: e[k].at[:, dst_content].set(
                    h[k].astype(e[k].dtype)) for k in ("k", "v")})
            else:
                cache.append({k: e[k].at[:, slot].set(
                    h[k].astype(e[k].dtype)) for k in e})
        self.cache = tuple(cache)
        req = _req_from_json(hand.req_json)
        req.slot = slot
        req.status = RequestStatus.RUNNING
        self.slot_req[slot] = req
        self.pos[slot] = hand.pos
        self.last_tok[slot] = hand.last_tok
        self.live[slot] = True
        self.migrations_in += 1
        self.pages_imported += hand.n_content_pages
        return True

    # -- snapshot / restore / recovery --------------------------------------

    def _device_state(self) -> Dict:
        state = {"cache": self.cache, "pos": self.pos, "live": self.live,
                 "last_tok": self.last_tok}
        if self.paged:
            state["block_tables"] = self.block_tables
        return state

    def snapshot(self):
        """Commit scheduler state + KV/SSM pools atomically (one rename —
        readers never observe a torn snapshot). Clears the write-ahead
        event log: everything before this point is folded into the
        snapshot, everything after is replayable."""
        if self.ckpt is None:
            raise RuntimeError("snapshot() needs snapshot_dir")
        by_rid: Dict[int, Request] = {r.rid: r for r in self.queue}
        by_rid.update({r.rid: r for r in self.slot_req if r is not None})
        by_rid.update(self.finished)
        extra = {
            "requests": {str(rid): _req_to_json(r)
                         for rid, r in by_rid.items()},
            "queue": [r.rid for r in self.queue],
            "slots": [r.rid if r is not None else None
                      for r in self.slot_req],
            "finished": sorted(self.finished),
            "next_rid": self._next_rid,
            "alloc": self.alloc.snapshot_state() if self.paged else None,
        }
        self.ckpt.save(self.step_idx, self._device_state(), wait=True,
                       extra=extra)
        self._log = []

    def restore(self, step: Optional[int] = None):
        """Restore scheduler + cache from the latest (or a given) committed
        snapshot. The monotonic fault clock (``step_idx``) and the
        exactly-once emission ledger are NOT rolled back."""
        if self.ckpt is None:
            raise RuntimeError("restore() needs snapshot_dir")
        self.ckpt.wait()
        state, step = self.ckpt.restore(self._device_state(), step=step)
        extra = self.ckpt.load_extra(step)
        self.cache = state["cache"]
        self.pos = np.asarray(state["pos"], np.int32).copy()
        self.live = np.asarray(state["live"], bool).copy()
        self.last_tok = np.asarray(state["last_tok"], np.int32).copy()
        if self.paged:
            self.block_tables = np.asarray(state["block_tables"],
                                           np.int32).copy()
            self.alloc.restore_state(extra["alloc"])
            # injected page squeezes (negative pseudo-slots) are transient
            # memory pressure, not scheduler state — don't resurrect them
            # (the injector's own release is owns()-guarded, so this can
            # never turn into a double free)
            for s in [int(s) for s in extra["alloc"]["owned"]
                      if int(s) < 0]:
                self.alloc.free_slot(s)
        reqs = {int(rid): _req_from_json(d)
                for rid, d in extra["requests"].items()}
        self.queue = deque(reqs[rid] for rid in extra["queue"])
        self.slot_req = [reqs[rid] if rid is not None else None
                         for rid in extra["slots"]]
        self.finished = {rid: reqs[rid] for rid in extra["finished"]}
        self._next_rid = max(self._next_rid, int(extra["next_rid"]))

    def _reset_empty(self):
        """No committed snapshot: reset to the engine's initial (empty)
        state; the full event log then replays every submission."""
        self.cache = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype), self.cache)
        self.pos[:] = 0
        self.live[:] = False
        self.last_tok[:] = 0
        self.queue = deque()
        self.slot_req = [None] * self.B
        if self.paged:
            self.alloc = BlockAllocator(self.n_pages, self.page_size,
                                        self.max_blocks)
            self.block_tables = np.zeros((self.B, self.max_blocks), np.int32)

    def _replay_log(self):
        """Re-apply post-snapshot external events (submits, cancels/sheds)
        in order. Replayed submissions start from token 0 — regeneration
        is bit-identical and the emission watermark suppresses duplicates,
        so delivery stays exactly-once."""
        log, self._log = self._log, []
        for ev in log:
            if ev[0] == "submit":
                d = dict(ev[1])
                d["tokens"], d["length"] = [], -1
                d["slot"], d["first_token_t"], d["done_t"] = -1, 0.0, 0.0
                d["status"] = RequestStatus.QUEUED.value
                req = _req_from_json(d)
                self.queue.append(req)
                self._log.append(("submit", ev[1]))
            elif ev[0] == "drop":
                _, rid, status, error = ev
                self._apply_drop(int(rid), RequestStatus(status), error)

    def _apply_drop(self, rid: int, status: RequestStatus, error: str):
        for r in list(self.queue):
            if r.rid == rid:
                self._drop_queued(r, status, error)
                return
        for slot, r in enumerate(self.slot_req):
            if r is not None and r.rid == rid:
                self._retire(slot, status, error)
                self._log.append(("drop", rid, status.value, error))
                return

    def _recover(self, error: Exception):
        """Restore the last committed snapshot (or reset empty) and replay
        the event log. In-flight work resumes exactly where the snapshot
        left it; post-snapshot submissions re-enter the queue."""
        have = self.ckpt.latest_step() if self.ckpt is not None else None
        if have is not None:
            self.restore(have)
        else:
            self._reset_empty()
        self._replay_log()
        self.recoveries += 1
        print(f"[serve] step {self.step_idx} failed "
              f"({type(error).__name__}: {error}); restored snapshot "
              f"{'@step %d' % have if have is not None else '(initial)'} "
              f"+ replayed log ({self._consec_failures}/"
              f"{self.max_restarts} consecutive)")

    def _fail_all(self, error: Exception):
        """Unrecoverable engine failure: every non-terminal request reaches
        the terminal ``failed`` status so callers are never left hanging."""
        msg = f"engine failure: {type(error).__name__}: {error}"
        for r in list(self.queue):
            self._drop_queued(r, RequestStatus.FAILED, msg)
        for slot, r in enumerate(self.slot_req):
            if r is not None:
                self._retire(slot, RequestStatus.FAILED, msg)

    # -- drain / collect ----------------------------------------------------

    def run(self) -> Dict[int, Request]:
        """Drain queue + slots; returns {rid: finished Request}."""
        while self.pending:
            self.step()
        return self.finished

    def collect(self, rid: int) -> Request:
        """Pop a finished request's record. Long-running streaming servers
        must collect results (or clear ``finished``) — the engine keeps a
        reference to every uncollected request, tokens included."""
        self.emitted.pop(rid, None)
        return self.finished.pop(rid)

    # -- batch convenience wrapper -----------------------------------------

    def generate(self, prompts: Sequence[Union[Sequence[int], RequestSpec]],
                 max_new: int = 32,
                 eos_id: Optional[int] = None) -> GenerateResult:
        """Submit every prompt, run to completion, return a batch result
        (rows in submit order). More prompts than slots simply queue —
        freed slots are refilled mid-decode. Prompts may be raw token
        sequences (the kwargs apply) or per-row :class:`RequestSpec`
        values (the spec's own fields win). A malformed prompt does NOT
        abort the batch: its row comes back zeroed (length 0, status
        "rejected") with the typed exception in ``result.rejected``."""
        base_steps = self.decode_steps
        rids: List[Optional[int]] = []
        rejected: Dict[int, RejectedRequest] = {}
        widths: List[int] = []
        pre_toks = 0
        for i, p in enumerate(prompts):
            widths.append(p.max_new if isinstance(p, RequestSpec)
                          else max_new)
            try:
                rids.append(self.submit(p, max_new=max_new, eos_id=eos_id))
                pre_toks += len(p.prompt if isinstance(p, RequestSpec)
                                else p)
            except RejectedRequest as e:
                rejected[i] = e
                rids.append(None)
        self.run()
        n = len(prompts)
        width = max(widths, default=max_new)
        out = np.zeros((n, width), np.int32)
        lengths = np.zeros((n,), np.int64)
        statuses: List[str] = []
        for i, rid in enumerate(rids):
            if rid is None:
                statuses.append(RequestStatus.REJECTED.value)
                continue
            req = self.collect(rid)
            t = req.tokens[:width]
            out[i, :len(t)] = t
            lengths[i] = req.length
            statuses.append(req.status.value)
        return GenerateResult(out, lengths, prefill_tokens=pre_toks,
                              decode_steps=self.decode_steps - base_steps,
                              statuses=statuses, rejected=rejected)


# ---------------------------------------------------------------------------
# Engine construction config
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineConfig:
    """Engine construction, consolidated: the ~20 flat CLI flags of
    ``launch/serve.py`` and the duplicated keyword soup of the serving
    benchmarks, as ONE validated dataclass with the same groups the CLI
    shows (engine / paging / robustness / chaos / disagg) and ONE builder.
    ``build(model_cfg)`` returns a :class:`ServeEngine` — or, when
    ``disagg`` is set, the router/worker topology
    (:class:`~repro.serving.disagg.Router`) behind the same streaming
    API. ``add_cli_args`` / ``from_cli_args`` keep the flag names the CLI
    always had, grouped."""
    # engine
    max_seq: int = 256
    batch_size: int = 4
    chunk: int = 0
    seed: int = 0
    plan_cache: Optional[str] = None
    plan_hw: str = ""
    # paging
    page_size: int = 0
    n_pages: int = 0
    admit_k: int = 0
    # robustness
    max_queue: int = 0
    shed_policy: Union[str, Callable] = "reject"
    ttft_deadline_s: Optional[float] = None
    deadline_s: Optional[float] = None
    snapshot_dir: Optional[str] = None
    snapshot_every: int = 8
    max_restarts: int = 3
    recover: Optional[bool] = None
    # chaos (seeded fault injection; rate 0 = off)
    chaos_rate: float = 0.0
    chaos_seed: int = 0
    chaos_horizon: int = 256
    # disagg (router/worker topology; requires paging — the handoff IS
    # page migration)
    disagg: bool = False
    prefill_workers: int = 1
    decode_workers: int = 1
    prefill_slots: int = 0      # 0 = batch_size
    decode_slots: int = 0       # 0 = batch_size

    def __post_init__(self):
        for name in ("max_seq", "batch_size", "prefill_workers",
                     "decode_workers"):
            if int(getattr(self, name)) < 1:
                raise ValueError(f"{name} must be >= 1, "
                                 f"got {getattr(self, name)}")
        for name in ("chunk", "page_size", "n_pages", "admit_k",
                     "max_queue", "snapshot_every", "max_restarts",
                     "prefill_slots", "decode_slots"):
            if int(getattr(self, name)) < 0:
                raise ValueError(f"{name} must be >= 0, "
                                 f"got {getattr(self, name)}")
        if not callable(self.shed_policy) and \
                self.shed_policy not in ("reject", "deadline"):
            raise ValueError(f"shed_policy must be reject|deadline|callable,"
                             f" got {self.shed_policy!r}")
        if self.chaos_rate < 0:
            raise ValueError(f"chaos_rate must be >= 0, "
                             f"got {self.chaos_rate}")
        if self.disagg and self.page_size <= 0:
            raise ValueError(
                "disagg mode needs a paged KV cache (page_size > 0): the "
                "prefill→decode handoff is page migration")

    # -- chaos --------------------------------------------------------------

    def worker_targets(self) -> Tuple[Tuple[str, int], ...]:
        """Every (role, index) in the disagg topology, crash-target
        order."""
        return (tuple(("prefill", i) for i in range(self.prefill_workers))
                + tuple(("decode", i) for i in range(self.decode_workers)))

    def make_faults(self, role: Optional[Tuple[str, int]] = None):
        """Seeded chaos injector from the chaos group (None when the rate
        is 0). In disagg mode, crash draws target single workers and each
        worker gets a role-scoped injector over the SAME plan."""
        if self.chaos_rate <= 0:
            return None
        from repro.serving.faults import FaultInjector, FaultPlan
        plan = FaultPlan.poisson(
            self.chaos_seed, self.chaos_horizon,
            crash_rate=self.chaos_rate, nan_rate=self.chaos_rate,
            spike_rate=self.chaos_rate,
            workers=self.worker_targets() if self.disagg else ())
        return FaultInjector(plan, role=role)

    # -- the one builder ----------------------------------------------------

    def build(self, model_cfg: ModelConfig, params=None, mesh=None,
              clock: Optional[Callable[[], float]] = None,
              on_token: Optional[Callable[[int, int, int], None]] = None,
              faults="auto", tracer: Optional[Tracer] = None):
        """Construct the engine this config describes: a ServeEngine, or
        the Router topology when ``disagg`` is set. ``faults="auto"``
        derives injector(s) from the chaos group; pass an injector or
        None to override. Chaos with unset ``recover`` turns recovery
        on. ``tracer`` takes the engine's spans (a Router gives each
        worker its own, with the same sink)."""
        recover = self.recover
        if recover is None and self.chaos_rate > 0:
            recover = True
        if self.disagg:
            from repro.serving.disagg import Router   # disagg imports us
            return Router(model_cfg, self, params=params, mesh=mesh,
                          clock=clock, on_token=on_token, faults=faults,
                          tracer=tracer)
        inj = self.make_faults() if faults == "auto" else faults
        return ServeEngine(
            model_cfg, params=params, mesh=mesh, max_seq=self.max_seq,
            batch_size=self.batch_size, seed=self.seed,
            plan_cache=self.plan_cache, plan_hw=self.plan_hw,
            chunk=self.chunk, page_size=self.page_size,
            n_pages=self.n_pages, admit_k=self.admit_k,
            max_queue=self.max_queue, shed_policy=self.shed_policy,
            ttft_deadline_s=self.ttft_deadline_s, deadline_s=self.deadline_s,
            snapshot_dir=self.snapshot_dir,
            snapshot_every=self.snapshot_every,
            max_restarts=self.max_restarts, recover=recover, faults=inj,
            clock=clock, on_token=on_token, tracer=tracer)

    # -- CLI mapping --------------------------------------------------------

    @staticmethod
    def add_cli_args(ap) -> None:
        """Register the flag groups on an argparse parser (same flag
        names ``launch/serve.py`` always had, now grouped)."""
        g = ap.add_argument_group("engine")
        g.add_argument("--max-seq", type=int, default=128)
        g.add_argument("--batch", type=int, default=4,
                       help="decode slots (disagg: default per-role slots)")
        g.add_argument("--chunk", type=int, default=16,
                       help="prefill chunk length")
        g.add_argument("--seed", type=int, default=0)
        g.add_argument("--plan-cache", default=None)
        g.add_argument("--plan-hw", default="")
        g = ap.add_argument_group("paging")
        g.add_argument("--page-size", type=int, default=0,
                       help="paged KV page length (0 = contiguous cache)")
        g.add_argument("--pages", type=int, default=0,
                       help="pool size incl. null page (0 = parity)")
        g.add_argument("--admit-k", type=int, default=0,
                       help="max stacked admissions per step (0 = slots)")
        g = ap.add_argument_group("robustness")
        g.add_argument("--max-queue", type=int, default=0,
                       help="bounded queue (0 = unbounded)")
        g.add_argument("--shed", default="reject",
                       choices=["reject", "deadline"])
        g.add_argument("--ttft-deadline", type=float, default=None)
        g.add_argument("--deadline", type=float, default=None)
        g.add_argument("--snapshot-dir", default=None)
        g.add_argument("--snapshot-every", type=int, default=8)
        g.add_argument("--max-restarts", type=int, default=3)
        g = ap.add_argument_group("chaos")
        g.add_argument("--chaos", type=float, default=0.0,
                       help="per-step fault rate (0 = off)")
        g.add_argument("--chaos-seed", type=int, default=0)
        g = ap.add_argument_group("disagg")
        g.add_argument("--disagg", action="store_true",
                       help="router/worker topology (needs --page-size)")
        g.add_argument("--prefill-workers", type=int, default=1)
        g.add_argument("--decode-workers", type=int, default=1)
        g.add_argument("--prefill-slots", type=int, default=0,
                       help="slots per prefill worker (0 = --batch)")
        g.add_argument("--decode-slots", type=int, default=0,
                       help="slots per decode worker (0 = --batch)")

    @classmethod
    def from_cli_args(cls, args, chaos_horizon: int = 0) -> "EngineConfig":
        """Parsed argparse namespace → EngineConfig (flag names as
        registered by :meth:`add_cli_args`)."""
        return cls(max_seq=args.max_seq, batch_size=args.batch,
                   chunk=args.chunk, seed=args.seed,
                   plan_cache=args.plan_cache, plan_hw=args.plan_hw,
                   page_size=args.page_size, n_pages=args.pages,
                   admit_k=args.admit_k, max_queue=args.max_queue,
                   shed_policy=args.shed,
                   ttft_deadline_s=args.ttft_deadline,
                   deadline_s=args.deadline,
                   snapshot_dir=args.snapshot_dir,
                   snapshot_every=args.snapshot_every,
                   max_restarts=args.max_restarts,
                   chaos_rate=args.chaos, chaos_seed=args.chaos_seed,
                   chaos_horizon=chaos_horizon or 256,
                   disagg=args.disagg,
                   prefill_workers=args.prefill_workers,
                   decode_workers=args.decode_workers,
                   prefill_slots=args.prefill_slots,
                   decode_slots=args.decode_slots)
