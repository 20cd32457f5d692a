"""Continuous-batching serving engine on top of ``LoweredPlan``.

The missing runtime layer between the UPIR compiler and "heavy traffic":
requests enter a bounded queue (admission control), prefill one-at-a-time into
a **fixed-width decode batch** of slots, and decode advances every active slot
one token per step. When a sequence finishes, its slot is freed and refilled
from the queue on the next step — the decode batch shape never changes, so
slot recycling never re-jits.

Model dispatch goes through the **ModelFamily protocol** (``api.FamilySpec``):
capability flags decide what each family gets — ``pageable`` families may use
the paged KV layout below, ``needs_encoder_memory`` families (whisper) get a
per-slot encoder-memory buffer filled at admission and then serve through the
same slot loop as everyone else, ``stateful_cache`` families ride the dense
layout. Per-request decode policy is ``SamplingParams`` + ``eos_id``
(``runtime.sampling``): sampling runs on-device with per-slot PRNG keys
snapshotted at admission (so eviction-by-recompute replays sampled streams
exactly), and EOS completion is a device-side finished mask — the hot loop
still never syncs (the host polls the mask every ``eos_poll_every`` steps,
only while an EOS-carrying request is active).

Two KV-cache layouts (``EngineConfig.kv_layout``):

  * ``dense`` — one ``[slots, max_seq]`` block per layer; every admitted
    request implicitly reserves the full horizon.
  * ``paged`` — a ``[num_pages, page_size]`` physical pool per layer plus a
    per-slot page table and a free-list allocator (``PagedKVAllocator``).
    Sequences hold only the pages they have actually reached, so admission
    **overcommits**: a request is admitted when its *prompt* pages are free,
    not when its worst-case horizon is. If the pool truly runs dry mid-decode
    the newest-admitted sequence is evicted (pages freed, request requeued at
    the front; decode — greedy or sampled — is a deterministic function of
    the request's snapshotted PRNG key and position, so recomputation
    reproduces the same stream). Decode gathers K/V through the page table
    per ``EngineConfig.decode_kernel``: the XLA path gathers only the live
    blocks of 128 tokens (``models.layers.attention_decode_live``), at the
    smallest width of a ladder (``decode_block_ladder``) that holds the
    active slots' blocks; the Pallas kernel (``kernels/paged_attention``)
    streams every page of every row.

Paged mode also enables **chunked prefill** (``prefill_chunk > 0``): prompts
prefill page-aligned chunk by chunk, one chunk per engine step, interleaved
with decode — long prompts stop stalling the decode batch, which is what
drops tail time-to-first-token at depth. Each chunk gathers only the pages
already holding context (bucketed by powers of two), not the full per-slot
horizon.

``EngineConfig.prefix_cache`` (paged mode only) turns on **automatic prefix
caching**: at admission the padded prompt's page-aligned prefix chunks are
hashed into a chain (token ids + page geometry + plan fingerprint —
``PrefixIndex``), looked up in a ref-counted page index, and hits are mapped
straight into the new request's page table (``PagedKVAllocator.share``)
instead of being re-prefilled — identical system prompts across slots share
physical KV pages and skip their prefill compute; only the unseen suffix
runs a forward pass. Shared pages are **copy-on-write**: a slot that must
write into a shared page (decode or speculative verify reaching a
partially-filled tail page) first duplicates it (``cache_copy_pages``), so
the cached original stays byte-identical for its other readers. Sharing is
bitwise-invisible to token streams — greedy streams with the cache on equal
the sharing-disabled paged engine exactly. The ``share``/``cow`` memory ops
and the ``mm(shared_prefix)`` annotation are part of the UPIR program, so a
sharing-enabled engine fingerprints (and plan-caches) apart from a plain
paged one.

``EngineConfig.spec_decode`` switches the decode loop into **speculative
mode** (``runtime.speculative``): a draft family proposes ``lookahead_k``
tokens per slot per step, the target verifies all k+1 positions in one
batched call, and a lossless rejection sampler accepts a prefix — greedy
streams stay bitwise identical to this engine's plain decode loop, sampled
streams keep exact eviction-by-recompute replay, and each step emits 1 to
k+1 tokens per slot. Speculative mode is the one engine path that syncs per
step (the host must learn the acceptance counts to advance positions).

**Fault tolerance** (``runtime.faults``): an engine built with a
``fault_plan``, ``nan_guard``, or ``watchdog_ms`` runs in fault-tolerant
mode. Detection is three-pronged — a device-side finite guard on decode
logits (sticky ``poisoned`` mask, polled on the EOS cadence so the hot loop
still never syncs), a per-iteration wall-clock watchdog that converts a
stalled step into a fault, and allocator/page-table invariant checks each
tick under ``debug_checks``. Recovery reuses eviction-by-recompute: a
faulted request is quarantined (slot and pages released, partial stream
dropped) and re-admitted with its PRNG key snapshot intact, so the replayed
stream is bitwise the fault-free one; retries are bounded with exponential
backoff in admission order, and exhaustion terminates the request ``failed``
with a typed ``FailureInfo`` — never a crash. ``Engine.snapshot()`` /
``restore()`` copy the full serving state (KV pool, page tables, allocator,
requests, admission counters) to host buffers and back for crash-restart
resume, rendered into the UPIR program as ``upir.memory_snapshot`` /
``upir.memory_restore`` MemOps under ``mm(fault_tolerant)`` — FT plans
fingerprint (and plan-cache) apart. Graceful degradation: ``max_queue``
bounds admission with a typed ``REJECTED_QUEUE_FULL``, ``enforce_deadlines``
sheds queued requests whose TTFT deadline already passed (typed
``SHED_DEADLINE``), and a speculative engine under pool pressure drops its
lookahead reservation (degraded mode, greedy workloads only — where spec and
plain streams are bitwise identical) before resorting to eviction.

All compiled artifacts route through ``core.lower.PlanCache``; the paged page
geometry is part of the UPIR program (``paged_kv_alloc`` data attributes +
``alloc_pages``/``free_pages`` MemOps), so it participates in the canonical
``program_fingerprint`` and therefore the cache key.

Engine events and stats flow through the same trace machinery the pass
pipeline uses: a list of dicts, one per event, interleaved with the per-pass
entries that ``run_pipeline`` appends when the plan is first compiled.
"""
from __future__ import annotations

import bisect
import copy
import dataclasses
import hashlib
import time
import warnings
from collections import OrderedDict, deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig, ShapeCfg
from ..core.lower import PlanCache, default_plan_cache
from ..models import api
from ..models.api import KernelSpec
from ..models.layers import (block_pages, cache_copy_pages, cache_write_pages,
                             decode_block_ladder, row_blocks)
from .faults import (EngineSnapshot, FailureInfo, FaultPlan, FaultSpec,
                     InjectedFault, note_failure, note_quarantine, note_retry)
from .sampling import (GREEDY, SamplingParams, decode_select, poison_and_guard,
                       request_key, sample_tokens)
from .scheduling import (FIFO, SchedulerState, SchedulingPolicy,
                         backoff_eligible, note_preemption, select_index,
                         victim as policy_victim, wants_preemption)
from .speculative import SpecConfig, SpeculativeDecoder
from .telemetry import Telemetry, span
from .tiered import HostPagePool


def snapshot(host: np.ndarray) -> jax.Array:
    """Upload host state that the engine keeps mutating in place.

    ``jnp.asarray`` of a NumPy array may read the host buffer after it
    returns: jaxlib's CPU client finishes the transfer on a worker thread
    when the backend is busy with dispatched steps. An in-place update right
    after the call (``pos[i] += 1``, a page-table write, an admission's
    policy row) can then leak into the step that was just dispatched. A
    private copy that nothing else references makes the device value a
    snapshot of the host state at dispatch time."""
    return jnp.asarray(np.array(host, copy=True))


# The engine's compiled programs and their roles, keyed by the name of the
# function each builder hands to ``jax.jit``. XLA names a program's module
# ``jit_<name>``, and a device profile attributes time to a role by that
# module name, so these function names are part of the engine's interface:
# a rename is an edit here (the encoder ``enc`` and the prefix-hit sampler
# ``fn`` are not attributed).
PROGRAM_ROLES: Dict[str, str] = {
    "step": "decode",       # _build_decode, _build_decode_paged
    "chunk": "prefill",     # _build_chunk_prefill
    "pre": "prefill",       # _prefill_fn: one-shot prefill of a bucket
    "sfx": "prefill",       # _build_suffix_prefill: after a prefix hit
    "ins": "cache",         # _build_page_insert
    "insert": "cache",      # _build_insert (api.build_cache_insert)
    "cp": "cache",          # _build_page_copy: copy-on-write
    "up": "cache",          # _build_page_upload: tiered page-in
    "xfer": "cache",        # _build_kv_transfer: prefill -> decode pool
}

# ----------------------------------------------------------------- requests


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    """What the caller wants generated — the validated, immutable half of a
    request, with no engine state attached.

    This is the single front door: ``Engine.submit(spec)`` (and
    :func:`serve_sequential`) materialize a :class:`Request` from it, running
    the engine-dependent checks (vocab range, encoder-input capability,
    speculative-mode restrictions) at that point. The legacy
    ``Engine.make_request(prompt, max_new_tokens, ...)`` kwarg pile survives
    as a deprecated shim over this class.

    Scheduling fields (consumed by ``runtime.scheduling``):

    * ``tenant`` — fairness accounting bucket for the ``fair`` policy
      (weighted per-tenant service); any non-empty string.
    * ``priority_class`` — integer class for the ``priority`` policy; higher
      admits first and may preempt strictly-lower running classes.
    * ``deadline_ms`` — TTFT service-level objective in milliseconds; purely
      observational (the engine reports per-class SLO attainment, it does not
      drop late requests).
    """

    prompt: Tuple[int, ...]
    max_new_tokens: int
    sampling: Optional[SamplingParams] = None
    eos_id: Optional[int] = None
    encoder_input: Any = None
    tenant: str = "default"
    priority_class: int = 0
    deadline_ms: Optional[float] = None

    def __post_init__(self):
        object.__setattr__(self, "prompt",
                           tuple(int(t) for t in self.prompt))
        if not self.prompt:
            raise ValueError("empty prompt: a request must carry at least "
                             "one prompt token")
        if self.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {self.max_new_tokens}")
        if not isinstance(self.tenant, str) or not self.tenant:
            raise ValueError(f"tenant must be a non-empty string, "
                             f"got {self.tenant!r}")
        if not isinstance(self.priority_class, int):
            raise ValueError(f"priority_class must be an int, "
                             f"got {self.priority_class!r}")
        if self.deadline_ms is not None and not self.deadline_ms > 0:
            raise ValueError(f"deadline_ms must be > 0, "
                             f"got {self.deadline_ms}")


@dataclasses.dataclass
class Request:
    """One generation request; build via :meth:`Engine.submit` with a
    :class:`RequestSpec` (which validates) rather than directly.
    ``tokens_out`` is filled by the engine.

    User-facing fields:

    * ``rid`` — engine-assigned request id, unique per engine. Folds into the
      request's PRNG key, so two engines fed the same workload in the same
      order produce identical sampled streams.
    * ``prompt`` — token ids; padded with zeros up to the matching
      ``EngineConfig.prompt_buckets`` entry before prefill (streams are a
      function of the *padded* prompt — the prefix cache hashes it likewise).
    * ``max_new_tokens`` — generation budget; the request finishes early only
      on ``eos_id``.
    * ``sampling`` — per-request :class:`~repro.runtime.sampling.
      SamplingParams`; ``None`` means greedy (the bitwise pre-sampling argmax
      path).
    * ``eos_id`` — finish when this token is emitted, tracked by the
      device-side finished mask (no hot-loop sync); ``None`` runs to budget.
    * ``encoder_input`` — ``[enc_seq, d_model]`` frames, required exactly for
      ``needs_encoder_memory`` families (whisper), rejected elsewhere.

    Engine-filled observability fields: ``state`` (``new | queued |
    prefilling | active | done | rejected``), ``reason`` (rejection text or
    ``"eos"``), ``bucket`` (padded prompt length), ``slot``, ``tokens_out``
    (via :meth:`Engine.finalize_request`) and the ``t_submit`` /
    ``t_admitted`` / ``t_first`` / ``t_done`` timestamps on the host's
    ``perf_counter`` clock (queue wait = ``t_admitted - t_submit``, stamped at
    the latest admission; TTFT = ``t_first - t_submit``, wall-clock exact
    only under ``run(sync_per_step=True)``).
    """

    rid: int
    prompt: Sequence[int]
    max_new_tokens: int
    sampling: Optional[SamplingParams] = None   # None = greedy
    eos_id: Optional[int] = None   # stop (device-side) on this token
    encoder_input: Any = None      # [enc_seq, D] frames (needs_encoder_memory)
    tenant: str = "default"        # fair-scheduling accounting bucket
    priority_class: int = 0        # priority policy class (higher = sooner)
    deadline_ms: Optional[float] = None   # TTFT SLO (observational)
    state: str = "new"             # new | queued | prefilling | active | done
    #                                | rejected | failed | shed
    reason: str = ""               # rejection reason / "eos" completion
    failure: Optional[FailureInfo] = None   # set on terminal FAILED
    bucket: int = 0                # padded prompt length
    slot: int = -1                 # decode slot while active
    tokens_out: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_admitted: float = 0.0        # bound to a slot (latest admission)
    t_first: float = 0.0           # first token produced (TTFT = t_first - t_submit)
    t_done: float = 0.0
    # engine-internal countdown of decode steps remaining
    _remaining: int = 0
    _first_tok: Any = None
    _admit_seq: int = 0            # monotonic admission order (eviction policy)
    _chunk_cursor: int = 0         # chunked prefill progress
    # PRNG key snapshot (uint32[2]): taken at make_request and never reset, so
    # eviction-by-recompute replays a sampled stream identically
    _key: Any = None
    # prefix-cache bookkeeping (paged + prefix_cache engines): the prompt's
    # page chain keys and how many leading pages were shared at admission —
    # kept on the request so chunked prefill can register the fresh pages
    # when its last chunk lands
    _prefix_keys: Any = None
    _prefix_hit: int = 0
    # content-addressed page chain keys cached for prefix-affinity admission
    # probes; a pure function of the padded prompt + engine salt, so never
    # reset (unlike _prefix_keys, whose hit count is admission state)
    _chain_keys: Any = None
    # fault-tolerance bookkeeping: quarantine replays consumed, and the
    # earliest engine tick at which re-admission is allowed (exponential
    # backoff — 2**(retries-1) ticks per quarantine)
    _retries: int = 0
    _not_before: int = 0
    # telemetry-enabled engines only: per-token host dispatch intervals
    # (ms) observed while this request was active — bounded by
    # max_new_tokens, same order as tokens_out itself
    _itl_ms: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine construction knobs, validated once in :class:`Engine`.

    Fields marked *[plan key]* change the UPIR decode program and therefore
    the canonical fingerprint — two engines differing in one of them never
    share a ``PlanCache`` entry; the remaining fields either enter the
    engine's derived jit keys directly (``slots``, ``max_seq``,
    ``kv_layout``, kernel knobs, speculative slack) or are pure host-side
    scheduling policy with no compiled artifact at all.

    * ``slots`` *[plan key]* — fixed decode batch width; recycling a finished
      slot never re-jits because the batch shape never changes.
    * ``max_queue`` — admission-control bound; ``None`` (the default) leaves
      the queue unbounded, an integer rejects submits beyond it with the
      typed reason ``REJECTED_QUEUE_FULL`` (counted in
      ``EngineStats.rejected_queue_full``), not buffered.
    * ``prompt_buckets`` — allowed padded prompt lengths; each bucket gets
      its own traced prefill (bounded retraces). Streams are a function of
      the *bucket-padded* prompt.
    * ``max_seq`` *[plan key]* — per-sequence horizon
      (``bucket + max_new_tokens`` must fit).
    * ``backend`` — ``"jit"`` single-process serving (the mesh path lives in
      ``runtime.server``).
    * ``keep_results`` / ``max_trace_events`` — memory bounds for long-lived
      processes (unfinalized outputs / trace ring).
    * ``eos_poll_every`` — decode steps between host polls of the
      device-side finished mask (``0`` = only truncate at finalize);
      workloads with no ``eos_id`` never sync regardless.
    * ``kv_layout`` *[plan key]* — ``"dense"`` (per-slot horizon
      reservation) or ``"paged"`` (physical page pool + page tables +
      free-list allocator; overcommit admission with
      eviction-by-recompute).
    * ``page_size`` / ``num_pages`` *[plan key]* — paged pool geometry,
      rendered as ``mm(...)`` data attributes into the program text
      (``0`` pages = ``slots * ceil(max_seq/page_size)``, i.e. no
      overcommit).
    * ``prefill_chunk`` — ``0`` = one-shot prefill; else long prompts
      prefill this many page-aligned tokens per engine step, interleaved
      with decode (chunked prefill; cuts tail TTFT under long prompts).
    * ``decode_kernel`` / ``interpret`` — paged decode attention
      implementation (``"xla"`` gather of the live blocks, or the
      ``"pallas"`` paged-attention kernel over whole rows, compiled for the
      TPU unless ``interpret``); validated here
      once as a ``KernelSpec`` and keyed into the paged jit entries.
    * ``prefix_cache`` *[plan key]* — paged mode only: automatic prefix
      caching with ref-counted page sharing and copy-on-write (adds
      ``mm(shared_prefix)`` + ``share``/``cow`` MemOps to the program).
      Bitwise-invisible to token streams.
    * ``tiered_kv`` / ``host_pages`` *[plan key]* — two-tier KV: under pool
      pressure, cold prefix-cache pages spill to a ref-counted host-memory
      page pool (``runtime.tiered.HostPagePool``) instead of being dropped,
      and a later prefix hit pages them back device←host before the chunk
      cursor reaches them. Pure movement, never recompute — streams stay
      bitwise identical to the untiered engine, but a spilled-then-hit
      prefix re-prefills zero tokens. Requires ``prefix_cache``.
      ``host_pages=0`` sizes the host tier to ``num_pages``. Adds
      ``mm(tiered(host_pages))`` + spill/page-in ``upir.kv_transfer``
      MemOps to the program, so tiered engines fingerprint apart.
    * ``disaggregated`` *[plan key]* — disaggregated prefill/decode:
      admission splits into a prefill worker (own page pool + allocator)
      and the decode worker in one process; prefilled KV hands off
      prefill→decode as an explicit ``upir.kv_transfer`` MemOp on a cache
      annotated ``mm(disaggregated)``. Streams stay bitwise identical to
      the aggregated engine. Paged only; incompatible with
      ``prefix_cache``/``tiered_kv``/``spec_decode`` (the prefill pool
      holds no shared or draft state).
    * ``spec_decode`` *[plan key]* — draft/verify speculative mode
      (:class:`~repro.runtime.speculative.SpecConfig`); the verify program
      fingerprints the draft/target pairing, and every cache layout carries
      ``lookahead_k`` slack rows.
    * ``scheduling`` *[plan key]* — declarative admission policy
      (:class:`~repro.runtime.scheduling.SchedulingPolicy`); rendered into
      the decode program as ``sched(...)``, so engines with different
      policies fingerprint apart. The default (``fifo``) is bitwise-
      compatible with the pre-policy engine. ``prefix_affinity`` requires
      ``prefix_cache=True``; ``priority`` preemption engages only on the
      paged layout (dense slots hold no pages to release).
    * ``fault_plan`` *[plan key]* — a validated
      :class:`~repro.runtime.faults.FaultPlan` of injected faults (NaN logit
      poisoning, raised exceptions, stalls, forced allocator exhaustion);
      arms the engine's fault-tolerant mode.
    * ``nan_guard`` *[plan key]* — fold the device-side finite guard into
      the decode step even without injected faults (detects *real*
      numerical faults); polled on the EOS cadence, so no new syncs.
    * ``watchdog_ms`` *[plan key]* — per-iteration wall-clock bound;
      a step exceeding it counts a ``watchdog_trips`` and quarantines the
      policy victim (a hung sequence is recovered like any other fault).
    * ``max_retries`` — quarantine replays a request may consume before it
      terminates ``failed`` with a typed ``FailureInfo``.
    * ``debug_checks`` — run allocator + page-table invariant checks every
      tick (loud ``RuntimeError`` on accounting bugs); also implies
      ``verify_ir``.
    * ``verify_ir`` — run the static verifier (``repro.analysis``) on the
      decode program at plan-build time; raises
      ``repro.analysis.VerificationError`` on any error diagnostic. A
      one-time cost per (cold) plan build — nothing in the hot loop.
    * ``enforce_deadlines`` — actually shed queued requests whose
      ``deadline_ms`` TTFT deadline has already passed (typed
      ``SHED_DEADLINE``); off by default — ``deadline_ms`` stays
      observational then, exactly as before.
    * ``telemetry`` *[plan key]* — request-lifecycle tracing + latency
      histograms (``runtime.telemetry``): lifecycle events into a bounded
      ring, TTFT/ITL/queue-delay/step histograms, Chrome-trace export.
      Host-side only — no new device syncs, token streams stay bitwise
      identical — but the instrumented program fingerprints apart
      (``mm(traced)`` + ``upir.trace_emit``), so traced and untraced
      engines never share a plan.
    * ``telemetry_events`` — event-ring capacity (steady-state telemetry
      memory is O(this); overflow drops the oldest events and counts them).
    """

    slots: int = 4                     # fixed decode batch width
    max_queue: Optional[int] = None    # admission bound (None = unbounded)
    prompt_buckets: Tuple[int, ...] = (16, 32, 64)
    max_seq: int = 128                 # per-sequence horizon
    backend: str = "jit"               # single-process jax.jit serving
    keep_results: int = 4096           # unfinalized request outputs retained
    max_trace_events: int = 10000      # trace ring bound (long-lived process)
    # ---- decode completion (EOS)
    eos_poll_every: int = 16           # decode steps between finished-mask host
    #                                    polls (0 = only truncate at finalize);
    #                                    workloads with no eos_id never sync
    # ---- paged KV cache (explicit memory management)
    kv_layout: str = "dense"           # dense | paged
    page_size: int = 16                # tokens per physical KV page
    num_pages: int = 0                 # allocatable pages; 0 = slots*ceil(max_seq/page_size)
    prefill_chunk: int = 0             # 0 = one-shot prefill; else chunk length
    decode_kernel: str = "xla"         # xla (live-block gather) | pallas (paged-attention kernel)
    interpret: bool = False            # Pallas interpreter (CPU-only runs)
    prefix_cache: bool = False         # paged only: share prompt-prefix pages
    # ---- tiered KV + disaggregated prefill/decode (runtime.tiered)
    tiered_kv: bool = False            # spill cold prefix pages to host tier
    host_pages: int = 0                # host-tier capacity; 0 = num_pages
    disaggregated: bool = False        # split prefill/decode pools + kv_transfer
    # ---- speculative decoding (draft/verify mode; runtime.speculative)
    spec_decode: Optional[SpecConfig] = None
    # ---- declarative admission scheduling (runtime.scheduling)
    scheduling: SchedulingPolicy = FIFO
    # ---- fault tolerance (runtime.faults)
    fault_plan: Optional[FaultPlan] = None   # injected-fault schedule
    nan_guard: bool = False            # finite-guard decode logits
    watchdog_ms: Optional[float] = None   # per-iteration wall-clock bound
    max_retries: int = 3               # quarantine replays before FAILED
    debug_checks: bool = False         # per-tick invariant checks
    verify_ir: bool = False            # static-verify the program at plan build
    enforce_deadlines: bool = False    # shed past-deadline queued requests
    # ---- observability (runtime.telemetry)
    telemetry: bool = False            # lifecycle tracing + latency histograms
    telemetry_events: int = 65536      # event-ring capacity (bounded memory)


# --------------------------------------------------------- free-list allocator


class PagedKVAllocator:
    """Host-side ref-counted free list over physical KV pages ``1..num_pages``.

    Page 0 is the reserved null page (``models.layers.NULL_PAGE``) — never
    handed out, so unmapped page-table entries always point somewhere
    harmless.

    ``alloc`` hands out pages with refcount 1; prefix sharing takes
    additional references on live pages (``share``) when the same physical
    page is mapped into several slots' page tables and/or retained by the
    engine's :class:`PrefixIndex`. ``free`` drops one reference and returns
    the page to the free list only when the last holder lets go — a shared
    page (refcount > 1) can therefore never be recycled, which is what makes
    "shared pages are never evicted while referenced" an allocator invariant
    rather than a scheduler promise. ``in_use`` counts *unique* pages, so
    overcommit admission and ``peak_pages`` accounting stay correct under
    aliasing (``available + in_use == total`` always holds).

    Double-free, foreign-page frees, and shares of pages not in use raise: a
    page accounting bug silently corrupts another sequence's KV, so it must
    be loud.
    """

    def __init__(self, num_pages: int):
        self.total = num_pages
        self._free: List[int] = list(range(num_pages, 0, -1))  # pop() -> low ids
        self._ref: Dict[int, int] = {}

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        """Unique pages currently allocated (aliases count once)."""
        return len(self._ref)

    @property
    def shared_pages(self) -> int:
        """Pages currently held by more than one reference."""
        return sum(1 for c in self._ref.values() if c > 1)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def alloc(self, n: int = 1) -> Optional[List[int]]:
        """``n`` pages, or None (all-or-nothing) when the pool can't cover it."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def share(self, pages: Sequence[int]) -> None:
        """Take one additional reference on each (live) page."""
        for p in pages:
            if p not in self._ref:
                raise ValueError(f"share of page {p} not in use")
        for p in pages:
            self._ref[p] += 1

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; recycle pages that reach zero."""
        for p in pages:
            c = self._ref.get(p)
            if c is None:
                raise ValueError(f"free of page {p} not in use (double free?)")
            if c == 1:
                del self._ref[p]
                self._free.append(p)
            else:
                self._ref[p] = c - 1

    def check_invariants(self) -> None:
        """Validate the allocator's internal consistency; raises
        ``RuntimeError`` on the first violation. O(pages) host work — cheap
        enough that fault-tolerant engines run it every tick under
        ``EngineConfig.debug_checks``. The invariants: the free list holds
        each page at most once and only in-range pages, no page is both free
        and live, every live refcount is >= 1, and free + live accounts for
        the whole pool (nothing leaked, nothing double-counted)."""
        if len(set(self._free)) != len(self._free):
            dupes = sorted(p for p in set(self._free)
                           if self._free.count(p) > 1)
            raise RuntimeError(f"allocator free list holds duplicates "
                               f"{dupes}")
        bad = sorted(p for p in self._free if not 1 <= p <= self.total)
        if bad:
            raise RuntimeError(f"free pages {bad} outside [1, {self.total}]")
        overlap = set(self._free) & set(self._ref)
        if overlap:
            raise RuntimeError(f"pages {sorted(overlap)} are both free and "
                               f"live")
        bad_ref = {p: c for p, c in self._ref.items()
                   if c < 1 or not 1 <= p <= self.total}
        if bad_ref:
            raise RuntimeError(f"invalid live refcounts {bad_ref}")
        if len(self._free) + len(self._ref) != self.total:
            raise RuntimeError(
                f"page accounting leak: {len(self._free)} free + "
                f"{len(self._ref)} live != {self.total} total")


class PrefixIndex:
    """Content-addressed index of prompt-prefix KV pages (prefix caching).

    Maps a *page chain key* — the SHA-256 chain over the padded prompt's
    page-sized token chunks, salted with the page geometry and the decode
    plan's canonical fingerprint — to the physical page holding that chunk's
    K/V. Causality makes this sound: the K/V content of page ``j`` is a
    deterministic function of every token up to the end of page ``j``, which
    is exactly what the chain digests.

    **Cross-bucket hashing** (``keys_for(..., real_len=...)``): chunks made
    entirely of real prompt tokens digest their full padded bytes, but the
    *boundary* chunk — the one containing the prompt's last real token, when
    the prompt is not page-aligned — digests only its real bytes. Two
    prompts therefore share the boundary key iff they have the same real
    tokens AND the same real length (different lengths digest different
    byte counts), regardless of which bucket padded them — so a short
    prompt's pages seed a longer prompt in a bigger bucket. All-padding
    chunks past the boundary continue the chain over their padded zero
    bytes: padding-page K/V at layers > 0 is *prefix-dependent* (attention
    mixes real-token state into deeper layers), so those pages stay
    chain-keyed to the exact real prefix — and keeping them in the chain
    preserves the zero-compute full hit for repeated identical prompts.

    The index holds one allocator reference per entry (taken by the engine at
    registration), so cached pages survive their originating request;
    entries whose page nobody else maps (refcount 1) are reclaimable
    LRU-first under pool pressure. Entries for complete prompts additionally
    carry the prefill's last-position logits, letting a full-prompt hit skip
    the forward pass entirely and still sample its first token bitwise
    exactly.

    **Tiered entries**: on a tiered engine, reclaim *spills* the victim to
    the host tier instead of dropping it — the entry becomes
    ``{"host": hid, "logits": ...}`` (payload in ``HostPagePool``) and a
    later hit pages it back to a device entry. An entry is on exactly one
    tier at all times; logits (tiny, device-resident) ride along untouched.
    """

    def __init__(self, page_size: int, salt: str):
        self.page_size = page_size
        self._salt = salt.encode("utf-8")
        # key -> {"page": int, "logits": Optional[device [V]]}; insertion
        # order doubles as LRU (lookups move hit chains to the MRU end)
        self._entries: "OrderedDict[bytes, Dict[str, Any]]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def keys_for(self, tokens: np.ndarray,
                 real_len: Optional[int] = None) -> List[bytes]:
        """Chain keys, one per page the padded prompt covers.

        ``real_len`` (the unpadded prompt length) makes hashing
        bucket-independent: the chunk containing position ``real_len``
        digests only its real bytes, so equal prompts padded into
        different buckets produce equal chain prefixes (see class doc).
        """
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        if real_len is None:
            real_len = len(toks)
        digest = hashlib.sha256(self._salt).digest()
        out: List[bytes] = []
        for start in range(0, len(toks), self.page_size):
            chunk = toks[start:start + self.page_size]
            if start < real_len < start + len(chunk):
                chunk = chunk[:real_len - start]   # boundary: real bytes only
            digest = hashlib.sha256(digest + chunk.tobytes()).digest()
            out.append(digest)
        return out

    def lookup(self, keys: Sequence[bytes]) -> List[int]:
        """Pages of the longest *device-resident* cached chain prefix
        (possibly empty). Host-resident entries end the chain — the tiered
        engine pages them in before calling this, so a remaining host entry
        means its page-in failed (device pool full) and the chain truncates
        there, falling back to re-prefill."""
        pages: List[int] = []
        for k in keys:
            e = self._entries.get(k)
            if e is None or "page" not in e:
                break
            self._entries.move_to_end(k)
            pages.append(e["page"])
        return pages

    def peek(self, keys: Sequence[bytes]) -> int:
        """Length of the cached chain prefix WITHOUT touching LRU order.

        The scheduler's ``prefix_affinity`` probe calls this on every queued
        request each admission round; mutating recency there would let a
        merely-probed (never admitted) chain crowd out genuinely reused ones.
        """
        n = 0
        for k in keys:
            if k not in self._entries:
                break
            n += 1
        return n

    def tail_logits(self, key: bytes):
        """Cached last-position prefill logits for a complete-prompt key."""
        e = self._entries.get(key)
        return None if e is None else e.get("logits")

    def register(self, key: bytes, page: int) -> bool:
        """Insert ``key -> page``; False if the key is already cached (the
        caller keeps its duplicate page private and takes no index ref)."""
        if key in self._entries:
            return False
        self._entries[key] = {"page": page}
        return True

    def attach_logits(self, key: bytes, logits) -> None:
        e = self._entries.get(key)
        if e is not None and e.get("logits") is None:
            e["logits"] = logits

    def pop_reclaimable(self, allocator: PagedKVAllocator) -> Optional[int]:
        """Drop the LRU device entry whose page nobody else holds; returns
        the page (caller frees it) or None when every cached device page is
        still mapped. Host-resident entries hold no device page and are
        never reclaim victims."""
        victim = next((k for k, e in self._entries.items()
                       if "page" in e and allocator.refcount(e["page"]) == 1),
                      None)
        if victim is None:
            return None
        return self._entries.pop(victim)["page"]

    # ------------------------------------------------- tiered (host) entries

    def pop_spillable(self, allocator: PagedKVAllocator
                      ) -> Optional[Tuple[bytes, Dict[str, Any]]]:
        """Pop the LRU device entry whose page nobody else maps — the spill
        victim. Returns ``(key, entry)`` so the caller can move the page
        bytes to the host tier and re-insert via :meth:`insert_host`, or
        None when nothing is spillable."""
        victim = next((k for k, e in self._entries.items()
                       if "page" in e and allocator.refcount(e["page"]) == 1),
                      None)
        if victim is None:
            return None
        return victim, self._entries.pop(victim)

    def insert_host(self, key: bytes, hid: int, logits=None) -> None:
        """Insert a spilled entry as host-resident (payload lives in the
        HostPagePool under ``hid``; logits stay device-resident)."""
        e: Dict[str, Any] = {"host": hid}
        if logits is not None:
            e["logits"] = logits
        self._entries[key] = e

    def host_entry(self, key: bytes) -> Optional[int]:
        """Host page id if this key's entry is host-resident, else None."""
        e = self._entries.get(key)
        return None if e is None or "host" not in e else e["host"]

    def commit_page_in(self, key: bytes, page: int) -> None:
        """Flip a host-resident entry back to a device entry (the page-in
        upload succeeded; the index now holds the one allocator ref)."""
        e = self._entries[key]
        del e["host"]
        e["page"] = page
        self._entries.move_to_end(key)

    def host_ids(self) -> List[int]:
        """All host page ids currently referenced by the index (for
        invariant checks: each must be live in the HostPagePool)."""
        return [e["host"] for e in self._entries.values() if "host" in e]


# -------------------------------------------------------------------- stats


@dataclasses.dataclass
class EngineStats:
    """Typed snapshot of the engine's counters (``Engine.stats()``).

    Replaces the former ad-hoc dict. Sections that don't apply to the
    engine's configuration — paged-KV, prefix-cache, speculative — leave
    their fields ``None``. A read-only mapping view (``keys`` /
    ``__getitem__`` / ``get`` / ``items`` / ``in`` / ``**unpacking``) is
    provided for backward compatibility and skips ``None`` fields, so
    ``dict(stats)`` looks exactly like the old dict did.

    ``tokens_per_s`` is decode-only throughput: ``tokens_generated`` counts
    decode-loop tokens (each request's first token comes from prefill logits
    and is tallied in ``prefill_tokens`` instead).
    """

    # ---- always present
    queue_depth: int = 0
    active_slots: int = 0
    slots: int = 0
    kv_layout: str = "dense"
    capabilities: List[str] = dataclasses.field(default_factory=list)
    policy: str = "fifo"
    decode_steps: int = 0
    prefills: int = 0
    recycles: int = 0
    submitted: int = 0
    admitted: int = 0
    completed: int = 0
    eos_finished: int = 0
    rejected: int = 0
    preemptions: int = 0
    batch_occupancy: float = 0.0
    peak_concurrent: int = 0
    tokens_generated: int = 0
    prefill_tokens: int = 0
    elapsed_s: float = 0.0
    tokens_per_s: float = 0.0
    queue_depth_by_class: Dict[int, int] = dataclasses.field(
        default_factory=dict)
    slo_attained: int = 0
    slo_missed: int = 0
    slo_attainment: Optional[float] = None     # None until a deadline ends
    slo_by_class: Dict[int, float] = dataclasses.field(default_factory=dict)
    # steps whose admission left the queue waiting because every slot was
    # busy (paged engines also count a pool short of pages apart)
    admit_wait_slots: int = 0
    rejected_queue_full: int = 0   # typed REJECTED_QUEUE_FULL admissions
    shed_deadline: int = 0         # typed SHED_DEADLINE load shedding
    plan_cache: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # ---- paged section
    page_size: Optional[int] = None
    num_pages: Optional[int] = None
    pages_in_use: Optional[int] = None
    peak_pages: Optional[int] = None
    evictions: Optional[int] = None
    prefill_chunks: Optional[int] = None
    # steps whose admission left the queue waiting because the pool held
    # fewer free pages than the head's prompt plus the growth reserve
    admit_wait_pages: Optional[int] = None
    # live-block decode (XLA paged attention): blocks holding the active
    # slots' cached tokens, and blocks the dispatched widths gathered, each
    # summed over decode steps; their ratio is the share of gathered blocks
    # that hold live tokens (1.0 when every dispatched block is live)
    decode_live_blocks: Optional[int] = None
    decode_dispatched_blocks: Optional[int] = None
    decode_block_fill: Optional[float] = None
    # ---- prefix-cache section
    prefix_hits: Optional[int] = None
    prefix_full_hits: Optional[int] = None
    prefix_misses: Optional[int] = None
    prefix_hit_tokens: Optional[int] = None
    prefix_reclaimed: Optional[int] = None
    cow_copies: Optional[int] = None
    prefix_cached_pages: Optional[int] = None
    shared_pages: Optional[int] = None
    # ---- tiered-KV section (EngineConfig.tiered_kv)
    host_pages: Optional[int] = None         # host-tier capacity
    host_pages_in_use: Optional[int] = None  # live host pages right now
    spilled: Optional[int] = None            # device -> host page spills
    paged_in: Optional[int] = None           # host -> device page uploads
    # ---- disaggregated section (EngineConfig.disaggregated)
    prefill_pool_pages: Optional[int] = None  # prefill worker pool capacity
    kv_transfers: Optional[int] = None        # prefill -> decode hand-offs
    kv_transfer_pages: Optional[int] = None   # pages moved across pools
    # ---- speculative section
    spec_steps: Optional[int] = None
    lookahead_k: Optional[int] = None
    draft_arch: Optional[str] = None
    draft_proposed: Optional[int] = None
    draft_accepted: Optional[int] = None
    acceptance_rate: Optional[float] = None
    degraded_steps: Optional[int] = None     # plain-decode steps under
    #                                          pressure-degraded mode
    degraded_entries: Optional[int] = None   # times degraded mode engaged
    # ---- fault-tolerance section (fault_plan / nan_guard / watchdog_ms)
    faults_injected: Optional[int] = None
    quarantines: Optional[int] = None
    recovered: Optional[int] = None          # completed after >= 1 replay
    failed: Optional[int] = None             # retries exhausted (terminal)
    watchdog_trips: Optional[int] = None
    failures: Optional[List[FailureInfo]] = None
    # ---- telemetry section (EngineConfig.telemetry) — lifecycle event
    # counts plus p50/p95/p99 latency-histogram summaries, per-class TTFT
    # included (see runtime.telemetry.Telemetry.section)
    telemetry: Optional[Dict[str, Any]] = None

    # ---- mapping view (backward compatibility with the former dict)
    def keys(self) -> List[str]:
        return [f.name for f in dataclasses.fields(self)
                if getattr(self, f.name) is not None]

    def __getitem__(self, key: str) -> Any:
        try:
            v = getattr(self, key)
        except AttributeError:
            raise KeyError(key) from None
        if v is None:
            raise KeyError(key)
        return v

    def __contains__(self, key: str) -> bool:
        return key in self.keys()

    def get(self, key: str, default: Any = None) -> Any:
        v = getattr(self, key, None)
        return default if v is None else v

    def items(self):
        return [(k, getattr(self, k)) for k in self.keys()]

    def to_dict(self) -> Dict[str, Any]:
        return dict(self.items())


# ------------------------------------------------------------------- engine


class Engine:
    """Slot-based continuous-batching engine over the ModelFamily protocol.

    Dispatch is capability-driven (``api.FamilySpec``): pageable families may
    use the paged KV layout, ``needs_encoder_memory`` families get a per-slot
    encoder-memory buffer filled at admission, and stateful families serve
    through the dense layout. All knobs — including the paged decode kernel
    choice — are validated here, once, at construction.
    """

    def __init__(self, cfg: ArchConfig, ecfg: EngineConfig = EngineConfig(), *,
                 params=None, key=None, plan_cache: Optional[PlanCache] = None,
                 trace: Optional[list] = None, draft_params=None):
        self.cfg = cfg
        self.ecfg = ecfg
        self.spec = api.family_spec(cfg)
        if ecfg.kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', "
                             f"got {ecfg.kv_layout!r}")
        if ecfg.eos_poll_every < 0:
            raise ValueError("eos_poll_every must be >= 0")
        self.paged = ecfg.kv_layout == "paged"
        self.prefix_cache = bool(ecfg.prefix_cache)
        if self.prefix_cache and not self.paged:
            raise ValueError("prefix_cache requires kv_layout='paged': "
                             "prefix sharing is page aliasing, and the dense "
                             "layout has no pages to alias")
        # tiered KV + disaggregated prefill/decode (runtime.tiered)
        self.tiered = bool(ecfg.tiered_kv)
        self.disagg = bool(ecfg.disaggregated)
        if ecfg.host_pages < 0:
            raise ValueError(f"host_pages must be >= 0 (0 = num_pages), "
                             f"got {ecfg.host_pages}")
        if self.tiered and not self.prefix_cache:
            raise ValueError("tiered_kv requires prefix_cache=True: the host "
                             "tier holds spilled prefix-cache pages, and "
                             "without the index there is nothing to spill")
        if self.disagg:
            if not self.paged:
                raise ValueError("disaggregated requires kv_layout='paged': "
                                 "the prefill->decode hand-off is a "
                                 "page-granular kv_transfer")
            if self.prefix_cache or self.tiered:
                raise ValueError("disaggregated is incompatible with "
                                 "prefix_cache/tiered_kv: the prefill pool "
                                 "is private per admission and holds no "
                                 "shared pages to cache or spill")
            if ecfg.spec_decode is not None:
                raise ValueError("disaggregated is incompatible with "
                                 "spec_decode: the draft cache is not split "
                                 "across prefill/decode pools")
        self.policy = ecfg.scheduling
        if not isinstance(self.policy, SchedulingPolicy):
            raise ValueError(f"scheduling must be a SchedulingPolicy, "
                             f"got {self.policy!r}")
        if self.policy.prefix_affinity and not self.prefix_cache:
            raise ValueError("prefix_affinity scheduling requires "
                             "prefix_cache=True: affinity admits requests "
                             "whose page chains hit the PrefixIndex first, "
                             "and without the index there is nothing to hit")
        self._sched_state = SchedulerState(self.policy)
        # speculative mode: the verify step writes K/V up to lookahead_k
        # positions past the last accepted token, so every cache layout
        # carries that many slack rows past the admission horizon
        self.spec_cfg = ecfg.spec_decode
        self._slack = self.spec_cfg.lookahead_k if self.spec_cfg else 0
        # fault tolerance: any of fault_plan / nan_guard / watchdog_ms arms
        # the recovery machinery; the mode changes the program's memory
        # contract (mm(fault_tolerant) + snapshot/restore MemOps), so FT
        # engines fingerprint — and plan-cache — apart
        self.fault_plan = ecfg.fault_plan
        if self.fault_plan is not None \
                and not isinstance(self.fault_plan, FaultPlan):
            raise ValueError(f"fault_plan must be a FaultPlan, "
                             f"got {self.fault_plan!r}")
        if ecfg.watchdog_ms is not None and not ecfg.watchdog_ms > 0:
            raise ValueError(f"watchdog_ms must be > 0 (or None), "
                             f"got {ecfg.watchdog_ms}")
        if ecfg.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, "
                             f"got {ecfg.max_retries}")
        if ecfg.max_queue is not None and ecfg.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1 (or None = unbounded), "
                             f"got {ecfg.max_queue}")
        self.ft = (self.fault_plan is not None or ecfg.nan_guard
                   or ecfg.watchdog_ms is not None)
        self._nan_guard = ecfg.nan_guard or (
            self.fault_plan is not None
            and any(f.kind == "nan" for f in self.fault_plan.faults))
        if self._nan_guard and self.spec_cfg is not None:
            raise ValueError(
                "nan faults / nan_guard require the plain decode loop: the "
                "speculative verify step has no per-step finite-guard "
                "output (exception and stall faults still cover spec "
                "engines)")
        for f in (self.fault_plan.faults if self.fault_plan else ()):
            if f.kind == "nan" and f.slot >= ecfg.slots:
                raise ValueError(f"nan fault targets slot {f.slot}; engine "
                                 f"has {ecfg.slots} slots")
        # decode-kernel knobs live in EngineConfig and are validated once —
        # they no longer leak through every decode_step_paged call
        self._kernel = KernelSpec(attn_impl=ecfg.decode_kernel,
                                  interpret=ecfg.interpret)
        if self.paged:
            if not self.spec.pageable:
                raise api.CapabilityError(
                    f"paged KV cache: family '{self.spec.key}' does not "
                    f"declare the 'pageable' capability (no dense per-layer "
                    f"K/V cache)")
            if ecfg.page_size < 1:
                raise ValueError("page_size must be >= 1")
            if ecfg.prefill_chunk:
                if ecfg.prefill_chunk % ecfg.page_size:
                    raise ValueError("prefill_chunk must be a multiple of "
                                     "page_size (chunks write whole pages)")
                bad = [b for b in ecfg.prompt_buckets
                       if b > ecfg.prefill_chunk and b % ecfg.prefill_chunk]
                if bad:
                    raise ValueError(f"prompt buckets {bad} not divisible by "
                                     f"prefill_chunk {ecfg.prefill_chunk}")
        self.plan_cache = plan_cache if plan_cache is not None \
            else default_plan_cache()
        self.trace = trace if trace is not None else []

        # telemetry (runtime.telemetry): host-side lifecycle events +
        # latency histograms, created before the plan so the traced
        # annotation and the recording machinery can never disagree
        if ecfg.telemetry_events < 1:
            raise ValueError(f"telemetry_events must be >= 1, "
                             f"got {ecfg.telemetry_events}")
        self.telemetry = Telemetry(
            slots=ecfg.slots, max_events=ecfg.telemetry_events) \
            if ecfg.telemetry else None

        self.pages_per_slot = -(-(ecfg.max_seq + self._slack)
                                // ecfg.page_size)
        self.num_pages = (ecfg.num_pages or ecfg.slots * self.pages_per_slot) \
            if self.paged else 0
        page_geom = (self.num_pages, ecfg.page_size, self.pages_per_slot) \
            if self.paged else None
        # live-block decode: the XLA paged step gathers only the blocks that
        # hold the active slots' tokens, at the smallest width of its ladder
        # that fits them (the widths it counts in); the Pallas kernel reads
        # whole rows and has no ladder
        self._block_pages = block_pages(ecfg.page_size, self.pages_per_slot)
        self._blocks_per_row = -(-self.pages_per_slot // self._block_pages)
        self.decode_widths = decode_block_ladder(
            ecfg.slots, self._blocks_per_row) \
            if self.paged and self._kernel.attn_impl == "xla" else ()
        # host-tier capacity: 0 sizes it like the device pool
        self.host_pages = (ecfg.host_pages or self.num_pages) \
            if self.tiered else 0

        # the decode plan: UPIR program -> pass pipeline -> LoweredPlan,
        # cached by canonical fingerprint (warm engines skip re-lowering);
        # the paged page geometry is fingerprinted with it
        from . import server
        self.shape = ShapeCfg(f"engine_b{ecfg.slots}", "decode",
                              ecfg.max_seq, ecfg.slots)
        # the admission policy is part of the program: sched(...) renders
        # next to mm()/caps() and participates in the fingerprint, so two
        # engines differing only in scheduling never share a PlanCache entry
        self.plan = server.serving_plan(cfg, self.shape, backend=ecfg.backend,
                                        plan_cache=self.plan_cache,
                                        trace=self.trace,
                                        page_geometry=page_geom,
                                        prefix_sharing=self.prefix_cache,
                                        scheduling=self.policy.ext(),
                                        fault_tolerant=self.ft,
                                        traced=self.telemetry is not None,
                                        tiering=self.host_pages
                                        if self.tiered else None,
                                        disaggregated=self.disagg,
                                        verify=ecfg.verify_ir
                                        or ecfg.debug_checks)
        # the program's traced annotation and the engine's telemetry config
        # must agree (the static contract SC007/SC008 checks the same pairing)
        assert self.plan.traced == (self.telemetry is not None)
        # likewise the pool topology: mm(tiered)/mm(disaggregated) and the
        # kv_transfer machinery must travel together (SC009/SC010)
        assert (self.plan.tiering is not None) == self.tiered
        assert self.plan.disaggregated == self.disagg

        self.params = params if params is not None \
            else api.init_params(cfg, key if key is not None else jax.random.key(0))

        # draft/verify mode: the decoder owns the draft params + dense draft
        # cache and the fused draft/verify/accept step (its verify plan is a
        # first-class UPIR program carrying the draft/target pairing)
        self._spec = SpeculativeDecoder(self, self.spec_cfg,
                                        draft_params=draft_params) \
            if self.spec_cfg else None

        # _slack is in the key: spec and plain engines of the same geometry
        # bake different s_max into their prefill/insert closures and must
        # never share them through a common PlanCache
        fkey = (self.plan.fingerprint, cfg, ecfg.backend, ecfg.slots,
                ecfg.max_seq, ecfg.kv_layout, self._slack)
        if self.paged:
            fkey += (self._kernel,)
            self._decode = self.plan_cache.get_or_build(
                fkey + ("decode",), self._build_decode_paged)
            self._page_insert = self.plan_cache.get_or_build(
                fkey + ("page_insert",), self._build_page_insert)
            if ecfg.prefill_chunk:
                self._chunk_prefill = self.plan_cache.get_or_build(
                    fkey + ("chunk_prefill", ecfg.prefill_chunk),
                    self._build_chunk_prefill)
            if self.tiered:
                self._page_upload = self.plan_cache.get_or_build(
                    fkey + ("page_upload",), self._build_page_upload)
            if self.disagg:
                self._kv_transfer = self.plan_cache.get_or_build(
                    fkey + ("kv_transfer",), self._build_kv_transfer)
        else:
            self._decode = self.plan_cache.get_or_build(
                fkey + ("decode",), self._build_decode)
            self._insert = self.plan_cache.get_or_build(
                fkey + ("insert",), self._build_insert)
        self._fkey = fkey

        # mutable serving state
        if self.paged:
            self.pool = api.init_paged_cache(cfg, self.num_pages,
                                             ecfg.page_size)
            self.allocator = PagedKVAllocator(self.num_pages)
            self.page_table_np = np.zeros(
                (ecfg.slots, self.pages_per_slot), np.int32)
            self._slot_pages: List[List[int]] = [[] for _ in range(ecfg.slots)]
            if self.prefix_cache:
                # chain keys are salted with the plan fingerprint, which
                # already digests the model config, page geometry, and the
                # shared_prefix memory contract — a cache entry can never be
                # hit by a different model or geometry
                self.prefix_index = PrefixIndex(
                    ecfg.page_size, salt=f"{cfg.name}/{self.plan.fingerprint}")
                self._page_copy = self.plan_cache.get_or_build(
                    fkey + ("page_copy",), self._build_page_copy)
                self._hit_sample = self.plan_cache.get_or_build(
                    fkey + ("hit_sample",), self._build_hit_sample)
            # tiered KV: the host tier behind the device pool
            self.host_pool = HostPagePool(self.host_pages) \
                if self.tiered else None
            if self.disagg:
                # prefill worker pool: sized so every concurrent admission's
                # largest-bucket prefill fits by construction (slots bounds
                # the number of in-flight prefills)
                self.prefill_pages = ecfg.slots * self._page_count(
                    max(ecfg.prompt_buckets))
                self.prefill_pool = api.init_paged_cache(
                    cfg, self.prefill_pages, ecfg.page_size)
                self.prefill_allocator = PagedKVAllocator(self.prefill_pages)
                # same column count as page_table_np, so chunked prefill's
                # power-of-two gather widths slice both tables identically
                self.prefill_table_np = np.zeros(
                    (ecfg.slots, self.pages_per_slot), np.int32)
                self._prefill_slot_pages: List[List[int]] = \
                    [[] for _ in range(ecfg.slots)]
        else:
            self.cache = api.init_cache(cfg, ecfg.slots,
                                        ecfg.max_seq + self._slack)
        # per-slot encoder memory (needs_encoder_memory capability): filled
        # once at admission from the request's frames, read by prefill
        if self.spec.needs_encoder_memory:
            self.enc_memory = jnp.zeros(
                (ecfg.slots, cfg.encdec.enc_seq, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))
            self._encode = self.plan_cache.get_or_build(
                fkey + ("encode",), self._build_encode)
        self.tokens = jnp.zeros((ecfg.slots, 1), jnp.int32)
        self.pos = np.zeros((ecfg.slots,), np.int32)
        # per-slot decode policy, shipped to the device each step (tiny);
        # the finished mask is device-resident — EOS completion never syncs
        self.finished = jnp.zeros((ecfg.slots,), bool)
        self.keys_np = np.zeros((ecfg.slots, 2), np.uint32)
        self.temps_np = np.zeros((ecfg.slots,), np.float32)
        self.topks_np = np.zeros((ecfg.slots,), np.int32)
        self.topps_np = np.ones((ecfg.slots,), np.float32)
        self.eos_np = np.full((ecfg.slots,), -1, np.int32)
        self.presence_np = np.zeros((ecfg.slots,), np.float32)
        self.frequency_np = np.zeros((ecfg.slots,), np.float32)
        # per-slot on-device emission counts backing repetition penalties;
        # reset at (re)admission, so eviction-by-recompute rebuilds the same
        # counts trajectory and penalized streams replay exactly
        self.counts = jnp.zeros((ecfg.slots, cfg.vocab), jnp.int32)
        # fault tolerance: sticky device-side finite-guard mask, the host-
        # staged poison vector the FaultPlan arms, and degraded-mode state
        self.poisoned = jnp.zeros((ecfg.slots,), bool)
        self._poison_np = np.zeros((ecfg.slots,), bool)
        self._poison_dev = None
        self.degraded = False
        self._policy_dev = None        # device copy, rebuilt only when dirty
        self.queue: Deque[Request] = deque()
        self.slots_req: List[Optional[Request]] = [None] * ecfg.slots
        self._prefilling: Dict[int, Request] = {}
        self._slot_used = [False] * ecfg.slots
        self._toklog: List[Tuple[Any, Tuple[int, ...]]] = []
        self._pending_tokens: Dict[int, List[int]] = {}
        self._rid = 0
        self._admit_counter = 0
        self._activated: List[Request] = []
        self._sync_each_step = False
        # counters
        self.reset_stats()

    # ------------------------------------------------------------ step fns

    def _live_blocks(self, active) -> Tuple[int, int]:
        """Live KV blocks of the ``active`` slots, the count the decode
        step's block list is built from, and the ladder width the step
        gathers for it."""
        live = int(row_blocks(self.pos[active],
                              self._block_pages * self.ecfg.page_size,
                              self._blocks_per_row).sum())
        return live, self.decode_widths[
            bisect.bisect_left(self.decode_widths, live)]

    def _build_decode(self):
        cfg = self.cfg

        def step(params, cache, tokens, pos, keys, temps, topks, topps, eos,
                 fin, counts, presence, frequency, poison, bad):
            logits, cache = api.decode_step(cfg, params, cache,
                                            {"tokens": tokens, "pos": pos})
            with jax.named_scope("sample"):
                # the step's input token is the previously emitted one
                # (prefill's first token on the first step): count it before
                # selecting, so the penalty at position p sees every token
                # emitted before p
                counts = counts.at[jnp.arange(counts.shape[0]),
                                   tokens[:, 0]].add(1)
                # fault injection + sticky finite guard; all-False poison is
                # a bitwise identity, so non-FT engines pay only a fused
                # mask op
                lg, bad = poison_and_guard(logits[:, -1], poison, bad)
                nxt, fin = decode_select(lg, keys, pos, temps, topks, eos,
                                         fin, top_ps=topps, counts=counts,
                                         presence=presence,
                                         frequency=frequency)
            return nxt, fin, cache, counts, bad

        return jax.jit(step, donate_argnums=(1, 10))

    def _build_decode_paged(self):
        cfg, kernel = self.cfg, self._kernel

        def step(params, pool, page_table, tokens, pos, keys, temps, topks,
                 topps, eos, fin, counts, presence, frequency, poison, bad):
            logits, pool = api.decode_step_paged(
                cfg, params, pool, page_table,
                {"tokens": tokens, "pos": pos}, kernel=kernel)
            with jax.named_scope("sample"):
                counts = counts.at[jnp.arange(counts.shape[0]),
                                   tokens[:, 0]].add(1)
                lg, bad = poison_and_guard(logits[:, -1], poison, bad)
                nxt, fin = decode_select(lg, keys, pos, temps, topks, eos,
                                         fin, top_ps=topps, counts=counts,
                                         presence=presence,
                                         frequency=frequency)
            return nxt, fin, pool, counts, bad

        return jax.jit(step, donate_argnums=(1, 11))

    def _build_encode(self):
        cfg = self.cfg

        def enc(params, frames):
            return api.encode(cfg, params, {"audio_embeds": frames})

        return jax.jit(enc)

    def _build_page_insert(self):
        def ins(pool, k_chunk, v_chunk, page_ids):
            return {"k_pages": cache_write_pages(pool["k_pages"], k_chunk,
                                                 page_ids),
                    "v_pages": cache_write_pages(pool["v_pages"], v_chunk,
                                                 page_ids)}
        return jax.jit(ins, donate_argnums=(0,))

    def _build_chunk_prefill(self):
        cfg = self.cfg

        def chunk(params, pool, page_row, tokens, offset, page_ids, key,
                  temp, topk, topp):
            logits, (k_c, v_c) = api.prefill_chunk(
                cfg, params, pool, page_row, {"tokens": tokens}, offset)
            # only the final chunk's token is used; its sampling position is
            # the last processed position — identical to one-shot prefill's.
            # The raw last-position logits ride along so the prefix cache can
            # retain a complete prompt's logits for full-hit admissions.
            last = (offset + tokens.shape[1] - 1).astype(jnp.int32)
            nxt = sample_tokens(logits[:, -1], key[None], last[None],
                                temp[None], topk[None], topp[None])
            pool = {"k_pages": cache_write_pages(pool["k_pages"], k_c,
                                                 page_ids),
                    "v_pages": cache_write_pages(pool["v_pages"], v_c,
                                                 page_ids)}
            return nxt, logits[:, -1], pool

        return jax.jit(chunk, donate_argnums=(1,))

    def _build_suffix_prefill(self, bucket: int, offset: int):
        """Prefill only the unseen suffix ``[offset, bucket)`` of a prompt
        whose first ``offset`` tokens were served from the prefix cache: one
        ``prefill_chunk`` forward over the suffix, gathering the shared
        pages as context, writing K/V into the request's fresh pages only.
        Traced per (bucket, offset) pair — bounded by pages-per-bucket."""
        cfg, ps = self.cfg, self.ecfg.page_size
        suffix = bucket - offset
        pad = -suffix % ps     # fill the tail page (rows are position-masked)

        def sfx(params, pool, page_row, tokens, page_ids, key, temp, topk,
                topp):
            logits, (k_c, v_c) = api.prefill_chunk(
                cfg, params, pool, page_row, {"tokens": tokens},
                jnp.int32(offset))
            last = jnp.full((1,), bucket - 1, jnp.int32)
            nxt = sample_tokens(logits[:, -1], key[None], last,
                                temp[None], topk[None], topp[None])
            if pad:
                widths = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
                k_c = jnp.pad(k_c, widths)
                v_c = jnp.pad(v_c, widths)
            pool = {"k_pages": cache_write_pages(pool["k_pages"], k_c,
                                                 page_ids),
                    "v_pages": cache_write_pages(pool["v_pages"], v_c,
                                                 page_ids)}
            return nxt, logits[:, -1], pool

        return jax.jit(sfx, donate_argnums=(1,))

    def _build_page_copy(self):
        """Copy-on-write duplication: physical pages src -> dst, all layers."""
        def cp(pool, src, dst):
            return {"k_pages": cache_copy_pages(pool["k_pages"], src, dst),
                    "v_pages": cache_copy_pages(pool["v_pages"], src, dst)}
        return jax.jit(cp, donate_argnums=(0,))

    def _build_page_upload(self):
        """Tiered page-in: upload one host-resident page's K/V bytes
        (each ``[L, PS, KV, hd]``) into a device page slot. A pure memcpy —
        the page-in half of the ``upir.kv_transfer src_pool(host)`` op."""
        def up(pool, k_page, v_page, page):
            return {"k_pages": pool["k_pages"].at[:, page].set(k_page),
                    "v_pages": pool["v_pages"].at[:, page].set(v_page)}
        return jax.jit(up, donate_argnums=(0,))

    def _build_kv_transfer(self):
        """Disaggregated hand-off: copy prefilled pages src (prefill pool)
        -> dst (decode pool), all layers. The runtime half of the
        ``upir.kv_transfer src_pool(prefill) dst_pool(decode)`` op. Only the
        decode pool is donated — the prefill pool buffer is reused."""
        def xfer(dpool, ppool, src, dst):
            return {"k_pages": dpool["k_pages"].at[:, dst]
                    .set(ppool["k_pages"][:, src]),
                    "v_pages": dpool["v_pages"].at[:, dst]
                    .set(ppool["v_pages"][:, src])}
        return jax.jit(xfer, donate_argnums=(0,))

    def _build_hit_sample(self):
        """First token of a full-prompt prefix hit: sample from the *cached*
        last-position prefill logits — the same device buffer the original
        prefill produced, so greedy hits are bitwise the original argmax and
        sampled hits draw with the new request's own key/position."""
        def fn(logits, key, pos, temp, topk, topp):
            return sample_tokens(logits[None, :], key[None], pos[None],
                                 temp[None], topk[None], topp[None])
        return jax.jit(fn)

    def _build_insert(self):
        return api.build_cache_insert(self.cfg,
                                      self.ecfg.max_seq + self._slack)

    def _prefill_fn(self, bucket: int):
        cfg = self.cfg
        encdec = self.spec.needs_encoder_memory
        # paged one-shot prefill pads the cache only to the prompt's pages —
        # the whole point: a short prompt no longer reserves the horizon
        s_max = self._page_count(bucket) * self.ecfg.page_size if self.paged \
            else self.ecfg.max_seq + self._slack

        def build():
            def pre(params, tokens, memory, key, temp, topk, topp):
                batch = {"tokens": tokens}
                if encdec:
                    batch["encoder_memory"] = memory
                logits, cache = api.prefill(cfg, params, batch, s_max=s_max)
                # first-token sampling position = last processed position;
                # the raw last-position logits ride along for the prefix
                # cache's full-hit entries
                last = jnp.full((1,), tokens.shape[1] - 1, jnp.int32)
                nxt = sample_tokens(logits[:, -1], key[None], last,
                                    temp[None], topk[None], topp[None])
                return nxt, logits[:, -1], cache
            return jax.jit(pre)
        return self.plan_cache.get_or_build(
            self._fkey + ("prefill", bucket), build)

    def _run_prefill(self, req: Request, i: int):
        """One-shot prefill for ``req``: run the encoder into the slot's
        encoder-memory buffer (capability path), then prefill *from that
        buffer row* — the per-slot buffer is the source of cross-attention
        memory, not a side copy. Returns (first token [1],
        last-position logits [1, V], cache-of-one)."""
        s = req.sampling or GREEDY
        fn = self._prefill_fn(req.bucket)
        with span("engine.prefill"), span("engine.dispatch"):
            toks = jnp.asarray(self._padded_prompt(req))[None, :]
            memory = jnp.zeros((1, 0, 0), jnp.float32)   # unused placeholder
            if self.spec.needs_encoder_memory:
                mem = self._encode(self.params,
                                   jnp.asarray(req.encoder_input)[None])
                self.enc_memory = self.enc_memory.at[i].set(mem[0])
                memory = self.enc_memory[i][None]
            return fn(self.params, toks, memory, jnp.asarray(req._key),
                      jnp.float32(s.temperature), jnp.int32(s.top_k),
                      jnp.float32(s.top_p))

    def _page_count(self, tokens: int) -> int:
        return -(-tokens // self.ecfg.page_size)

    # ------------------------------------------------------------ admission

    def make_request(self, prompt: Sequence[int], max_new_tokens: int, *,
                     sampling: Optional[SamplingParams] = None,
                     eos_id: Optional[int] = None,
                     encoder_input=None, tenant: str = "default",
                     priority_class: int = 0,
                     deadline_ms: Optional[float] = None) -> Request:
        """Deprecated shim over :class:`RequestSpec` — build a spec and call
        :meth:`submit` with it instead; this kwarg pile only materializes
        one for you."""
        warnings.warn("Engine.make_request is deprecated; build a "
                      "RequestSpec and Engine.submit(spec) it instead",
                      DeprecationWarning, stacklevel=2)
        return self._materialize(RequestSpec(
            prompt=tuple(prompt), max_new_tokens=max_new_tokens,
            sampling=sampling, eos_id=eos_id, encoder_input=encoder_input,
            tenant=tenant, priority_class=priority_class,
            deadline_ms=deadline_ms))

    def _materialize(self, spec: RequestSpec) -> Request:
        """RequestSpec -> validated Request: the engine-dependent half of
        validation (vocab range, capability checks, speculative-mode
        restrictions) plus rid assignment and the PRNG key snapshot.
        Degenerate inputs raise ``ValueError`` here, loudly, instead of
        being admitted into the slot loop."""
        if spec.eos_id is not None and not 0 <= spec.eos_id < self.cfg.vocab:
            raise ValueError(f"eos_id {spec.eos_id} outside vocab "
                             f"[0, {self.cfg.vocab})")
        encoder_input = spec.encoder_input
        if self.spec.needs_encoder_memory:
            if encoder_input is None:
                raise ValueError(
                    f"family '{self.spec.key}' declares needs_encoder_memory:"
                    f" requests must carry encoder_input frames "
                    f"[{self.cfg.encdec.enc_seq}, {self.cfg.d_model}]")
            encoder_input = np.asarray(encoder_input)
            want = (self.cfg.encdec.enc_seq, self.cfg.d_model)
            if encoder_input.shape != want:
                raise ValueError(f"encoder_input shape "
                                 f"{encoder_input.shape} != {want}")
        elif encoder_input is not None:
            raise ValueError(f"family '{self.spec.key}' does not take "
                             f"encoder_input")
        if self.spec_cfg is not None and spec.sampling is not None \
                and spec.sampling.penalized:
            raise ValueError(
                "repetition penalties are not supported in speculative "
                "mode: the verify step scores k+1 positions against one "
                "count snapshot, which breaks the per-position penalty law")
        self._rid += 1
        with span("engine.readback"):     # the key is built on the device
            key = request_key(spec.sampling or GREEDY, self._rid)
        return Request(rid=self._rid, prompt=list(spec.prompt),
                       max_new_tokens=spec.max_new_tokens,
                       sampling=spec.sampling, eos_id=spec.eos_id,
                       encoder_input=encoder_input, tenant=spec.tenant,
                       priority_class=spec.priority_class,
                       deadline_ms=spec.deadline_ms,
                       _key=key)

    def submit(self, req: Union[Request, RequestSpec]):
        """Admission control: bounded queue + horizon check.

        The structured entry point: given a :class:`RequestSpec`, the engine
        materializes and enqueues a :class:`Request` and returns it (inspect
        ``req.state``/``req.reason`` for rejection). The legacy path — an
        already-materialized ``Request`` — returns ``bool``
        (False = rejected), unchanged.

        Paged mode admits on the *prompt* footprint (overcommit) — the only
        hard caps are the per-sequence horizon and the request alone fitting
        the pool; transient exhaustion is handled later by eviction.
        """
        with span("engine.submit"):
            if isinstance(req, RequestSpec):
                mat = self._materialize(req)
                self._submit(mat)
                return mat
            return self._submit(req)

    def _submit(self, req: Request) -> bool:
        req.t_submit = time.perf_counter()
        self.submitted += 1
        bucket = next((b for b in sorted(self.ecfg.prompt_buckets)
                       if b >= len(req.prompt)), None)
        if bucket is None:
            return self._reject(req, f"prompt len {len(req.prompt)} exceeds "
                                     f"largest bucket")
        if bucket + req.max_new_tokens > self.ecfg.max_seq:
            return self._reject(req, f"bucket {bucket} + {req.max_new_tokens} "
                                     f"new tokens exceeds max_seq "
                                     f"{self.ecfg.max_seq}")
        if req.max_new_tokens < 1:
            return self._reject(req, "max_new_tokens must be >= 1")
        if self.paged and \
                self._page_count(bucket + req.max_new_tokens) > self.num_pages:
            return self._reject(req, f"request needs "
                                     f"{self._page_count(bucket + req.max_new_tokens)} "
                                     f"pages; pool has {self.num_pages}")
        if self.ecfg.max_queue is not None \
                and len(self.queue) >= self.ecfg.max_queue:
            self.rejected_queue_full += 1
            return self._reject(req, "REJECTED_QUEUE_FULL")
        req.bucket = bucket
        req.state = "queued"
        self.queue.append(req)
        self.trace.append({"event": "submit", "rid": req.rid,
                           "bucket": bucket, "queue_depth": len(self.queue)})
        if self.telemetry is not None:
            self.telemetry.event("submitted", rid=req.rid, bucket=bucket,
                                 tenant=req.tenant,
                                 priority_class=req.priority_class)
        return True

    def _reject(self, req: Request, reason: str) -> bool:
        req.state, req.reason = "rejected", reason
        self.rejected += 1
        self.trace.append({"event": "reject", "rid": req.rid, "reason": reason})
        if self.telemetry is not None:
            self.telemetry.event("rejected", rid=req.rid, reason=reason)
        return False

    # ------------------------------------------------------------ serving

    def _padded_prompt(self, req: Request) -> np.ndarray:
        toks = np.zeros((req.bucket,), np.int32)
        toks[:len(req.prompt)] = np.asarray(req.prompt, np.int32)
        return toks

    def _mark_admitted(self, req: Request, i: int) -> None:
        recycled = self._slot_used[i]
        if recycled:
            self.recycles += 1
        self._slot_used[i] = True
        self._admit_counter += 1
        req._admit_seq = self._admit_counter
        req.t_admitted = time.perf_counter()
        req.slot = i
        self.admitted += 1
        # fair scheduling charges the tenant's normalized service here, at
        # admission — re-admission after eviction charges again, which is
        # correct: the recompute consumes real service
        self._sched_state.charge(req)
        # slot decode policy: PRNG key snapshot + sampling params + EOS id
        s = req.sampling or GREEDY
        self.keys_np[i] = req._key
        self.temps_np[i] = s.temperature
        self.topks_np[i] = s.top_k
        self.topps_np[i] = s.top_p
        self.eos_np[i] = -1 if req.eos_id is None else req.eos_id
        self.presence_np[i] = s.presence_penalty
        self.frequency_np[i] = s.frequency_penalty
        # zeroed at (re)admission: eviction replay rebuilds the same counts
        with span("engine.dispatch"):
            self.counts = self.counts.at[i].set(0)
        self._policy_dev = None
        self.trace.append({"event": "admit", "rid": req.rid, "slot": i,
                           "recycled": recycled})
        if self.telemetry is not None:
            self.telemetry.event("admitted", rid=req.rid, slot=i)
            if recycled:
                self.telemetry.event("recycled", rid=req.rid, slot=i)
            if req.t_submit is not None:
                self.telemetry.observe(
                    "queue_delay_ms", (req.t_admitted - req.t_submit) * 1e3)

    def _activate(self, req: Request, i: int, nxt0) -> None:
        """Prefill finished: first token is in hand, slot joins the decode
        batch (or the request completes outright for 1-token generations)."""
        self.pos[i] = req.bucket
        with span("engine.dispatch"):
            self.tokens = self.tokens.at[i, 0].set(nxt0[0])
            # reset the slot's finished bit — device-side, no sync; the
            # first token may itself be the EOS
            if req.eos_id is not None:
                self.finished = self.finished.at[i].set(
                    jnp.equal(nxt0[0], req.eos_id))
            else:
                self.finished = self.finished.at[i].set(False)
            if self._nan_guard:
                # a recycled slot must not inherit its previous occupant's
                # sticky finite-guard bit
                self.poisoned = self.poisoned.at[i].set(False)
            # the decode jit counts tokens[i, 0] for every row, so while
            # this slot sat empty or mid-chunked-prefill the batch deposited
            # phantom counts into it; zero here — not only at admission — so
            # penalized replay streams stay bitwise the sequential ones
            self.counts = self.counts.at[i].set(0)
        self.prefills += 1
        req.state = "active"
        req._first_tok = nxt0
        req._remaining = req.max_new_tokens - 1
        if self._sync_each_step:
            # latency mode: block on the first token so TTFT is stamped when
            # it actually exists, not at step end (head-of-line prefill
            # blocking inside a step stays visible)
            jax.block_until_ready(nxt0)
            req.t_first = time.perf_counter()
            self._note_first_token(req)
        self._activated.append(req)
        if req._remaining <= 0:
            req.slot = i
            self._finish(req)      # 1-token request: done at prefill
        else:
            self.slots_req[i] = req
            if self._spec is not None:
                # the draft needs its own prompt KV before it can propose
                self._spec.prefill_slot(self._padded_prompt(req), i,
                                        rid=req.rid)

    def _note_first_token(self, req: Request) -> None:
        """Telemetry at the TTFT stamp site (both the per-request sync
        branch and the batch stamp at step end route here)."""
        if self.telemetry is None:
            return
        self.telemetry.event("first_token", rid=req.rid, slot=req.slot)
        if req.t_submit is not None and req.t_first is not None:
            self.telemetry.observe_ttft(
                (req.t_first - req.t_submit) * 1e3, req.priority_class)

    def _next_index(self) -> Optional[int]:
        """The admission policy's pick from the queue (None = empty, or —
        fault-tolerant engines — every queued request is still inside its
        quarantine backoff). FIFO always returns the head — exactly the old
        ``popleft`` order; backoff filtering hands the policy the eligible
        subsequence, so within it the policy's order is unchanged."""
        probe = self._affinity_probe \
            if self.policy.prefix_affinity and self.prefix_cache else None
        elig = backoff_eligible(self.queue, self._tick)
        if elig is None:       # fast path: no one is backing off
            return select_index(self.policy, self.queue,
                                state=self._sched_state, prefix_hit=probe)
        if not elig:
            return None
        sub = [self.queue[j] for j in elig]
        idx = select_index(self.policy, sub, state=self._sched_state,
                           prefix_hit=probe)
        return None if idx is None else elig[idx]

    def _affinity_probe(self, req: Request) -> bool:
        """Does this queued request's page chain currently hit the prefix
        index? Non-mutating (no LRU promotion) — probing must not reorder
        the cache the admission itself will consult."""
        if req._chain_keys is None:
            req._chain_keys = self.prefix_index.keys_for(
                self._padded_prompt(req), real_len=len(req.prompt))
        return self.prefix_index.peek(req._chain_keys) > 0

    def _admit_into_free_slots(self) -> None:
        if self.paged:
            return self._admit_paged()
        for i in range(self.ecfg.slots):
            while self.slots_req[i] is None and self.queue:
                idx = self._next_index()
                if idx is None:
                    return     # everyone queued is inside quarantine backoff
                req = self.queue[idx]
                del self.queue[idx]
                self._mark_admitted(req, i)
                try:
                    self._maybe_raise("prefill", rid=req.rid)
                    nxt0, _, one = self._run_prefill(req, i)
                except Exception as e:   # noqa: BLE001 — FT quarantine
                    if not self.ft:
                        raise
                    self._fault_unwind(req, i, "exception", str(e))
                    continue
                with span("engine.dispatch"):
                    self.cache = self._insert(self.cache, one, i)
                self._activate(req, i, nxt0)
        if self.queue:
            self.admit_wait_slots += 1     # every slot holds a request

    def _growth_reserve(self) -> int:
        """Admission headroom: one free page per running sequence, so normal
        decode growth rarely has to evict. This is the overcommit watermark —
        worst-case demand may still exceed it, which eviction then absorbs."""
        return sum(1 for r in self.slots_req if r is not None) \
            + len(self._prefilling)

    def _maybe_preempt(self, cand: Request) -> bool:
        """Priority preemption: evict the policy's victim — the lowest-class
        newest-admitted running request — to make room (slot and pages) for
        a strictly higher-class queued candidate. Returns True when an
        eviction happened (the caller retries admission). Only the paged
        layout can preempt: eviction-by-recompute frees *pages*; a dense
        slot's horizon reservation has nothing to hand back."""
        if not self.paged:
            return False
        running = [r for r in self.slots_req if r is not None]
        if not wants_preemption(self.policy, cand, running):
            return False
        # before the eviction: running still holds the victim, so the event
        # can name both sides of the scheduler's decision
        note_preemption(self.telemetry, self.policy, cand, running)
        if self._evict_victim():
            self.preemptions += 1
            return True
        return False

    def _admit_paged(self) -> None:
        while self.queue:
            idx = self._next_index()
            if idx is None:
                return         # everyone queued is inside quarantine backoff
            req = self.queue[idx]
            i = next((s for s in range(self.ecfg.slots)
                      if self.slots_req[s] is None
                      and s not in self._prefilling), None)
            if i is None:
                # every slot busy: a higher-priority candidate may preempt
                # the lowest-class running request (the victim requeues at
                # the head and the freed slot is retried immediately)
                if self._maybe_preempt(req):
                    continue
                self.admit_wait_slots += 1
                return
            # prefix caching: find the longest cached chain of the padded
            # prompt's pages and take references on the hits immediately —
            # a referenced page can't be reclaimed out from under us below
            keys, hits, tail_logits = self._prefix_probe(req)
            self.allocator.share(hits)
            need = self._page_count(req.bucket) - len(hits)
            short = need + self._growth_reserve() - self.allocator.available
            if short > 0 and self.prefix_cache:
                self._reclaim_pages(short)
            if self.allocator.available < need + self._growth_reserve():
                self.allocator.free(hits)  # back out the probe references
                # pool pressure: priority may evict a lower class for its
                # pages; anyone else waits for pages to free up
                if self._maybe_preempt(req):
                    continue
                self.admit_wait_pages += 1
                return
            pages = hits + self.allocator.alloc(need)
            del self.queue[idx]
            self._slot_pages[i] = pages
            self.page_table_np[i, :] = 0
            self.page_table_np[i, :len(pages)] = pages
            self._mark_admitted(req, i)
            hit_tokens = min(len(hits) * self.ecfg.page_size, req.bucket)
            req._prefix_keys, req._prefix_hit = keys, len(hits)
            if self.prefix_cache:
                if hit_tokens:
                    self.prefix_hits += 1
                    self.prefix_hit_tokens += hit_tokens
                    if self.telemetry is not None:
                        self.telemetry.event("prefix_hit", rid=req.rid,
                                             slot=i, pages=len(hits),
                                             tokens=hit_tokens)
                else:
                    self.prefix_misses += 1
            try:
                self._maybe_raise("prefill", rid=req.rid)
                if hit_tokens == req.bucket:
                    # full-prompt hit: every page (including a partially-
                    # filled tail) is shared and the cached last-position
                    # logits stand in for the skipped forward pass — zero
                    # prefill compute
                    s = req.sampling or GREEDY
                    with span("engine.dispatch"):
                        nxt0 = self._hit_sample(
                            tail_logits, jnp.asarray(req._key),
                            jnp.int32(req.bucket - 1),
                            jnp.float32(s.temperature), jnp.int32(s.top_k),
                            jnp.float32(s.top_p))
                    self.prefix_full_hits += 1
                    self._activate(req, i, nxt0)
                # prompts longer than one chunk prefill incrementally; at or
                # below a chunk, one-shot is strictly cheaper (one dispatch)
                elif self.ecfg.prefill_chunk and \
                        req.bucket > self.ecfg.prefill_chunk:
                    req.state = "prefilling"
                    # hits land on chunk boundaries (the probe rounds down),
                    # so the tick resumes exactly at the first unshared chunk
                    req._chunk_cursor = hit_tokens // self.ecfg.prefill_chunk
                    if self.disagg:
                        # the prefill worker owns its own pages while the
                        # chunks run; hand-off to the decode pool happens on
                        # the final chunk (_kv_handoff)
                        ppages = self.prefill_allocator.alloc(len(pages))
                        assert ppages is not None, \
                            "prefill pool is sized to fit every admission"
                        self._prefill_slot_pages[i] = ppages
                        self.prefill_table_np[i, :] = 0
                        self.prefill_table_np[i, :len(ppages)] = ppages
                    self._prefilling[i] = req
                elif hit_tokens:
                    nxt0 = self._run_suffix_prefill(req, i, hit_tokens)
                    self._activate(req, i, nxt0)
                else:
                    nxt0, logits, one = self._run_prefill(req, i)
                    if self.disagg:
                        # prefill worker: write K/V into the prefill pool,
                        # then hand the pages to the decode pool as an
                        # explicit kv_transfer
                        ppages = self.prefill_allocator.alloc(len(pages))
                        assert ppages is not None, \
                            "prefill pool is sized to fit every admission"
                        with span("engine.dispatch"):
                            self.prefill_pool = self._page_insert(
                                self.prefill_pool, one["k"], one["v"],
                                jnp.asarray(ppages, jnp.int32))
                        self._prefill_slot_pages[i] = ppages
                        self._kv_handoff(req, i)
                    else:
                        with span("engine.dispatch"):
                            self.pool = self._page_insert(
                                self.pool, one["k"], one["v"],
                                jnp.asarray(pages, jnp.int32))
                    self._register_prefix(req, i, logits)
                    self._activate(req, i, nxt0)
            except Exception as e:   # noqa: BLE001 — FT quarantine
                if not self.ft:
                    raise
                self._fault_unwind(req, i, "exception", str(e))
                continue

    # ------------------------------------------------------- prefix caching

    def _prefix_probe(self, req: Request):
        """Longest usable cached prefix for ``req``'s padded prompt.

        Returns ``(keys, hit_pages, tail_logits)``: the prompt's page chain
        keys, the pages of the longest cached chain prefix (no allocator
        references taken yet), and — for a complete-prompt hit only — the
        cached last-position prefill logits. A complete-chain hit without
        cached logits is trimmed by one page so the suffix forward can
        produce the first token; chunked-prefill engines round partial hits
        down to a chunk boundary (the tick's traced chunk length is fixed).

        On a tiered engine, host-resident chain entries are paged back to
        device pages *here* — before admission takes its references and
        before any prefill chunk could read them (the SC011 ordering).
        """
        if not self.prefix_cache:
            return None, [], None
        keys = self.prefix_index.keys_for(self._padded_prompt(req),
                                          real_len=len(req.prompt))
        if self.tiered:
            self._page_in_chain(req, keys)
        pages = self.prefix_index.lookup(keys)
        tail_logits = None
        if len(pages) == len(keys):
            tail_logits = self.prefix_index.tail_logits(keys[-1])
            if tail_logits is None:
                pages = pages[:-1]
        chunk = self.ecfg.prefill_chunk
        if chunk and req.bucket > chunk and len(pages) < len(keys):
            per_chunk = chunk // self.ecfg.page_size
            pages = pages[:(len(pages) // per_chunk) * per_chunk]
        return keys, pages, tail_logits

    def _page_in_chain(self, req: Request, keys: List[bytes]) -> None:
        """Tiered page-in: upload every host-resident entry on ``req``'s
        cached chain back into freshly allocated device pages. A pure
        host→device memcpy of the spilled bytes — the page-in half of the
        ``upir.kv_transfer src_pool(host)`` op — so a spilled-then-hit
        prefix re-prefills zero tokens. Allocation here never reclaims
        (reclaiming could spill not-yet-referenced pages of this very
        chain); a dry pool truncates the chain at the host entry and the
        tail falls back to re-prefill."""
        for k in keys[:self.prefix_index.peek(keys)]:
            hid = self.prefix_index.host_entry(k)
            if hid is None:
                continue       # already device-resident
            dev = self.allocator.alloc(1)
            if dev is None:
                break          # device pool full: chain ends here
            k_np, v_np = self.host_pool.load(hid)
            with span("engine.dispatch"):
                self.pool = self._page_upload(self.pool, jnp.asarray(k_np),
                                              jnp.asarray(v_np),
                                              jnp.int32(dev[0]))
            # the index keeps the single allocator ref, exactly as at
            # registration; the host copy dies with its last reference
            self.prefix_index.commit_page_in(k, dev[0])
            self.host_pool.free([hid])
            self.paged_in += 1
            if self.telemetry is not None:
                self.telemetry.event("paged_in", rid=req.rid, page=dev[0],
                                     host_in_use=self.host_pool.in_use)

    def _register_prefix(self, req: Request, i: int, last_logits) -> None:
        """Publish ``req``'s freshly prefilled prompt pages into the index
        (the index takes one allocator reference per new entry, so cached
        pages outlive the request). The final chain key also retains the
        prefill's last-position logits, enabling full-prompt hits. Pages the
        request itself obtained from the cache are already registered."""
        if not self.prefix_cache or req._prefix_keys is None:
            return
        keys = req._prefix_keys
        for j in range(req._prefix_hit, len(keys)):
            page = self._slot_pages[i][j]
            if self.prefix_index.register(keys[j], page):
                self.allocator.share([page])
        if last_logits is not None:
            self.prefix_index.attach_logits(keys[-1], last_logits[0])

    def _run_suffix_prefill(self, req: Request, i: int, offset: int):
        """Prefill positions ``[offset, bucket)`` — the part of the prompt
        the prefix cache did not cover — gathering the shared pages as
        context, then register the fresh pages. Returns the first token."""
        ps = self.ecfg.page_size
        n_hit = offset // ps
        fn = self.plan_cache.get_or_build(
            self._fkey + ("suffix_prefill", req.bucket, offset),
            lambda: self._build_suffix_prefill(req.bucket, offset))
        width = self._gather_bucket(n_hit)
        row = self.page_table_np[i][:width]
        ids = self._slot_pages[i][n_hit:]
        toks = self._padded_prompt(req)[offset:]
        s = req.sampling or GREEDY
        with span("engine.prefill"), span("engine.dispatch"):
            nxt, logits, self.pool = fn(
                self.params, self.pool, snapshot(row),
                jnp.asarray(toks)[None, :], jnp.asarray(ids, jnp.int32),
                jnp.asarray(req._key), jnp.float32(s.temperature),
                jnp.int32(s.top_k), jnp.float32(s.top_p))
        self._register_prefix(req, i, logits)
        return nxt

    def _reclaim_pages(self, n: int) -> int:
        """Recycle up to ``n`` cached pages nobody maps (refcount 1 — held
        only by the index), LRU-first. Returns the count actually freed;
        pages still shared with live slots are never touched.

        Untiered engines *drop* the victim (a later hit re-prefills it).
        Tiered engines *spill* it: the page bytes move device→host into the
        ``HostPagePool`` and the index entry flips to host-resident, so a
        later hit pages it back in instead of recomputing — the spill half
        of the ``upir.kv_transfer dst_pool(host)`` op. A full host tier
        falls back to dropping, exactly the untiered behavior."""
        freed = 0
        while freed < n:
            if self.tiered:
                popped = self.prefix_index.pop_spillable(self.allocator)
                if popped is None:
                    break
                key, entry = popped
                page = entry["page"]
                hid = self.host_pool.alloc(1)
                if hid is not None:
                    # exact device->host copy of the page bytes: spill is
                    # movement, never recompute
                    with span("engine.readback"):
                        k_np = np.asarray(self.pool["k_pages"][:, page])
                        v_np = np.asarray(self.pool["v_pages"][:, page])
                    self.host_pool.store(hid[0], k_np, v_np)
                    self.prefix_index.insert_host(key, hid[0],
                                                  entry.get("logits"))
                    self.spilled += 1
                    if self.telemetry is not None:
                        self.telemetry.event(
                            "spilled", page=page,
                            host_in_use=self.host_pool.in_use)
                else:
                    self.prefix_reclaimed += 1   # host tier full: drop
            else:
                page = self.prefix_index.pop_reclaimable(self.allocator)
                if page is None:
                    break
                self.prefix_reclaimed += 1
            self.allocator.free([page])
            freed += 1
        return freed

    def _kv_handoff(self, req: Request, i: int) -> None:
        """Disaggregated prefill→decode hand-off: copy slot ``i``'s
        prefilled pages from the prefill pool into the decode pool's pages
        (an exact page-granular device copy — the runtime half of the
        ``upir.kv_transfer src_pool(prefill) dst_pool(decode)`` op), then
        release the prefill worker's pages."""
        ppages = self._prefill_slot_pages[i]
        pages = self._slot_pages[i][:len(ppages)]
        with span("engine.dispatch"):
            self.pool = self._kv_transfer(
                self.pool, self.prefill_pool,
                jnp.asarray(ppages, jnp.int32), jnp.asarray(pages, jnp.int32))
        self.prefill_allocator.free(ppages)
        self._prefill_slot_pages[i] = []
        self.prefill_table_np[i, :] = 0
        self.kv_transfers += 1
        self.kv_transfer_pages += len(ppages)
        if self.telemetry is not None:
            self.telemetry.event("kv_transfer", rid=req.rid, slot=i,
                                 pages=len(ppages))

    def _prefill_tick(self) -> None:
        """Advance chunked prefill: every prefilling slot moves one chunk per
        step, shortest remaining prompt first — short requests reach their
        first token before a long prompt's remaining chunks run, and no step
        ever does more than ``slots * prefill_chunk`` tokens of prefill work
        (that bound is what keeps decode latency flat under long prompts)."""
        if not self._prefilling:
            return
        chunk = self.ecfg.prefill_chunk
        order = sorted(self._prefilling.items(),
                       key=lambda kv: (kv[1].bucket - kv[1]._chunk_cursor * chunk,
                                       kv[1]._admit_seq))
        for i, req in order:
            try:
                self._maybe_raise("prefill", rid=req.rid)
            except Exception as e:
                if not self.ft:
                    raise
                self._fault_unwind(req, i, "exception", str(e))
                continue
            off = req._chunk_cursor * chunk
            toks = self._padded_prompt(req)[off:off + chunk]
            # disaggregated: chunks run in the prefill worker's own pool
            # against its own page table; the decode pool is untouched
            # until the final chunk's hand-off
            slot_pages = self._prefill_slot_pages[i] if self.disagg \
                else self._slot_pages[i]
            table = self.prefill_table_np if self.disagg \
                else self.page_table_np
            ids = slot_pages[off // self.ecfg.page_size:
                             (off + chunk) // self.ecfg.page_size]
            s = req.sampling or GREEDY
            # chunk-sized context gather: only the pages holding previous
            # chunks' K/V are gathered (bucketed to powers of two to bound
            # retraces) — the full-row gather paid full-horizon attention
            # cost on every chunk, even at offset 0. Dropped entries were
            # masked (kpos < offset) anyway, so streams are unchanged.
            width = self._gather_bucket(off // self.ecfg.page_size)
            row = table[i][:width]
            t_c = time.perf_counter() if self.telemetry is not None else None
            pool_in = self.prefill_pool if self.disagg else self.pool
            with span("engine.dispatch"):
                nxt, logits, pool_out = self._chunk_prefill(
                    self.params, pool_in, snapshot(row),
                    jnp.asarray(toks)[None, :], jnp.int32(off),
                    jnp.asarray(ids, jnp.int32), jnp.asarray(req._key),
                    jnp.float32(s.temperature), jnp.int32(s.top_k),
                    jnp.float32(s.top_p))
            if self.disagg:
                self.prefill_pool = pool_out
            else:
                self.pool = pool_out
            if self.telemetry is not None:
                # host dispatch time of the chunk (no added sync)
                self.telemetry.event("prefill_chunk", rid=req.rid, slot=i,
                                     chunk=req._chunk_cursor)
                self.telemetry.observe(
                    "prefill_chunk_ms", (time.perf_counter() - t_c) * 1e3)
            req._chunk_cursor += 1
            self.prefill_chunks += 1
            if off + chunk >= req.bucket:
                del self._prefilling[i]
                if self.disagg:
                    self._kv_handoff(req, i)
                self._register_prefix(req, i, logits)
                self._activate(req, i, nxt)

    def _gather_bucket(self, ctx_pages: int) -> int:
        """Context-gather width (in pages) for a chunked-prefill step:
        the next power of two covering the pages already written, so short
        offsets stop gathering (and attending over) the full per-slot
        horizon. Power-of-two bucketing bounds jit retraces to O(log P)."""
        if ctx_pages <= 0:
            return 0
        width = 1
        while width < ctx_pages:
            width <<= 1
        return min(width, self.pages_per_slot)

    # ------------------------------------------------------ paged page flow

    def _alloc_one_pressured(self, i: int, req: Request) -> Optional[int]:
        """One page for slot ``i`` under pool pressure: reclaim unreferenced
        prefix-cached pages first, then evict the newest-admitted request
        (recompute-on-readmit). Returns None when ``req`` itself became the
        victim. An armed ``alloc_fail`` fault forces one attempt to come up
        dry, driving this whole pressure path on demand."""
        while True:
            if self._fault_fires("alloc_fail") is not None:
                got = None             # forced exhaustion: one dry attempt
            else:
                got = self.allocator.alloc(1)
            if got is not None:
                return got[0]
            if self.prefix_cache and self._reclaim_pages(1):
                continue
            if not self._evict_victim():
                raise RuntimeError(
                    "paged KV pool exhausted with no evictable "
                    "sequence")  # unreachable: admission caps size
            if self.slots_req[i] is not req:
                return None            # this slot itself was the victim

    def _write_slack(self) -> int:
        """Decode-write lookahead in tokens: ``lookahead_k`` for speculative
        engines, 0 in degraded mode (the degradation *is* dropping that
        reservation) and for plain engines."""
        return 0 if self.degraded else self._slack

    def _ensure_pages(self) -> None:
        """Before decode, every active slot about to write position ``pos``
        (through ``pos + lookahead_k`` in speculative mode) must own the
        pages covering it — *privately*. Missing pages are allocated
        (reclaiming cached pages, then evicting the newest-admitted request,
        under pressure; oldest requests always make progress — liveness
        under overcommit), and prefix-shared pages in the write span are
        duplicated copy-on-write so the cached original stays pristine. A
        speculative engine whose pool has run dry first tries degraded mode
        (drop the lookahead reservation, serve through the plain decode
        loop) before anything is evicted — bitwise-invisible for greedy
        workloads, so it is only engaged for them."""
        order = sorted((i for i in range(self.ecfg.slots)
                        if self.slots_req[i] is not None),
                       key=lambda i: self.slots_req[i]._admit_seq)
        for i in order:
            req = self.slots_req[i]
            if req is None:
                continue               # evicted while growing an older slot
            while (self.pos[i] + self._write_slack()) // self.ecfg.page_size \
                    >= len(self._slot_pages[i]):
                if self.allocator.available == 0 and self._maybe_degrade():
                    continue           # re-test with the smaller write span
                page = self._alloc_one_pressured(i, req)
                if page is None:
                    break              # this slot itself was the victim
                self._slot_pages[i].append(page)
                self.page_table_np[i, len(self._slot_pages[i]) - 1] = page
        if self.prefix_cache:
            self._cow_tick()

    def _cow_tick(self) -> None:
        """Copy-on-write: any shared page a slot is about to write — the
        pages covering ``[pos, pos + lookahead_k]``, in practice the
        partially-filled tail page of a fully-hit prompt — is duplicated
        into a private copy first (``cache_copy_pages``), the page table is
        repointed, and the shared original keeps serving its other readers
        (and the prefix index) byte-identical."""
        ps = self.ecfg.page_size
        for i in range(self.ecfg.slots):
            req = self.slots_req[i]
            if req is None:
                continue
            row = self._slot_pages[i]
            first = int(self.pos[i]) // ps
            last = (int(self.pos[i]) + self._write_slack()) // ps
            for j in range(first, min(last + 1, len(row))):
                if self.allocator.refcount(row[j]) <= 1:
                    continue
                page = self._alloc_one_pressured(i, req)
                if page is None:
                    break              # slot evicted hunting for a copy
                with span("engine.dispatch"):
                    self.pool = self._page_copy(
                        self.pool, jnp.asarray([row[j]], jnp.int32),
                        jnp.asarray([page], jnp.int32))
                self.allocator.free([row[j]])
                row[j] = page
                self.page_table_np[i, j] = page
                self.cow_copies += 1
                if self.telemetry is not None:
                    self.telemetry.event("cow", rid=req.rid, slot=i)

    def _evict_victim(self) -> bool:
        """Evict one running request (recompute-on-readmit). The victim is
        policy-chosen: newest-admitted under fifo/fair/sjf, lowest class
        (newest within it) under priority — so preemption never sacrifices a
        higher class to seat a lower one."""
        victims = [r for r in self.slots_req if r is not None]
        if not victims:
            return False
        req = policy_victim(self.policy, victims)
        i = req.slot
        # flush the device token log so the victim's partial stream can be
        # dropped (it will be recomputed identically on re-admission)
        self._collect_tokens()
        self._pending_tokens.pop(req.rid, None)
        self.allocator.free(self._slot_pages[i])
        self._slot_pages[i] = []
        self.page_table_np[i, :] = 0
        self.slots_req[i] = None
        self.pos[i] = 0
        req.state, req.slot = "queued", -1
        req._first_tok = None
        req._remaining = 0
        req._chunk_cursor = 0
        req._prefix_keys, req._prefix_hit = None, 0  # re-probed on readmit
        req.tokens_out = []
        self.eos_np[i] = -1
        self.temps_np[i] = 0.0
        self.topps_np[i] = 1.0
        self.presence_np[i] = 0.0
        self.frequency_np[i] = 0.0
        self._policy_dev = None
        # req._key is NOT reset: recompute-on-readmit replays the same
        # fold_in(key, pos) schedule, so sampled streams reproduce exactly
        self.queue.appendleft(req)
        self.evictions += 1
        self.trace.append({"event": "evict", "rid": req.rid, "slot": i})
        if self.telemetry is not None:
            self.telemetry.event("evicted", rid=req.rid, slot=i)
        return True

    def _release_pages(self, req: Request) -> None:
        i = req.slot
        if i < 0 or not self._slot_pages[i]:
            return
        self.allocator.free(self._slot_pages[i])
        self._slot_pages[i] = []
        self.page_table_np[i, :] = 0
        self.pos[i] = 0

    def _rollback_pages(self, i: int) -> None:
        """Speculative paged commit: after acceptance, pages wholly past the
        accepted context tail hold only rejected drafts' K/V — return them
        to the free list and null their page-table entries, so the pool only
        ever stays charged for committed tokens."""
        keep = self._page_count(int(self.pos[i]))
        row = self._slot_pages[i]
        if len(row) > keep:
            self.allocator.free(row[keep:])
            self.page_table_np[i, keep:len(row)] = 0
            del row[keep:]

    def _device_page_table(self):
        """Decode sees real rows only for active slots; prefilling/free slots
        are masked to the null page so their scatters and gathers are inert."""
        with span("engine.pages"):
            mask = np.fromiter((self.slots_req[i] is not None
                                for i in range(self.ecfg.slots)), bool,
                               self.ecfg.slots)
            table = np.where(mask[:, None], self.page_table_np, 0)
            with span("engine.dispatch"):
                return jnp.asarray(table)

    # -------------------------------------------------------------- stepping

    def _finish(self, req: Request, reason: str = "") -> None:
        req.state = "done"
        req.t_done = time.perf_counter()
        self.completed += 1
        if req._retries:
            # quarantined at least once, yet completed: the replay-exact
            # recovery actually delivered
            self.recovered += 1
        if reason == "eos":
            req.reason = "eos"
            self.eos_finished += 1
        if req.deadline_ms is not None and req.t_submit:
            # the SLO clock measures time-to-first-token: admission latency is
            # what scheduling controls (decode speed is the model's business)
            tf = req.t_first or req.t_done
            ok = (tf - req.t_submit) * 1e3 <= req.deadline_ms
            if ok:
                self.slo_attained += 1
            else:
                self.slo_missed += 1
            by = self.slo_by_class.setdefault(req.priority_class,
                                              [0, 0])
            by[0 if ok else 1] += 1
        # the first token comes from prefill logits; only the decode loop's
        # tokens count toward decode throughput. EOS-finished requests count
        # the decode steps actually executed, not the max_new_tokens budget.
        self.prefill_tokens += 1
        self.tokens_generated += max(
            req.max_new_tokens - 1 - max(req._remaining, 0), 0)
        if self.paged:
            self._release_pages(req)
        if req.slot >= 0 and self.slots_req[req.slot] is req:
            self.slots_req[req.slot] = None
            self.eos_np[req.slot] = -1
            self.temps_np[req.slot] = 0.0
            self.topps_np[req.slot] = 1.0
            self.presence_np[req.slot] = 0.0
            self.frequency_np[req.slot] = 0.0
            self._policy_dev = None
        self.trace.append({"event": "finish", "rid": req.rid,
                           "slot": req.slot, "reason": reason})
        if self.telemetry is not None:
            self.telemetry.event("finished", rid=req.rid, slot=req.slot,
                                 reason=reason)

    def _eos_poll(self) -> None:
        """Learn about device-side EOS completions. The finished mask is
        read back only every ``eos_poll_every`` decode steps and only while a
        request with an ``eos_id`` is active — workloads that never set an
        EOS keep the hot loop sync-free. Between polls a finished slot's
        stream is frozen at its EOS token (``decode_select``), so polling
        late costs idle decode work, never correctness."""
        every = self.ecfg.eos_poll_every
        if not every or self.decode_steps % every:
            return
        polled = [i for i in range(self.ecfg.slots)
                  if self.slots_req[i] is not None
                  and self.slots_req[i].state == "active"
                  and self.slots_req[i].eos_id is not None]
        if not polled:
            return
        with span("engine.readback"):
            fin = np.asarray(self.finished)
        for i in polled:
            if fin[i]:
                self._finish(self.slots_req[i], reason="eos")

    # ------------------------------------------------------- fault tolerance

    def _fault_fires(self, kind: str, *, site: Optional[str] = None,
                     rid: Optional[int] = None,
                     slot_active=None) -> Optional[FaultSpec]:
        """The first armed ``FaultPlan`` entry of ``kind`` that fires now,
        consuming one of its ``times`` — or None. A fault arms once the
        engine tick reaches its ``step``; ``exception`` faults additionally
        match site (and, if targeted, rid), and ``nan`` faults wait until
        their slot actually holds an active request (``slot_active``)."""
        if self.fault_plan is None:
            return None
        for idx, f in enumerate(self.fault_plan.faults):
            if f.kind != kind or self._tick < f.step:
                continue
            if self._fault_fired.get(idx, 0) >= f.times:
                continue
            if kind == "exception":
                if f.site != site:
                    continue
                if f.rid is not None and rid != f.rid:
                    continue
            if kind == "nan" and slot_active is not None \
                    and not slot_active(f.slot):
                continue
            self._fault_fired[idx] = self._fault_fired.get(idx, 0) + 1
            self.faults_injected += 1
            self.trace.append({"event": "fault_inject", "kind": kind,
                               "tick": self._tick, "site": site, "rid": rid,
                               "slot": f.slot})
            return f
        return None

    def _maybe_raise(self, site: str, rid: Optional[int] = None) -> None:
        """Fire an armed ``exception`` fault at an engine boundary — before
        the jit dispatch, exactly where a real runtime error would surface
        (and before any buffer is donated, so unwinding is safe)."""
        f = self._fault_fires("exception", site=site, rid=rid)
        if f is not None:
            raise InjectedFault(site, f"injected {site} fault "
                                      f"(tick {self._tick}, rid {rid})")

    def _arm_poison(self, active) -> Any:
        """The decode step's poison input: all-False (a bitwise identity in
        ``poison_and_guard``) except for the single tick an armed ``nan``
        fault fires on a slot holding an active request. The device vector
        is rebuilt only when the host staging changes."""
        if self._poison_np.any():
            self._poison_np[:] = False
            self._poison_dev = None
        f = self._fault_fires(
            "nan", slot_active=lambda s: s in active
            and self.slots_req[s] is not None)
        if f is not None:
            self._poison_np[f.slot] = True
            self._poison_dev = None
        if self._poison_dev is None:
            with span("engine.dispatch"):
                self._poison_dev = snapshot(self._poison_np)
        return self._poison_dev

    def _nan_poll(self, active) -> None:
        """Read the sticky device-side finite-guard mask and quarantine
        poisoned slots. Polled on the EOS cadence — plus whenever an active
        request is about to finish, so a poisoned stream is never finalized
        as done — and only on engines that armed the guard: everyone else
        keeps the sync-free hot loop."""
        if not self._nan_guard:
            return
        every = self.ecfg.eos_poll_every
        due = bool(every) and self.decode_steps % every == 0
        if not due and not any(
                self.slots_req[i] is not None
                and self.slots_req[i]._remaining <= 0 for i in active):
            return
        with span("engine.readback"):
            bad = np.asarray(self.poisoned)
        for i in active:
            req = self.slots_req[i]
            if req is not None and bad[i]:
                self._fault_unwind(req, i, "nan",
                                   "non-finite decode logits")

    def _fault_unwind(self, req: Request, i: int, kind: str,
                      detail: str = "") -> None:
        """Quarantine a faulted request: drop its partial stream, release
        its slot (and pages), and either requeue it for a replay-exact
        recompute — bounded retries, exponential backoff in admission
        order — or terminate it ``failed``. This mirrors
        :meth:`_evict_victim`'s cleanup: the PRNG key snapshot survives, so
        the replayed stream is bitwise the fault-free one."""
        self._collect_tokens()
        self._pending_tokens.pop(req.rid, None)
        if self.paged and self._slot_pages[i]:
            self.allocator.free(self._slot_pages[i])
            self._slot_pages[i] = []
            self.page_table_np[i, :] = 0
        if self.disagg and self._prefill_slot_pages[i]:
            # a fault mid-chunked-prefill also unwinds the prefill worker's
            # pages (the hand-off never happened)
            self.prefill_allocator.free(self._prefill_slot_pages[i])
            self._prefill_slot_pages[i] = []
            self.prefill_table_np[i, :] = 0
        self._prefilling.pop(i, None)
        if self.slots_req[i] is req:
            self.slots_req[i] = None
        self.pos[i] = 0
        self.eos_np[i] = -1
        self.temps_np[i] = 0.0
        self.topps_np[i] = 1.0
        self.presence_np[i] = 0.0
        self.frequency_np[i] = 0.0
        self._policy_dev = None
        self.finished = self.finished.at[i].set(False)
        if self._nan_guard:
            self.poisoned = self.poisoned.at[i].set(False)
            self._poison_np[i] = False
            self._poison_dev = None
        req.slot = -1
        req._first_tok = None
        req._remaining = 0
        req._chunk_cursor = 0
        req._prefix_keys, req._prefix_hit = None, 0
        req.tokens_out = []
        # req._key is NOT reset — same argument as eviction-by-recompute
        req._retries += 1
        self.quarantines += 1
        self.trace.append({"event": "quarantine", "rid": req.rid,
                           "kind": kind, "slot": i,
                           "retries": req._retries})
        note_quarantine(self.telemetry, req.rid, i, kind)
        if req._retries > self.ecfg.max_retries:
            self._fail(req, kind, detail)
            return
        req.state = "queued"
        # exponential backoff in admission order: the replay waits
        # 2**(retries-1) ticks before it is eligible again, so a
        # persistently-faulting request cannot monopolize admission
        backoff = 1 << (req._retries - 1)
        req._not_before = self._tick + backoff
        note_retry(self.telemetry, req.rid, req._retries, backoff)
        self.queue.appendleft(req)

    def _fail(self, req: Request, kind: str, detail: str = "") -> None:
        """Retries exhausted: terminal ``failed`` with a typed
        ``FailureInfo`` — the engine keeps serving everyone else."""
        info = FailureInfo(rid=req.rid, kind=kind,
                           retries=req._retries - 1, detail=detail)
        req.state, req.reason, req.failure = "failed", kind, info
        req.t_done = time.perf_counter()
        self.failed += 1
        self.failures.append(info)
        self.trace.append({"event": "fail", "rid": req.rid, "kind": kind,
                           "retries": info.retries})
        note_failure(self.telemetry, info)

    def _decode_fault(self, e: Exception) -> None:
        """A decode/verify boundary raised: no tokens were committed this
        step, but the step's outputs are untrusted — quarantine the policy
        victim (the same attribution heuristic eviction uses, since a batch-
        level fault has no single guilty slot) and replay it."""
        victims = [r for r in self.slots_req if r is not None]
        if not victims:
            return
        req = policy_victim(self.policy, victims)
        self._fault_unwind(req, req.slot, "exception", str(e))

    def _watchdog_trip(self, dt_ms: float) -> None:
        """An engine iteration exceeded ``watchdog_ms``: count the trip and
        quarantine the policy victim — a hung sequence is converted into a
        fault and recovered like any other, instead of stalling the batch
        forever."""
        self.watchdog_trips += 1
        self.trace.append({"event": "watchdog", "tick": self._tick,
                           "ms": round(dt_ms, 1)})
        victims = [r for r in self.slots_req if r is not None]
        if victims:
            req = policy_victim(self.policy, victims)
            self._fault_unwind(
                req, req.slot, "stall",
                f"step took {dt_ms:.0f}ms > watchdog "
                f"{self.ecfg.watchdog_ms:.0f}ms")

    def _maybe_degrade(self) -> bool:
        """Pressure-triggered degraded mode: before a speculative engine
        starts evicting, drop the lookahead reservation and fall back to the
        plain one-token decode loop. Only engaged when every live and queued
        request is greedy — greedy spec and plain streams are bitwise
        identical, so the mode switch is invisible; a sampled stream's draws
        depend on which loop serves it, so sampled workloads keep the
        eviction path instead."""
        if self._spec is None or self.degraded:
            return False
        reqs = [r for r in self.slots_req if r is not None] \
            + list(self.queue) + list(self._prefilling.values())
        if any(r.sampling is not None and not r.sampling.greedy
               for r in reqs):
            return False
        self.degraded = True
        self.degraded_entries += 1
        self.trace.append({"event": "degrade", "tick": self._tick})
        return True

    def _maybe_exit_degraded(self) -> None:
        """Leave degraded mode once the pool again has headroom for every
        active slot's lookahead reservation (one hysteresis page each, so
        the mode doesn't flap at the boundary)."""
        per = -(-self._slack // self.ecfg.page_size) + 1
        occupied = sum(1 for r in self.slots_req if r is not None)
        if self.allocator.available >= per * max(occupied, 1):
            # flush plain-decode tokens before the spec loop appends to
            # _pending_tokens directly again, or streams would interleave
            self._collect_tokens()
            self.degraded = False
            self.trace.append({"event": "undegrade", "tick": self._tick})

    def _shed_deadlines(self) -> None:
        """Actual deadline enforcement (``enforce_deadlines=True``): a
        queued request whose TTFT deadline has already passed can no longer
        meet its SLO no matter what admission does — shed it now, typed
        ``SHED_DEADLINE``, instead of burning prefill compute on dead work.
        Active requests are never shed (their first token is already paid
        for or imminent)."""
        if not self.queue:
            return
        now = time.perf_counter()
        kept: Deque[Request] = deque()
        for r in self.queue:
            if r.deadline_ms is not None \
                    and (now - r.t_submit) * 1e3 > r.deadline_ms:
                r.state, r.reason = "shed", "SHED_DEADLINE"
                r.t_done = now
                self.shed_deadline += 1
                self.slo_missed += 1
                by = self.slo_by_class.setdefault(r.priority_class, [0, 0])
                by[1] += 1
                self.trace.append({"event": "shed", "rid": r.rid,
                                   "deadline_ms": r.deadline_ms})
                if self.telemetry is not None:
                    self.telemetry.event("shed", rid=r.rid,
                                         deadline_ms=r.deadline_ms)
            else:
                kept.append(r)
        if len(kept) != len(self.queue):
            self.queue = kept

    def check_invariants(self) -> None:
        """Debug-mode consistency check (``EngineConfig.debug_checks``), run
        each tick: allocator invariants plus the engine's page-table view —
        slot rows mirror ``_slot_pages``, mapped entries are live, rows fit
        ``pages_per_slot``, and empty slots hold no pages. Dense engines
        have no page state, so this is a no-op there."""
        if not self.paged:
            return
        self.allocator.check_invariants()
        for i in range(self.ecfg.slots):
            row = self._slot_pages[i]
            if len(row) > self.pages_per_slot:
                raise RuntimeError(f"slot {i} holds {len(row)} pages > "
                                   f"pages_per_slot {self.pages_per_slot}")
            table = self.page_table_np[i]
            if list(table[:len(row)]) != row:
                raise RuntimeError(f"slot {i} page table "
                                   f"{table[:len(row)].tolist()} != slot "
                                   f"pages {row}")
            if np.any(table[len(row):]):
                raise RuntimeError(f"slot {i} page table maps entries past "
                                   f"its {len(row)} pages")
            dead = [p for p in row if self.allocator.refcount(p) < 1]
            if dead:
                raise RuntimeError(f"slot {i} maps dead pages {dead}")
            if row and self.slots_req[i] is None \
                    and i not in self._prefilling:
                raise RuntimeError(f"empty slot {i} still holds pages {row}")
        if self.tiered:
            self.host_pool.check_invariants()
            # every host-resident index entry points at a live host page
            # with a stored payload, and no host page is referenced twice —
            # a page is live on exactly one tier
            hids = self.prefix_index.host_ids()
            if len(set(hids)) != len(hids):
                raise RuntimeError(f"host page referenced by multiple "
                                   f"prefix entries: {hids}")
            for hid in hids:
                if self.host_pool.refcount(hid) < 1:
                    raise RuntimeError(f"prefix entry maps dead host "
                                       f"page {hid}")
                if not self.host_pool.has_payload(hid):
                    raise RuntimeError(f"host page {hid} is live but holds "
                                       f"no spilled payload")
            if self.host_pool.in_use != len(hids):
                raise RuntimeError(
                    f"host pool holds {self.host_pool.in_use} live pages "
                    f"but the index references {len(hids)}")
        if self.disagg:
            self.prefill_allocator.check_invariants()
            for i in range(self.ecfg.slots):
                prow = self._prefill_slot_pages[i]
                table = self.prefill_table_np[i]
                if list(table[:len(prow)]) != prow:
                    raise RuntimeError(f"slot {i} prefill table "
                                       f"{table[:len(prow)].tolist()} != "
                                       f"prefill pages {prow}")
                if np.any(table[len(prow):]):
                    raise RuntimeError(f"slot {i} prefill table maps "
                                       f"entries past its {len(prow)} pages")
                if prow and i not in self._prefilling:
                    raise RuntimeError(f"slot {i} holds prefill pages "
                                       f"{prow} outside a chunked prefill")

    def step(self) -> int:
        """One engine iteration: refill free slots (and, in chunked mode,
        advance one prefill chunk), then one decode step for the whole batch.
        Returns the number of active slots decoded.

        Fault-tolerant engines additionally: shed past-deadline queued
        requests (``enforce_deadlines``), run invariant checks
        (``debug_checks``), fire armed faults from the ``FaultPlan``, poll
        the device-side finite guard on the EOS cadence, and time the whole
        iteration against the wall-clock watchdog."""
        with span("engine.step"):
            return self._step()

    def _step(self) -> int:
        t_step = time.perf_counter() \
            if (self.ecfg.watchdog_ms or self.telemetry is not None) else None
        self._activated = []
        self._step_emitted = 0     # decode tokens dispatched this iteration
        if self.degraded:
            self._maybe_exit_degraded()
        if self.ecfg.enforce_deadlines:
            self._shed_deadlines()
        if self.ecfg.debug_checks:
            self.check_invariants()
        stall = self._fault_fires("stall")
        if stall is not None:
            time.sleep(stall.stall_s)
        with span("engine.admit"):
            self._admit_into_free_slots()
        if self.paged:
            with span("engine.prefill"):
                self._prefill_tick()
                # cold start / post-drain: nothing to decode yet, so spend
                # the step activating the shortest prompt instead of idling
                while self._prefilling and \
                        not any(r is not None for r in self.slots_req):
                    self._prefill_tick()
            with span("engine.pages"):
                self._ensure_pages()
        active = [i for i in range(self.ecfg.slots)
                  if self.slots_req[i] is not None]
        if active:
            with span("engine.decode"):
                self._decode_active(active)
        if self._sync_each_step:
            jax.block_until_ready(self.tokens)
        if self._activated and not self._sync_each_step:
            now = time.perf_counter()
            for r in self._activated:
                r.t_first = now
                self._note_first_token(r)
        self.peak_concurrent = max(self.peak_concurrent, len(active))
        if self.paged:
            self.peak_pages = max(self.peak_pages, self.allocator.in_use)
        self._tick += 1
        if t_step is not None:
            dt_ms = (time.perf_counter() - t_step) * 1e3
            if self.ecfg.watchdog_ms and dt_ms > self.ecfg.watchdog_ms:
                self._watchdog_trip(dt_ms)
            if self.telemetry is not None:
                self.telemetry.gauge("queue_depth", len(self.queue))
                self.telemetry.gauge("active_slots", len(active))
                if self.paged:
                    self.telemetry.gauge("pages_in_use",
                                         self.allocator.in_use)
                if self.tiered:
                    self.telemetry.gauge("host_pages_in_use",
                                         self.host_pool.in_use)
                if self._step_emitted:
                    # one histogram sample per decode iteration; the
                    # per-token interval divides the wall time by the
                    # tokens each slot emitted (1 plain, 1..k+1 spec)
                    self.telemetry.observe("step_ms", dt_ms)
                    itl = dt_ms * len(active) / self._step_emitted
                    self.telemetry.observe("itl_ms", itl)
                    for i in active:
                        req = self.slots_req[i]
                        if req is not None:   # finished slots drop theirs
                            req._itl_ms.append(itl)
        return len(active)

    def _decode_active(self, active) -> None:
        """One decode step for the ``active`` slots: upload the slots'
        policy when it changed, dispatch the compiled step (or the
        speculative iteration), then count down, finish and poll."""
        # per-slot policy only changes at admit/finish/evict: the device
        # copy is rebuilt then, not per step — steady decode uploads nothing
        # but the position vector
        if self._policy_dev is None:
            with span("engine.dispatch"):
                self._policy_dev = (
                    snapshot(self.keys_np), snapshot(self.temps_np),
                    snapshot(self.topks_np), snapshot(self.topps_np),
                    snapshot(self.eos_np), snapshot(self.presence_np),
                    snapshot(self.frequency_np))
        if self._spec is not None and not self.degraded:
            try:
                self._maybe_raise("verify")
                self._spec_step(active)
            except Exception as e:   # noqa: BLE001 — FT quarantine
                if not self.ft:
                    raise
                self._decode_fault(e)
        else:
            keys, temps, topks, topps, eos, presence, frequency = \
                self._policy_dev
            poison = self._arm_poison(active)
            try:
                self._maybe_raise("decode")
                kv = (self.pool, self._device_page_table()) if self.paged \
                    else (self.cache,)
                with span("engine.dispatch"):
                    out = self._decode(
                        self.params, *kv, self.tokens, snapshot(self.pos),
                        keys, temps, topks, topps, eos, self.finished,
                        self.counts, presence, frequency, poison,
                        self.poisoned)
            except Exception as e:   # noqa: BLE001 — FT quarantine
                if not self.ft:
                    raise
                # the injection raises *before* the jit dispatch, so no
                # donated buffer was consumed: state is intact
                self._decode_fault(e)
            else:
                nxt, self.finished, store, self.counts, self.poisoned = \
                    out
                if self.paged:
                    self.pool = store
                else:
                    self.cache = store
                if self._spec is not None:
                    self.degraded_steps += 1
                if self.decode_widths:
                    live, width = self._live_blocks(active)
                    self.decode_live_blocks += live
                    self.decode_dispatched_blocks += width
                with span("engine.dispatch"):
                    self.tokens = nxt[:, None]
                rids = tuple(self.slots_req[i].rid
                             if self.slots_req[i] is not None
                             else -1 for i in range(self.ecfg.slots))
                self._toklog.append((nxt, rids))
                self.decode_steps += 1
                self._step_emitted = len(active)
                self._occupancy_sum += len(active)
                for i in active:
                    self.pos[i] += 1
                    self.slots_req[i]._remaining -= 1
                # guard poll runs between the countdown and _finish: a
                # poisoned stream must be quarantined, never finalized
                self._nan_poll(active)
                for i in active:
                    req = self.slots_req[i]
                    if req is not None and req.state == "active" \
                            and req._remaining <= 0:
                        self._finish(req)
                self._eos_poll()

    def _spec_step(self, active) -> None:
        """One draft/verify iteration: the fused jit proposes ``k`` tokens
        per slot, verifies all ``k+1`` positions in one batched call, and
        rejection-samples the accepted prefix. The host reads the acceptance
        counts (speculative mode syncs once per step — that is what buys
        multi-token emission), commits per-slot emissions clamped to each
        request's budget, handles EOS inline, and rolls back the paged tail
        so only accepted tokens stay committed."""
        keys, temps, topks, topps, _eos, _presence, _frequency = \
            self._policy_dev
        table = self._device_page_table() if self.paged else None
        with span("engine.dispatch"):
            pos_dev = snapshot(self.pos)
            if self.paged:
                out, n_acc, self.pool, self._spec.cache = self._spec._step(
                    self.params, self._spec.params, self.pool, table,
                    self._spec.cache, self.tokens, pos_dev, keys, temps,
                    topks, topps)
            else:
                out, n_acc, self.cache, self._spec.cache = self._spec._step(
                    self.params, self._spec.params, self.cache,
                    self._spec.cache, self.tokens, pos_dev, keys, temps,
                    topks, topps)
        with span("engine.readback"):
            out_np = np.asarray(out)
            n_np = np.asarray(n_acc)
        toks_np = np.array(self.tokens)   # mutable host copy
        self.decode_steps += 1
        self.spec_steps += 1
        self._occupancy_sum += len(active)
        for i in active:
            req = self.slots_req[i]
            emit = min(int(n_np[i]) + 1, req._remaining)
            toks = [int(t) for t in out_np[i, :emit]]
            eos_hit = req.eos_id is not None and req.eos_id in toks
            if eos_hit:
                toks = toks[:toks.index(req.eos_id) + 1]
            self.draft_proposed += self._slack
            self.draft_accepted += min(int(n_np[i]), len(toks))
            self._step_emitted += len(toks)
            self._pending_tokens.setdefault(req.rid, []).extend(toks)
            self.pos[i] += len(toks)
            toks_np[i, 0] = toks[-1]
            req._remaining -= len(toks)
            if eos_hit:
                self._finish(req, reason="eos")
            elif req._remaining <= 0:
                self._finish(req)
            elif self.paged:
                self._rollback_pages(i)
        with span("engine.dispatch"):
            self.tokens = jnp.asarray(toks_np)

    def run(self, requests: Sequence[Union[Request, RequestSpec]] = (), *,
            max_steps: int = 1_000_000,
            sync_per_step: bool = False) -> List[Request]:
        """Submit ``requests`` (``RequestSpec`` or already-materialized
        ``Request``) and drive the engine until drained; returns the
        materialized ``Request`` objects in submission order.

        ``sync_per_step`` blocks on the device each step so per-request
        timestamps (TTFT) are wall-clock-accurate — benchmark latency mode;
        throughput runs leave it off (the hot loop never syncs)."""
        mats: List[Request] = []
        for r in requests:
            if isinstance(r, RequestSpec):
                mats.append(self.submit(r))
            else:
                self.submit(r)
                mats.append(r)
        self._sync_each_step = sync_per_step
        t0 = time.perf_counter()
        steps = 0
        while (self.queue or self._prefilling
                or any(r is not None for r in self.slots_req)) \
                and steps < max_steps:
            self.step()
            steps += 1
        jax.block_until_ready(self.tokens)
        self.elapsed_s += time.perf_counter() - t0
        self._sync_each_step = False
        self._collect_tokens()
        self.trace.append({"event": "stats", **self.stats()})
        self._bound_state()
        return mats

    def _bound_state(self) -> None:
        """Keep a long-lived engine's memory flat: evict the oldest
        unfinalized outputs and oldest trace events beyond the config bounds."""
        while len(self._pending_tokens) > self.ecfg.keep_results:
            self._pending_tokens.pop(next(iter(self._pending_tokens)))
        excess = len(self.trace) - self.ecfg.max_trace_events
        if excess > 0:
            del self.trace[:excess]

    def _collect_tokens(self) -> None:
        """Distribute the device-side token log into per-request outputs.
        Done once, after the decode loop — the hot loop never syncs to host."""
        if not self._toklog:
            return
        with span("engine.readback"):
            toks = np.asarray(jnp.stack([t for t, _ in self._toklog]))
        for srow, rids in zip(toks, (r for _, r in self._toklog)):
            for slot, rid in enumerate(rids):
                if rid >= 0:
                    self._pending_tokens.setdefault(rid, []).append(
                        int(srow[slot]))
        self._toklog = []

    def finalize_request(self, req: Request) -> List[int]:
        """First token (from prefill logits) + decode-step tokens. Streams
        with an ``eos_id`` are truncated at the first EOS (inclusive) — any
        frozen post-EOS fill tokens the device emitted are dropped here.
        Finalization is a host-sync point anyway, so the device token log is
        flushed first — callers driving ``step()`` by hand (instead of
        ``run()``) get complete streams too."""
        if not req.tokens_out:
            self._collect_tokens()
            out: List[int] = []
            if req._first_tok is not None:
                out.append(int(np.asarray(req._first_tok)[0]))
                req._first_tok = None
            out.extend(self._pending_tokens.pop(req.rid, []))
            if req.eos_id is not None and req.eos_id in out:
                out = out[:out.index(req.eos_id) + 1]
            req.tokens_out = out
        return req.tokens_out

    # -------------------------------------------------------- snapshot/restore

    def snapshot(self) -> EngineSnapshot:
        """Copy the engine's full serving state to host buffers — the
        ``upir.memory_snapshot`` MemOp of a fault-tolerant plan, realized.

        Captures everything a crash-restarted engine needs to resume every
        in-flight stream bitwise: KV pool (or dense cache), page tables +
        allocator accounting, per-slot decode policy and device masks, the
        request objects (queue / slots / chunked prefills, deep-copied
        *together* so shared identity survives) with their PRNG key
        snapshots, prefix-index entries (with cached tail logits), and the
        admission counters future rids/keys depend on. Stats and trace are
        observability, not state, and are not captured. Speculative engines
        are refused — the draft cache is not snapshotted."""
        if self._spec is not None:
            raise ValueError("snapshot does not support speculative "
                             "engines: the draft cache is not captured")
        self._collect_tokens()
        host = lambda t: np.asarray(t)   # noqa: E731
        kv = jax.tree_util.tree_map(host,
                                    self.pool if self.paged else self.cache)
        live: List[Request] = [r for r in self.slots_req if r is not None] \
            + list(self.queue) + list(self._prefilling.values())
        for r in live:
            if r._first_tok is not None:
                r._first_tok = np.asarray(r._first_tok)   # host-normalize
        slots_c, queue_c, prefill_c = copy.deepcopy(
            (list(self.slots_req), list(self.queue),
             dict(self._prefilling)))
        prefix_entries = None
        tiered_entries = None
        if self.prefix_cache:
            if self.tiered:
                # tiered index entries live on one of two tiers; capture
                # kind + payload per entry (host entries carry their spilled
                # bytes) in index (= LRU) order
                tiered_entries = []
                for k, e in self.prefix_index._entries.items():
                    lg = None if e.get("logits") is None \
                        else host(e["logits"])
                    if "page" in e:
                        tiered_entries.append((k, "device", e["page"], lg))
                    else:
                        k_np, v_np = self.host_pool.load(e["host"])
                        tiered_entries.append(
                            (k, "host",
                             (e["host"], k_np.copy(), v_np.copy()), lg))
            else:
                prefix_entries = [
                    (k, e["page"],
                     None if e.get("logits") is None else host(e["logits"]))
                    for k, e in self.prefix_index._entries.items()]
        snap = EngineSnapshot(
            fingerprint=self.plan.fingerprint,
            tick=self._tick,
            rid=self._rid,
            admit_counter=self._admit_counter,
            kv=kv,
            tokens=host(self.tokens),
            pos=self.pos.copy(),
            finished=host(self.finished),
            poisoned=host(self.poisoned),
            counts=host(self.counts),
            policy_np={"keys": self.keys_np.copy(),
                       "temps": self.temps_np.copy(),
                       "topks": self.topks_np.copy(),
                       "topps": self.topps_np.copy(),
                       "eos": self.eos_np.copy(),
                       "presence": self.presence_np.copy(),
                       "frequency": self.frequency_np.copy()},
            page_table=self.page_table_np.copy() if self.paged else None,
            slot_pages=[list(r) for r in self._slot_pages]
            if self.paged else None,
            alloc_free=list(self.allocator._free) if self.paged else None,
            alloc_ref=dict(self.allocator._ref) if self.paged else None,
            slots_req=slots_c,
            queue=queue_c,
            prefilling=prefill_c,
            pending_tokens={rid: list(v)
                            for rid, v in self._pending_tokens.items()},
            prefix_entries=prefix_entries,
            enc_memory=host(self.enc_memory)
            if self.spec.needs_encoder_memory else None,
            slot_used=list(self._slot_used),
            host_free=list(self.host_pool._free) if self.tiered else None,
            host_ref=dict(self.host_pool._ref) if self.tiered else None,
            tiered_entries=tiered_entries,
            prefill_kv=jax.tree_util.tree_map(host, self.prefill_pool)
            if self.disagg else None,
            prefill_alloc_free=list(self.prefill_allocator._free)
            if self.disagg else None,
            prefill_alloc_ref=dict(self.prefill_allocator._ref)
            if self.disagg else None,
            prefill_slot_pages=[list(r) for r in self._prefill_slot_pages]
            if self.disagg else None,
            prefill_table=self.prefill_table_np.copy()
            if self.disagg else None)
        self.trace.append({"event": "snapshot", "tick": self._tick,
                           "fingerprint": self.plan.fingerprint})
        return snap

    def restore(self, snap: EngineSnapshot) -> None:
        """Load a :meth:`snapshot` back into this engine — the
        ``upir.memory_restore`` MemOp. The snapshot must come from the same
        decode plan (fingerprint-pinned: geometry, scheduling, and the
        fault-tolerance contract all participate); after restore the engine
        resumes every in-flight stream bitwise where the snapshot left off.
        The snapshot itself is not consumed — it deep-copies its request
        objects back in, so one snapshot can seed several engines."""
        if snap.fingerprint != self.plan.fingerprint:
            raise ValueError(
                f"snapshot was taken under plan {snap.fingerprint}, this "
                f"engine runs {self.plan.fingerprint}: model/geometry/"
                f"scheduling/fault-tolerance must match for bitwise resume")
        if self._spec is not None:
            raise ValueError("restore does not support speculative engines")
        slots_c, queue_c, prefill_c = copy.deepcopy(
            (snap.slots_req, snap.queue, snap.prefilling))
        if self.paged:
            self.pool = jax.tree_util.tree_map(jnp.asarray, snap.kv)
            self.allocator = PagedKVAllocator(self.num_pages)
            self.allocator._free = list(snap.alloc_free)
            self.allocator._ref = dict(snap.alloc_ref)
            self.page_table_np = snap.page_table.copy()
            self._slot_pages = [list(r) for r in snap.slot_pages]
            if self.prefix_cache:
                # rebuild the index in snapshot (= LRU) order; the allocator
                # refcounts restored above already include the index's
                # references, so registration takes no new ones
                self.prefix_index = PrefixIndex(
                    self.ecfg.page_size,
                    salt=f"{self.cfg.name}/{self.plan.fingerprint}")
                if self.tiered:
                    # host pool first (exact free-list order for replay
                    # determinism), then both entry kinds in LRU order
                    self.host_pool = HostPagePool(self.host_pages)
                    self.host_pool._free = list(snap.host_free)
                    self.host_pool._ref = dict(snap.host_ref)
                    for k, kind, payload, logits in snap.tiered_entries or []:
                        if kind == "device":
                            self.prefix_index.register(k, payload)
                        else:
                            hid, k_np, v_np = payload
                            self.host_pool.store(hid, k_np.copy(),
                                                 v_np.copy())
                            self.prefix_index.insert_host(k, hid)
                        if logits is not None:
                            self.prefix_index.attach_logits(
                                k, jnp.asarray(logits))
                else:
                    for k, page, logits in snap.prefix_entries or []:
                        self.prefix_index.register(k, page)
                        if logits is not None:
                            self.prefix_index.attach_logits(
                                k, jnp.asarray(logits))
            if self.disagg:
                self.prefill_pool = jax.tree_util.tree_map(
                    jnp.asarray, snap.prefill_kv)
                self.prefill_allocator = PagedKVAllocator(self.prefill_pages)
                self.prefill_allocator._free = list(snap.prefill_alloc_free)
                self.prefill_allocator._ref = dict(snap.prefill_alloc_ref)
                self.prefill_table_np = snap.prefill_table.copy()
                self._prefill_slot_pages = [list(r)
                                            for r in snap.prefill_slot_pages]
        else:
            self.cache = jax.tree_util.tree_map(jnp.asarray, snap.kv)
        if self.spec.needs_encoder_memory and snap.enc_memory is not None:
            self.enc_memory = jnp.asarray(snap.enc_memory)
        self.tokens = jnp.asarray(snap.tokens)
        self.pos = snap.pos.copy()
        self.finished = jnp.asarray(snap.finished)
        self.poisoned = jnp.asarray(snap.poisoned)
        self.counts = jnp.asarray(snap.counts)
        self.keys_np = snap.policy_np["keys"].copy()
        self.temps_np = snap.policy_np["temps"].copy()
        self.topks_np = snap.policy_np["topks"].copy()
        self.topps_np = snap.policy_np["topps"].copy()
        self.eos_np = snap.policy_np["eos"].copy()
        self.presence_np = snap.policy_np["presence"].copy()
        self.frequency_np = snap.policy_np["frequency"].copy()
        self._policy_dev = None
        self._poison_np[:] = False
        self._poison_dev = None
        self.slots_req = list(slots_c)
        self.queue = deque(queue_c)
        self._prefilling = dict(prefill_c)
        self._slot_used = list(snap.slot_used
                               if snap.slot_used is not None
                               else [True] * self.ecfg.slots)
        self._pending_tokens = {rid: list(v)
                                for rid, v in snap.pending_tokens.items()}
        self._toklog = []
        self._rid = snap.rid
        self._admit_counter = snap.admit_counter
        self._tick = snap.tick
        self.degraded = False
        self.trace.append({"event": "restore", "tick": self._tick,
                           "fingerprint": snap.fingerprint})

    # -------------------------------------------------------------- stats

    def reset_stats(self) -> None:
        """Zero the counters (keep compiled artifacts) — call after warmup so
        throughput numbers exclude jit compilation. Also restarts the engine
        tick clock and the ``FaultPlan`` fired-counts: fault steps are
        measured from the last reset, which is what makes the warm → reset →
        measure pattern give predictable injection ticks."""
        self.decode_steps = 0
        self.prefills = 0
        self.prefill_chunks = 0
        self.spec_steps = 0
        self.draft_proposed = 0
        self.draft_accepted = 0
        self.recycles = 0
        self.rejected = 0
        self.submitted = 0
        self.admitted = 0
        self.completed = 0
        self.eos_finished = 0
        self.evictions = 0
        self.preemptions = 0
        self.slo_attained = 0
        self.slo_missed = 0
        self.slo_by_class: Dict[int, List[int]] = {}
        self.tokens_generated = 0
        self.prefill_tokens = 0
        self.peak_concurrent = 0
        self.peak_pages = 0
        self.prefix_hits = 0
        self.prefix_full_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_tokens = 0
        self.prefix_reclaimed = 0
        self.cow_copies = 0
        self.spilled = 0
        self.paged_in = 0
        self.kv_transfers = 0
        self.kv_transfer_pages = 0
        self.rejected_queue_full = 0
        self.shed_deadline = 0
        self.admit_wait_slots = 0
        self.admit_wait_pages = 0
        self.decode_live_blocks = 0
        self.decode_dispatched_blocks = 0
        self.faults_injected = 0
        self.quarantines = 0
        self.recovered = 0
        self.failed = 0
        self.watchdog_trips = 0
        self.degraded_steps = 0
        self.degraded_entries = 0
        self.failures: List[FailureInfo] = []
        self._fault_fired: Dict[int, int] = {}
        self._tick = 0
        self._occupancy_sum = 0
        self.elapsed_s = 0.0
        # telemetry resets with the rest of the observability state — the
        # warm → reset → measure pattern must start the measured run with an
        # empty event ring and zeroed histograms (including the lazily
        # created per-class TTFT ones), or warmup samples pollute the run
        if self.telemetry is not None:
            self.telemetry.reset()

    def stats(self) -> EngineStats:
        """Typed counter snapshot (``EngineStats``). The mapping view keeps
        the old dict-style reads (``stats()["decode_steps"]``) working."""
        occ = (self._occupancy_sum / self.decode_steps / self.ecfg.slots
               if self.decode_steps else 0.0)
        depth_by_class: Dict[int, int] = {}
        for r in self.queue:
            depth_by_class[r.priority_class] = \
                depth_by_class.get(r.priority_class, 0) + 1
        slo_total = self.slo_attained + self.slo_missed
        out = EngineStats(
            queue_depth=len(self.queue),
            active_slots=sum(1 for r in self.slots_req if r is not None),
            slots=self.ecfg.slots,
            kv_layout=self.ecfg.kv_layout,
            capabilities=list(self.spec.capabilities),
            policy=self.policy.describe(),
            decode_steps=self.decode_steps,
            prefills=self.prefills,
            recycles=self.recycles,
            submitted=self.submitted,
            admitted=self.admitted,
            completed=self.completed,
            eos_finished=self.eos_finished,
            rejected=self.rejected,
            preemptions=self.preemptions,
            batch_occupancy=occ,
            peak_concurrent=self.peak_concurrent,
            tokens_generated=self.tokens_generated,
            prefill_tokens=self.prefill_tokens,
            elapsed_s=self.elapsed_s,
            tokens_per_s=(self.tokens_generated / self.elapsed_s
                          if self.elapsed_s else 0.0),
            queue_depth_by_class=depth_by_class,
            slo_attained=self.slo_attained,
            slo_missed=self.slo_missed,
            slo_attainment=(self.slo_attained / slo_total
                            if slo_total else None),
            slo_by_class={c: ok / (ok + miss)
                          for c, (ok, miss) in sorted(
                              self.slo_by_class.items())},
            rejected_queue_full=self.rejected_queue_full,
            shed_deadline=self.shed_deadline,
            admit_wait_slots=self.admit_wait_slots,
            plan_cache=self.plan_cache.stats(),
        )
        if self.paged:
            out.page_size = self.ecfg.page_size
            out.num_pages = self.num_pages
            out.pages_in_use = self.allocator.in_use
            out.peak_pages = self.peak_pages
            out.evictions = self.evictions
            out.prefill_chunks = self.prefill_chunks
            out.admit_wait_pages = self.admit_wait_pages
        if self.decode_widths:
            out.decode_live_blocks = self.decode_live_blocks
            out.decode_dispatched_blocks = self.decode_dispatched_blocks
            out.decode_block_fill = (
                self.decode_live_blocks / self.decode_dispatched_blocks
                if self.decode_dispatched_blocks else 0.0)
        if self.prefix_cache:
            out.prefix_hits = self.prefix_hits
            out.prefix_full_hits = self.prefix_full_hits
            out.prefix_misses = self.prefix_misses
            out.prefix_hit_tokens = self.prefix_hit_tokens
            out.prefix_reclaimed = self.prefix_reclaimed
            out.cow_copies = self.cow_copies
            out.prefix_cached_pages = len(self.prefix_index)
            out.shared_pages = self.allocator.shared_pages
        if self.tiered:
            out.host_pages = self.host_pages
            out.host_pages_in_use = self.host_pool.in_use
            out.spilled = self.spilled
            out.paged_in = self.paged_in
        if self.disagg:
            out.prefill_pool_pages = self.prefill_pages
            out.kv_transfers = self.kv_transfers
            out.kv_transfer_pages = self.kv_transfer_pages
        if self.spec_cfg is not None:
            out.spec_steps = self.spec_steps
            out.lookahead_k = self.spec_cfg.lookahead_k
            out.draft_arch = self.spec_cfg.draft_config.name
            out.draft_proposed = self.draft_proposed
            out.draft_accepted = self.draft_accepted
            out.acceptance_rate = (self.draft_accepted / self.draft_proposed
                                   if self.draft_proposed else 0.0)
            out.degraded_steps = self.degraded_steps
            out.degraded_entries = self.degraded_entries
        if self.ft:
            out.faults_injected = self.faults_injected
            out.quarantines = self.quarantines
            out.recovered = self.recovered
            out.failed = self.failed
            out.watchdog_trips = self.watchdog_trips
            out.failures = list(self.failures)
        if self.telemetry is not None:
            out.telemetry = self.telemetry.section()
        return out


# ------------------------------------------------------- sequential baseline


def serve_sequential(cfg: ArchConfig, params,
                     requests: Sequence[Union[Request, RequestSpec]], *,
                     max_seq: int, prompt_buckets: Tuple[int, ...] = (16, 32, 64),
                     warmup: bool = True) -> Dict[str, Any]:
    """The pre-engine path: one request at a time, B=1 prefill + B=1 decode
    loop. Pads prompts to the same buckets as the engine so token streams are
    comparable; ``warmup`` compiles both steps before the timed region.

    Accepts :class:`RequestSpec` entries (materialized here with
    ``rid = position + 1`` — exactly the rids a fresh :class:`Engine` would
    assign the same sequence, so PRNG key schedules line up) or
    already-materialized ``Request`` objects.

    Speaks the same decode API as the engine — per-request
    ``SamplingParams`` / ``eos_id`` (including repetition penalties, via the
    same input-token count-then-select order as the engine's decode step)
    through the shared ``sample_tokens`` key schedule, and encoder-decoder
    requests via their ``encoder_input`` frames — so it doubles as the
    reference for engine stream equality, greedy *and* sampled.

    Mirrors engine accounting: over-horizon requests are marked rejected and
    excluded from throughput (not silently served as empty), and
    ``tokens_per_s`` counts decode-loop tokens only (the first token of each
    request comes from prefill logits and is tallied in ``prefill_tokens``).
    Returns per-request tokens + aggregate throughput."""
    spec = api.family_spec(cfg)
    mats: List[Request] = []
    for i, r in enumerate(requests):
        if isinstance(r, RequestSpec):
            mats.append(Request(
                rid=i + 1, prompt=list(r.prompt),
                max_new_tokens=r.max_new_tokens, sampling=r.sampling,
                eos_id=r.eos_id, encoder_input=r.encoder_input,
                tenant=r.tenant, priority_class=r.priority_class,
                deadline_ms=r.deadline_ms,
                _key=request_key(r.sampling or GREEDY, i + 1)))
        else:
            mats.append(r)
    requests = mats

    def pre(params, batch, key, temp, topk, topp):
        logits, cache = api.prefill(cfg, params, batch, s_max=max_seq)
        last = jnp.full((1,), batch["tokens"].shape[1] - 1, jnp.int32)
        nxt = sample_tokens(logits[:, -1], key[None], last,
                            temp[None], topk[None], topp[None])
        return nxt, cache

    def dec(params, cache, tokens, pos, key, temp, topk, topp, counts,
            presence, frequency):
        logits, cache = api.decode_step(cfg, params, cache,
                                        {"tokens": tokens, "pos": pos})
        # count the step's input token (the previous emission) before
        # selecting — the same order as the engine's decode step, so
        # penalized streams agree bitwise
        counts = counts.at[jnp.arange(1), tokens[:, 0]].add(1)
        nxt = sample_tokens(logits[:, -1], key[None], pos,
                            temp[None], topk[None], topp[None],
                            counts=counts, presence=presence[None],
                            frequency=frequency[None])
        return nxt, cache, counts

    prefill_fn = jax.jit(pre)
    decode_fn = jax.jit(dec, donate_argnums=(1, 8))

    def batch_for(tokens_row, req):
        batch = {"tokens": jnp.asarray(tokens_row)[None, :]}
        if spec.needs_encoder_memory:
            batch["audio_embeds"] = jnp.asarray(req.encoder_input)[None]
        return batch

    def policy(req):
        s = req.sampling or GREEDY
        key = req._key if req._key is not None else request_key(s, req.rid)
        return (jnp.asarray(key), jnp.float32(s.temperature),
                jnp.int32(s.top_k), jnp.float32(s.top_p),
                jnp.float32(s.presence_penalty),
                jnp.float32(s.frequency_penalty))

    if warmup and requests:
        by_bucket = {}
        for r in requests:
            b = next((b for b in sorted(prompt_buckets)
                      if b >= len(r.prompt)), None)
            if b is not None:
                by_bucket.setdefault(b, r)
        for b, r in by_bucket.items():
            k, t, tk, tp, pp, fp = policy(r)
            nxt, cache = prefill_fn(params, batch_for(np.zeros(b, np.int32), r),
                                    k, t, tk, tp)
            nxt, cache, _ = decode_fn(params, cache, nxt[:, None],
                                      jnp.full((1,), b, jnp.int32), k, t,
                                      tk, tp,
                                      jnp.zeros((1, cfg.vocab), jnp.int32),
                                      pp, fp)
            jax.block_until_ready(nxt)

    outputs: Dict[int, List[int]] = {}
    total = 0
    prefill_tokens = 0
    rejected = 0
    t0 = time.perf_counter()
    for req in requests:
        bucket = next((b for b in sorted(prompt_buckets)
                       if b >= len(req.prompt)), None)
        if bucket is None:
            req.state, req.reason = "rejected", \
                f"prompt len {len(req.prompt)} exceeds largest bucket"
            rejected += 1
            continue
        if bucket + req.max_new_tokens > max_seq:
            req.state, req.reason = "rejected", \
                f"bucket {bucket} + {req.max_new_tokens} new tokens exceeds " \
                f"max_seq {max_seq}"
            rejected += 1
            continue
        toks = np.zeros((bucket,), np.int32)
        toks[:len(req.prompt)] = np.asarray(req.prompt, np.int32)
        k, t, tk, tp, pp, fp = policy(req)
        nxt, cache = prefill_fn(params, batch_for(toks, req), k, t, tk, tp)
        gen = [nxt]
        counts = jnp.zeros((1, cfg.vocab), jnp.int32)
        # the sequential path syncs per token only when a request opts into
        # EOS (it must know when to stop); the engine never has to
        hit_eos = req.eos_id is not None and \
            int(np.asarray(nxt)[0]) == req.eos_id
        if not hit_eos:
            for i in range(req.max_new_tokens - 1):
                pos = jnp.full((1,), bucket + i, jnp.int32)
                nxt, cache, counts = decode_fn(params, cache, gen[-1][:, None],
                                               pos, k, t, tk, tp, counts,
                                               pp, fp)
                gen.append(nxt)
                if req.eos_id is not None and \
                        int(np.asarray(nxt)[0]) == req.eos_id:
                    break
        jax.block_until_ready(gen[-1])
        outputs[req.rid] = [int(np.asarray(g)[0]) for g in gen]
        req.state = "done"
        prefill_tokens += 1
        total += len(gen) - 1
    elapsed = time.perf_counter() - t0
    return {"tokens": outputs, "tokens_generated": total,
            "prefill_tokens": prefill_tokens,
            "served": len(outputs), "rejected": rejected,
            "elapsed_s": elapsed,
            "tokens_per_s": total / elapsed if elapsed else 0.0}
