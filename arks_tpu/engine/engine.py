"""Continuous-batching inference engine.

This is the component the reference outsources entirely to vLLM/SGLang
containers (it only writes their command lines —
/root/reference/internal/controller/arksapplication_controller.go:941-1014).
Here it is TPU-native:

- **Slot model**: a fixed decode batch of ``num_slots`` sequences, each
  owning a stretch of the slotted KV cache.  Prompts are prefilled one at a
  time into bucketed-length compiled programs, then inserted into a free
  slot; decode advances all slots together.
- **Fused dispatch**: ``steps_per_dispatch`` decode steps + on-device
  sampling run inside ONE jitted ``lax.scan`` per dispatch, and only the
  sampled ids [K, B] come back to the host.
- **Host-authoritative scheduling**: lengths/last-token mirrors live on the
  host; device state is params + cache + sampler keys.  The scheduler
  decides admission, stopping, and slot reuse between dispatches.

All jax work happens on the engine thread; the server talks to it via
thread-safe queues (Request.outputs).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import queue
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from arks_tpu.engine import fairqueue
from arks_tpu.engine import faults as faults_mod
from arks_tpu.engine import sampler as sampler_mod
from arks_tpu.engine.faults import StepFault
from arks_tpu.engine.guides import GuideError
from arks_tpu.engine.model_pool import LoadTicket, ModelPool, PoolFullError
from arks_tpu.engine.tokenizer import Tokenizer
from arks_tpu.engine.types import (PrefilledState, Request, RequestOutput,
                                   SamplingParams)
from arks_tpu.models.config import ModelConfig
from arks_tpu.models import moe as moe_mod
from arks_tpu.models import transformer as tf
from arks_tpu.obs import logctx
from arks_tpu.obs import profiler as prof_mod
from arks_tpu.obs import stepclock as stepclock_mod
from arks_tpu.obs import trace as trace_mod
from arks_tpu.utils import knobs
from arks_tpu.utils import metrics as prom
from arks_tpu import slo as slo_mod
from arks_tpu import tenancy

log = logging.getLogger("arks_tpu.engine")
logctx.install(log)


class ContextLengthExceededError(ValueError):
    """Prompt does not fit the serving window.  OpenAI-compatible servers
    must surface this as HTTP 400 with code ``context_length_exceeded`` —
    silently truncating would corrupt long-context results and billing."""


@dataclasses.dataclass
class EngineConfig:
    model: str = "tiny"
    num_slots: int = 8
    max_cache_len: int = 1024
    prefill_buckets: tuple[int, ...] = (32, 64, 128, 256, 512, 1024)
    steps_per_dispatch: int = 4
    # Chunked prefill: prompts longer than the largest one-shot bucket are
    # processed in chunks of this many tokens, one chunk per scheduler step,
    # INTERLEAVED with decode dispatches — a burst of long prompts no longer
    # freezes every decoding slot.  None disables (long prompts then 400).
    prefill_chunk: int | None = 256
    # Parallelism: when a mesh isn't passed to InferenceEngine explicitly,
    # one is built from these over all visible devices (tp defaults to
    # devices/dp). All 1 (or 1 visible device) → no mesh, single-chip path.
    # context_parallel > 1 shards prefill's T over the 'seq' axis and runs
    # ring attention — the long-context serving path (prompts beyond one
    # chip's prefill budget; decode replicates over the seq axis, so cp
    # belongs on prefill-heavy tiers, e.g. the disaggregated prefill role).
    tensor_parallel: int | None = None
    data_parallel: int = 1
    context_parallel: int = 1
    # pipeline_parallel > 1 shards LAYERS (and each layer's KV) over the
    # 'stage' axis: HBM capacity scales with stages, for models whose
    # weights+KV exceed one chip.  Decode pipelines microbatches of slots
    # across stages (parallel.pipeline.pp_decode_step); prefill runs
    # one-shot through the stages.  Mutually exclusive with tp/dp/cp in the
    # engine (compose via multi-group replicas instead); chunked prefill
    # and the prefix cache are disabled under pp (their dynamic layer
    # indexing would gather the stage-sharded cache).
    pipeline_parallel: int = 1
    # Speculative decoding: a small draft model proposes draft_len-1 tokens
    # per dispatch and the target verifies them as RAGGED q_len=draft_len
    # rows of the SAME mixed dispatch that carries decode feeds and
    # prefill chunks (transformer.mixed_step / paged_mixed_attention) —
    # draft propose + verify + acceptance run inside ONE program per
    # scheduler iteration, and the spec engine keeps the mixed engine's
    # pipelining, guided decoding, and token-replay fault recovery.
    # Greedy slots keep the longest argmax-matching prefix plus one bonus
    # token — emitted tokens IDENTICAL to target-only greedy decoding.
    # Sampled slots use rejection sampling (sampler.speculative_accept) —
    # exact in DISTRIBUTION against the engine's own effective sampling
    # dist.  Requires the mixed scheduler (paged KV layout + chunked
    # prefill); dp/pp-exclusive.  Multi-host gangs mirror the
    # draft-prefill and spec_mixed dispatches like any other op.
    draft_model: str | None = None
    draft_len: int = 4
    dtype: str | None = None   # default: model config dtype
    # "auto"|"bf16"|"int8"|"int4": int8 halves KV HBM traffic and doubles
    # cache capacity (per-token scales, dequantized inside the attention
    # kernel).  int4 packs token pairs into one byte (same per-token scale
    # stripes) — half the page bytes again; requires the paged layout
    # (dequant is fused on the mixed kernel's page stream; there is no
    # int4 slot-cache kernel).  auto = int8 on real TPU (what the
    # benchmark's cells run), engine dtype elsewhere (CPU tests stay
    # full-width).
    kv_cache_dtype: str = "auto"
    # "bf16"|"int8"|"int4": weight-only quantization (models.quant).
    # int8 = w8a16 (per-output-channel scales, dequant fused into the
    # matmuls) — how 7B-class models fit a 16GB v5e chip, and it halves
    # decode weight reads.  int4 = w4a16 (per-128-row-group scales,
    # embedding stays int8) — halves weight bytes again: 13B-class
    # single-chip, or the freed HBM becomes KV pages.
    weight_dtype: str = "bf16"
    # "auto"|"slot"|"paged": device KV layout.  "paged" = block-table pool
    # (ops.paged_attention) with zero-copy on-device prefix sharing; the
    # layout every benchmark cell runs (PERF.md §4), and it works on
    # multi-host gangs.  "auto" = paged on TPU whenever the
    # engine shape allows (no pp / dp, lane-aligned head_dim,
    # chunk == page alignment); slot elsewhere — the slot layout remains
    # the fallback for those paths.  Speculative decoding REQUIRES paged
    # (verify blocks are ragged rows of the mixed dispatch; the draft
    # mirror stays slot-contiguous — it is num_slots x draft-model sized,
    # where paging buys nothing), so "auto" resolves to paged for draft
    # engines on every backend whose shape allows it.
    # Context parallelism pages too (one-shot prefill rides the ring;
    # the pool is seq-replicated, so tables/pages are unaffected — chunk
    # tails run unsharded over seq, as they do on the slot layout).
    # Pipeline parallelism pages too: the pool shards over 'stage' on its
    # layer dim and decode pipelines microbatches through the block
    # tables (parallel.pipeline.pp_decode_step_paged) — page-granular
    # allocation instead of per-slot max_cache_len reservations, the HBM
    # lever pp exists for (chunking/prefix reuse stay off under pp).
    # dp stays slot by design: the pool has no batch dim to shard and
    # per-dp-shard pools would fragment the prefix index.
    kv_layout: str = "auto"
    # Host-RAM budget for the prefix KV cache (0 disables).  Shared prompt
    # prefixes (system prompts, few-shot preambles, multi-turn history)
    # skip recomputation: cached blocks are inserted and only the tail is
    # prefilled.  Requires prefill_chunk (reuse lands on chunk boundaries);
    # single-host only (harvest needs fully-addressable arrays).
    prefix_cache_mb: int = 256
    # Pages of the full-attention pool of a model with window and full
    # attention layers (0: every slot's whole context, num_slots x
    # max_cache_len).  Below that, admission RESERVES a request's pages
    # (prompt + max_tokens) before it takes a slot, and a request the
    # pool cannot hold yet waits at the head of the queue
    # (InferenceEngine._pool_fits).  Refused by name for any other model:
    # their admission paths count slots only.
    kv_pool_pages: int = 0
    seed: int = 0

    def resolve_kv_cache_dtype(self) -> str:
        """Returns 'int8' | 'int4' | 'bf16' | 'engine' (= engine dtype)."""
        if self.kv_cache_dtype not in ("auto", "bf16", "int8", "int4"):
            raise ValueError(f"kv_cache_dtype={self.kv_cache_dtype!r}")
        if self.kv_cache_dtype == "auto":
            import jax
            return "int8" if jax.default_backend() == "tpu" else "engine"
        return self.kv_cache_dtype

    @property
    def kv_quantized(self) -> bool:
        return self.resolve_kv_cache_dtype() in ("int8", "int4")

    @property
    def kv_bits(self) -> int:
        """Stored bits per KV element: 4 / 8 / 16."""
        kvd = self.resolve_kv_cache_dtype()
        return {"int4": 4, "int8": 8}.get(kvd, 16)

    def resolve_buckets(self) -> list[int]:
        """Prefill buckets clamped to the cache; never empty."""
        buckets = sorted(b for b in self.prefill_buckets if b <= self.max_cache_len)
        if not buckets:
            buckets = [self.max_cache_len]
        elif buckets[-1] < self.max_cache_len and not self.prefill_chunk:
            # No chunked path: the one-shot buckets must cover full-cache-
            # length prompts.  (With chunking, prompts beyond the largest
            # bucket run chunked — appending a full-length bucket here would
            # make every long prompt monolithic again.)
            buckets.append(self.max_cache_len)
        return buckets

    def cache_len_alignment(self) -> int:
        """Required max_cache_len alignment for the Pallas decode path.

        The in-place cache-update kernels DMA along S in fixed tiles (16 for
        bf16, 128 for the int8 per-token scales) and the ragged attention
        grid needs S % min(block_s, S) == 0 (block_s = ARKS_ATTN_BLOCK_S,
        default 256) — so any cache length ≥ block_s must be a multiple of
        block_s (block_s is itself tile-aligned), and shorter caches a
        multiple of the update tile.
        """
        from arks_tpu.ops.attention import default_decode_impl
        if default_decode_impl() != "pallas":
            return 1
        block_s = knobs.get_int("ARKS_ATTN_BLOCK_S")
        if self.max_cache_len >= block_s:
            return block_s
        return 128 if self.kv_quantized else 16

    def align_cache_len(self) -> None:
        """Round max_cache_len up to the kernel alignment (warn if changed).

        Called at engine startup so a misconfigured --max-model-len fails
        (or self-corrects) immediately instead of raising a ValueError deep
        inside the first decode dispatch.
        """
        align = self.cache_len_alignment()
        rounded = -(-self.max_cache_len // align) * align
        if rounded != self.max_cache_len:
            log.warning(
                "max_cache_len=%d is not %d-aligned for the Pallas decode "
                "kernels (kv=%s); rounding up to %d",
                self.max_cache_len, align, self.resolve_kv_cache_dtype(),
                rounded)
            self.max_cache_len = rounded


@dataclasses.dataclass
class _Slot:
    request: Request
    num_prompt: int
    generated: list[int] = dataclasses.field(default_factory=list)
    num_emitted: int = 0  # tokens already streamed to the request queue
    first_token_time: float | None = None
    # Speculative decoding: the draft cache mirrors this slot's rows
    # (prompt draft-prefilled at registration).  The spec-mixed dispatch
    # feeds the draft the REAL last token every step, so the mirror stays
    # in sync for the slot's whole life whether or not it speculates.
    draft_synced: bool = False
    # Spec eligibility, frozen at registration (pure function of the
    # request): draft-synced, penalty-free, no logprobs/bias/min_tokens.
    # Guided slots ARE eligible — verify-aware DFA advancement
    # (sampler.speculative_accept) keeps the grammar exact.  Frozen
    # eligibility is what makes spec engines replay-safe: a lane's PRNG
    # key advances by the same per-dispatch structure on every re-run.
    spec_ok: bool = False
    # Per-token logprob entries parallel to ``generated`` (only populated
    # when the request asked for logprobs): (chosen_lp, [(id, lp), ...]).
    logprobs: list = dataclasses.field(default_factory=list)
    # Pipelined decode (ARKS_PIPELINE_DEPTH): the device stop column for
    # this slot (None = stop set exceeds sampler.STOP_IDS_MAX, slot rides
    # the sequential path) and the absolute length at which the device
    # must stop dispatching it (min of the max_tokens cutoff and the
    # cache-cap margin) — both frozen at registration.
    stop_col: object = None   # np.ndarray [STOP_IDS_MAX] | None
    dead_len: int = 0
    # Sampling seed (request seed or the engine-assigned one) — fault
    # recovery reconstructs the slot's key stream from it (advance_key).
    seed: int = 0


@dataclasses.dataclass
class _ChunkState:
    """A chunked prefill in progress (slot reserved, not yet decoding)."""

    request: Request
    ids: list[int]
    pos: int      # tokens already prefilled
    seed: int     # sampling seed (key = PRNGKey(seed))
    # Base sampling key (PRNGKey(seed)), kept on the HOST: the step packs
    # it into its override columns and the promotion folds it inside its
    # program, so no step reads it back from the device.
    key: np.ndarray
    # Paged layout: the prompt's chained page digests (computed at match
    # time), registered into the allocator's prefix index at promote.
    digests: list | None = None


@dataclasses.dataclass
class _RestoreState:
    """A tier-1 (host-RAM) prefix restore in flight: the request parks
    here (mirroring the guide_wait park) while its H2D scatter dispatch
    rides the device stream behind the pipelined decode; once the marker
    resolves, only the un-hit prompt tail goes through chunked prefill."""

    request: Request
    ids: list[int]
    digests: list        # full prompt digest chain (computed at match)
    shared: list[int]    # tier-0 device pages (caller refs held by us)
    pages: list[int]     # freshly-allocated pages the scatter writes
    marker: object       # device scalar from the last scatter dispatch
    seed: int
    t0: float


@dataclasses.dataclass
class _FetchState:
    """A tier-2 / peer prefix fetch in flight: the request parks here
    while a worker thread stages the missing blocks from the local disk
    tier (DiskPrefixTier.get) and/or a peer replica (GET
    /v1/cache/blocks/{digest}) INTO THE HOST TIER.  No device pages are
    held across the park — _resolve_fetches re-runs the admission match
    from scratch, so the unparked request rides the existing tier-1
    restore path (or plain chunked prefill if the fetch came up empty).
    The worker writes only `done`/`fetched_*`; the engine thread owns
    membership in _awaiting_fetch."""

    request: Request
    ids: list[int]
    digests: list          # full prompt digest chain (computed at match)
    start: int             # first uncovered digest index at park time
    peer: str | None       # hinted peer base address ("host:port")
    seed: int
    t0: float
    done: bool = False
    fetched_disk: int = 0  # blocks staged from the local disk tier
    fetched_peer: int = 0  # blocks staged from the peer


@dataclasses.dataclass
class _SwapRecord:
    """A preempted request's host-side slot snapshot (ARKS_PREEMPT):
    everything `_finish_resume` needs to rebuild the victim's `_Slot` and
    host mirrors byte-identically once its KV pages scatter back.  The
    device-side halves (KV page blocks, sampler row) live in the
    SwapStore entry keyed by the same request id."""

    request: Request
    num_prompt: int
    generated: list
    num_emitted: int
    logprobs: list
    first_token_time: float | None
    seed: int
    length: int       # host lengths mirror at preempt (valid KV rows)
    last_token: int   # host last-token mirror at preempt
    stop_col: object
    dead_len: int
    n_pages: int      # pool pages covering rows [0, length)
    priority: int
    t0: float         # preempt issue time (preempt_swap_seconds)


@dataclasses.dataclass
class _SwapState:
    """An in-flight preempt spill: the victim's slot is already freed
    (stream order guarantees the gathers below read pre-reuse bytes) and
    these D2H copies are draining."""

    rec: _SwapRecord
    staged: list   # [(n_valid, gather outputs)] per spill group
    row: tuple     # (key[2], counts[V], guide_row) device arrays


@dataclasses.dataclass
class _ResumeState:
    """A preempt-swap restore in flight: the resumed request holds
    ``slot`` (popped from _free) while its page blocks scatter back; it
    parks in ``_awaiting_restore`` beside the prefix ``_RestoreState``s
    and lands via ``_finish_resume`` once the marker resolves — no
    prefill, no first-token output, the stream just continues."""

    rec: _SwapRecord
    slot: int
    pages: list[int]
    marker: object
    t0: float

    @property
    def request(self) -> Request:
        return self.rec.request

    @property
    def ids(self) -> list[int]:
        return self.rec.request.prompt_ids


@dataclasses.dataclass
class _ResizeRequest:
    """A pending live-topology resize, posted by ``request_resize`` from
    any thread and serviced by the engine thread's elastic state machine
    (drain -> reshard -> rebuild -> resume).  ``event`` fires when the
    resize completes, is rejected, or faults; ``outcome``/``error``
    carry the result."""

    tensor_parallel: int
    data_parallel: int
    event: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    t0: float = dataclasses.field(default_factory=time.monotonic)
    active: bool = False
    drain_t0: float = 0.0
    outcome: str | None = None    # "ok" | "rejected" | "error"
    error: str | None = None
    seconds: float = 0.0

    def wait(self, timeout: float | None = None) -> bool:
        return self.event.wait(timeout)


class _WarmupSink:
    """Output sink for engine-issued warm-up requests: tokens go nowhere
    (the point is compiling/priming the new shape, not the text)."""

    def put(self, item) -> None:
        pass


@dataclasses.dataclass
class _Survivor:
    """An in-flight request's replayable state, snapshotted at a step
    fault (engine._recover_from_fault).  ``generated`` empty = the request
    had emitted nothing (queued/prefilling/deferred admission) and simply
    re-queues; non-empty = token-replay resume (deterministic
    re-execution behind a _ReplayGate — see that class)."""

    request: Request
    seed: int
    num_prompt: int
    generated: list = dataclasses.field(default_factory=list)
    num_emitted: int = 0
    logprobs: list = dataclasses.field(default_factory=list)
    first_token_time: float | None = None


class _ReplayGate:
    """Token-replay resume by DETERMINISTIC RE-EXECUTION (fault recovery).

    A surviving stream is re-admitted through its ORIGINAL schedule — the
    same admission path, the same compiled programs, the same pinned seed
    — so every regenerated token is byte-identical to the recorded stream
    by run-to-run determinism.  (The alternative, re-prefilling the
    generated tokens and restoring sampler state, recomputes KV rows with
    DIFFERENT program shapes than the original decode wrote them — the
    ulp-level drift occasionally flips a sampled token several steps after
    resume, which is exactly the silent corruption replay must never
    produce.)

    The gate wraps the request's output queue for the re-run:

    - **suppression**: regenerated tokens the client already received
      (the first ``client_total``) are dropped, so the resumed stream has
      no duplicates;
    - **verification**: every regenerated token is checked against the
      recorded stream; a mismatch (broken determinism) fails THIS request
      with an engine_fault error instead of splicing a divergent tail
      onto the client's stream — byte-identity is enforced, not assumed;
    - **re-entrancy**: a second fault during the re-run just restarts the
      cursor (``restart``); ``client_total`` survives, so suppression
      stays exact across nested recoveries.

    put() runs on the engine thread; get() is the server side's
    pass-through to the original queue.
    """

    def __init__(self, inner, engine, request_id: str, expect: list,
                 client_total: int):
        self._inner = inner
        self._engine = engine
        self._rid = request_id
        self.expect = [int(t) for t in expect]
        self.pos = 0              # regenerated tokens seen this run
        self.client_total = client_total  # tokens the client has received
        self.failed = False

    def restart(self, expect: list | None = None) -> None:
        self.pos = 0
        if expect and len(expect) > len(self.expect):
            self.expect = [int(t) for t in expect]

    def get(self, *args, **kwargs):
        return self._inner.get(*args, **kwargs)

    def put(self, out: RequestOutput) -> None:
        if self.failed:
            # The client already saw the divergence error; drop the rest
            # of the doomed re-run (its abort tail included).
            return
        toks = list(out.token_ids)
        start = self.pos
        n_check = min(len(toks), len(self.expect) - start)
        if toks[:n_check] != self.expect[start:start + n_check]:
            self.failed = True
            self._engine.abort(self._rid)
            self._inner.put(RequestOutput(
                request_id=self._rid, token_ids=[], finished=True,
                finish_reason="error",
                error="engine_fault: replay_diverged",
                num_prompt_tokens=out.num_prompt_tokens))
            log.error("replay of %s diverged from the recorded stream at "
                      "token %d; failing the request", self._rid,
                      start + 1)
            return
        self.pos += len(toks)
        skip = max(0, min(self.client_total - start, len(toks)))
        fwd = toks[skip:]
        lps = out.logprobs[skip:] if out.logprobs else None
        if not fwd and not out.finished:
            return  # entirely inside the already-delivered prefix
        self.client_total = max(self.client_total, self.pos)
        self._inner.put(dataclasses.replace(
            out, token_ids=fwd, logprobs=lps, ttft_s=None))


class EngineMetrics:
    """Normalized runtime metric names (what the reference's runtime
    ServiceMonitor relabels vLLM/SGLang names into —
    /root/reference/config/prometheus/monitor-runtime.yaml:13-44)."""

    def __init__(self, registry: prom.Registry | None = None):
        self.registry = registry or prom.Registry()
        r = self.registry
        self.num_requests_running = r.gauge(
            "num_requests_running", "Requests currently decoding")
        self.num_requests_waiting = r.gauge(
            "num_requests_waiting", "Requests queued for admission")
        self.prompt_tokens_total = r.counter(
            "prompt_tokens_total", "Prefilled prompt tokens")
        self.generation_tokens_total = r.counter(
            "generation_tokens_total", "Generated tokens")
        self.time_to_first_token_seconds = r.histogram(
            "time_to_first_token_seconds", "TTFT",
            buckets=[0.01, 0.025, 0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 3.2, 6.4, 12.8])
        self.time_per_output_token_seconds = r.histogram(
            "time_per_output_token_seconds", "TPOT",
            buckets=[0.002, 0.005, 0.01, 0.02, 0.04, 0.08, 0.16, 0.32, 0.64])
        self.e2e_request_latency_seconds = r.histogram(
            "e2e_request_latency_seconds", "End-to-end request latency",
            buckets=[0.1, 0.25, 0.5, 1, 2.5, 5, 10, 20, 40, 80, 160])
        self.request_success_total = r.counter(
            "request_success_total", "Finished requests by reason")
        # Prefix-cache family (reference dashboard's cache hit-rate panel —
        # docs/monitoring.md:118-144 — normalized like the other names).
        self.prefix_cache_query_tokens_total = r.counter(
            "prefix_cache_query_tokens_total",
            "Prompt tokens checked against the prefix cache")
        self.prefix_cache_hit_tokens_total = r.counter(
            "prefix_cache_hit_tokens_total",
            "Prompt tokens served from the prefix cache")
        self.prefix_cache_usage_bytes = r.gauge(
            "prefix_cache_usage_bytes",
            "Bytes held by the prefix cache, by tier (device = retained "
            "pool pages, host = host-RAM blocks)")
        self.prefix_cache_hit_rate = r.gauge(
            "prefix_cache_hit_rate", "Lifetime prefix-cache token hit rate")
        # Hierarchical prefix cache (paged engines): tier 0 is the
        # allocator's on-device page index, tier 1 the host-RAM spill
        # store — the families that make HBM-pressure thrash (spill storm)
        # and restore latency visible on a dashboard.
        self.prefix_spill_blocks_total = r.counter(
            "prefix_spill_blocks_total",
            "KV pages spilled from the device prefix index to the host tier")
        self.prefix_restore_blocks_total = r.counter(
            "prefix_restore_blocks_total",
            "KV pages restored from the host tier into fresh pool pages")
        self.prefix_restore_seconds = r.histogram(
            "prefix_restore_seconds",
            "Host-tier restore latency (scatter issue -> request unparked)",
            buckets=[0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1, 2.5])
        # Tier 2 (DiskPrefixTier) + fleet peer fetch: the families that
        # make disk-budget churn, a poisoned disk tier (corrupt reads),
        # and a peer fetch that lost to re-prefill visible on a dashboard.
        self.prefix_disk_evictions_total = r.counter(
            "prefix_disk_evictions_total",
            "KV page blocks LRU-evicted from the tier-2 disk store past "
            "its byte budget")
        self.prefix_disk_corrupt_total = r.counter(
            "prefix_disk_corrupt_total",
            "Tier-2 block files rejected on read (corrupt, truncated, or "
            "stale-epoch) and deleted")
        self.prefix_peer_fetch_blocks_total = r.counter(
            "prefix_peer_fetch_blocks_total",
            "Prefix KV blocks fetched into the host tier, by source "
            "(disk = local tier 2, peer = remote replica)")
        self.prefix_peer_fetch_seconds = r.histogram(
            "prefix_peer_fetch_seconds",
            "Disk/peer prefix fetch latency (park -> blocks staged in "
            "the host tier)",
            buckets=[0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1,
                     2.5, 5, 10])
        self.guided_requests_total = r.counter(
            "guided_requests_total",
            "Admitted guided-decoding requests by guide kind")
        # Guide compile pipeline (engine.guides): async worker-pool
        # compiles + LRU registry — the families that make a cold-compile
        # stall or an eviction storm visible on a dashboard.
        self.guide_compile_seconds = r.histogram(
            "guide_compile_seconds",
            "Guided-decoding DFA compile latency (worker-pool threads)",
            buckets=[0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120])
        self.guide_cache_hits_total = r.counter(
            "guide_cache_hits_total",
            "Guide requests served from the compiled registry")
        self.guide_cache_misses_total = r.counter(
            "guide_cache_misses_total",
            "Guide requests that scheduled a cold compile")
        self.guide_cache_evictions_total = r.counter(
            "guide_cache_evictions_total",
            "Guides evicted from the registry (LRU, no active slot)")
        self.guide_registry_guides_in_use = r.gauge(
            "guide_registry_guides_in_use",
            "Guides currently packed in the registry")
        self.guide_registry_rows_in_use = r.gauge(
            "guide_registry_rows_in_use",
            "DFA rows currently packed in the transition table")
        self.spec_decode_proposed_tokens_total = r.counter(
            "spec_decode_proposed_tokens_total",
            "Draft tokens proposed to the verifier")
        self.spec_decode_accepted_tokens_total = r.counter(
            "spec_decode_accepted_tokens_total",
            "Draft tokens accepted by the verifier")
        self.spec_decode_acceptance_rate = r.gauge(
            "spec_decode_acceptance_rate",
            "Lifetime draft-token acceptance rate")
        # Per-dispatch accepted-block length (1 = nothing accepted, just
        # the normally-sampled token; draft_len = full block + bonus).
        # The distribution — not just the lifetime rate — is what shows an
        # acceptance COLLAPSE (histogram mass sliding to 1) before
        # throughput falls over (docs/monitoring.md).
        self.spec_decode_accepted_length = r.histogram(
            "spec_decode_accepted_length",
            "Tokens landed per speculating request per spec dispatch",
            buckets=[1, 2, 3, 4, 6, 8, 12, 16])
        # Mixed-step scheduling (ARKS_MIXED_STEP): one token-budget dispatch
        # per iteration carrying decode tokens + prefill-chunk tokens.
        self.mixed_batch_tokens = r.histogram(
            "mixed_batch_tokens",
            "Valid tokens per mixed dispatch (decode + chunk)",
            buckets=[1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048])
        self.mixed_chunk_tokens_total = r.counter(
            "mixed_chunk_tokens_total",
            "Prefill-chunk tokens processed inside mixed dispatches")
        self.mixed_chunk_budget_tokens_total = r.counter(
            "mixed_chunk_budget_tokens_total",
            "Prefill-chunk token budget offered by sequential mixed "
            "dispatches issued while a request was prefilling or waited "
            "in the admission queue (mixed_chunk_tokens_total over this "
            "is the share of the budget the steps used)")
        # Calls the engine thread makes into JAX on the sequential step's
        # path (programs and host-to-device transfers), by site: over the
        # dispatch count (mixed_batch_tokens_count) it reads how often a
        # step speaks to the device (docs/monitoring.md).
        self.step_device_calls_total = r.counter(
            "step_device_calls_total",
            "Device programs and transfers the engine thread issued on "
            "the sequential step path, by site (step, promote, admit, "
            "clear, draft, warm)")
        # Frames handed to the requests' output queues, and those of them
        # that waited for the next dispatch first (deferred delivery: a
        # resolve that found more callers queued than slots free).
        self.fanout_outputs_total = r.counter(
            "fanout_outputs_total",
            "Output frames (token deltas, first tokens, finish and error "
            "frames) the engine handed to its requests' readers")
        self.fanout_deferred_outputs_total = r.counter(
            "fanout_deferred_outputs_total",
            "Output frames delivered behind the next dispatch because the "
            "resolve that made them left nothing in flight on the device "
            "(over fanout_outputs_total: the share of frames made with "
            "the device empty)")
        # An untouched counter renders no sample: the pair stands on
        # /metrics from the first scrape, so that a share of 0 reads 0.
        self.fanout_outputs_total.inc(0)
        self.fanout_deferred_outputs_total.inc(0)
        # XLA compilations seen by this process (jax.monitoring): which
        # step recompiled is an operator's question, not only a bench's.
        self.xla_compilations_total = r.counter(
            "xla_compilations_total",
            "Backend (XLA) compilations in this process since the engine "
            "was built")
        self.xla_compile_seconds_total = r.counter(
            "xla_compile_seconds_total",
            "Seconds spent in backend (XLA) compilation")
        # The page-compute steps the ragged work list runs per mixed
        # dispatch: each (sequence, q block) item's own causal page count
        # (ops.paged_attention.build_mixed_work_list).
        self.mixed_grid_steps_total = r.counter(
            "mixed_grid_steps_total",
            "Page-compute grid steps executed by mixed dispatches")
        # A latent model's pool traffic and a share's routing, counted on
        # the device inside the step and returned with its token ids (no
        # transfer of their own): latent rows written into the pool (one a
        # token a layer), the (token, expert) pairs the routers chose, and
        # those of them that landed on an expert held here.  held / routed
        # is this chip's share of the expert traffic: 1 / the shares under
        # a uniform router (docs/monitoring.md).
        self.mixed_latent_rows_total = r.counter(
            "mixed_latent_rows_total",
            "Latent rows written into the latent KV pool by mixed "
            "dispatches (tokens x layers)")
        self.moe_routed_pairs_total = r.counter(
            "moe_routed_pairs_total",
            "(token, expert) pairs chosen by the routers of mixed "
            "dispatches, over the routers' whole width (latent models)")
        self.moe_held_pairs_total = r.counter(
            "moe_held_pairs_total",
            "(token, expert) pairs of mixed dispatches whose expert is "
            "held by this chip's share of the layer (latent models)")
        # A model whose routers score identity experts: the pairs that
        # landed on one and cost nothing.  routed = held + zero + absent.
        self.moe_zero_pairs_total = r.counter(
            "moe_zero_pairs_total",
            "(token, expert) pairs of mixed dispatches that landed on an "
            "identity (zero-compute) expert (models that have them)")
        # What a share's batched dispatch ran behind its experts' fixed
        # batches (models/moe.py): needed / dispatches / routed layers is
        # the tiles a layer's routers asked for (_SPARE_TILES run in any
        # case), extra > 0 the steps whose time followed the router.
        self.moe_overflow_tiles_total = r.counter(
            "moe_overflow_tiles_total",
            "Overflow tiles of a share's routed layers in mixed dispatches "
            "(needed: rows beyond an expert's fixed batch, in tiles; extra: "
            "those beyond the spare tiles, run in a loop)")
        # The rows those layers put through their experts' contractions
        # (models/moe.py::share_rows: an expert's batch and the spare
        # tiles, the loop's trips, or every row for every held expert in
        # the dense dispatch): moe_held_pairs_total over it is how full the
        # experts' batches ran.
        self.moe_batch_rows_total = r.counter(
            "moe_batch_rows_total",
            "Rows a share's routed layers computed an expert contraction "
            "for in mixed dispatches (batches, spare tiles and the loop's "
            "tiles; rows x held experts under the dense dispatch)")
        # Query rows one mixed dispatch lays out for the attention kernel
        # (the plan's nb x block_q under the ragged grid's block-compacted
        # layout; lanes x the padded widest chunk under the dense grid), to
        # be read against the real rows, the sum of mixed_batch_tokens:
        # the layout's waste factor (docs/monitoring.md).
        self.mixed_q_layout_rows_total = r.counter(
            "mixed_q_layout_rows_total",
            "Query rows laid out for the mixed attention kernel by mixed "
            "dispatches (plan mirror)")
        # Blocks the KV row write reads and writes back for a dispatch, a
        # layer: distinct (slot, position // the pool's block rows) among
        # its live rows.  mixed_batch_tokens_sum over it = rows a block.
        self.mixed_kv_write_blocks_total = r.counter(
            "mixed_kv_write_blocks_total",
            "Blocks (a slot's aligned group of 16 bf16 / 32 int8 / 64 int4 "
            "rows) the KV row write touches in mixed dispatches, a layer")
        # KV bytes-moved pair (engine/paged.mixed_kv_bytes): bytes_total
        # mirrors the ragged kernel's actual DMA schedule (every q-block
        # re-streams its causal page prefix at the PLAN's block_q — the
        # GQA head-grouped autotune entries earn their keep by raising
        # block_q, which this counter shows directly); ideal_total counts
        # each distinct causal page once per dispatch.  The ratio is the
        # KV streaming waste factor (docs/monitoring.md alert).
        self.mixed_kv_bytes_total = r.counter(
            "mixed_kv_bytes_total",
            "KV bytes streamed from HBM by mixed dispatches (plan mirror), "
            "a layer of each kind (full; window: a window layer's launch)")
        # A model with window and full attention layers (two page pools).
        self.kv_pages_in_use = r.gauge(
            "kv_pages_in_use",
            "Pool pages held by slots or the prefix index, by kind (full: "
            "pages that live as long as their sequence; window: a window "
            "layer's, released behind the window)")
        self.kv_pages_reserved = r.gauge(
            "kv_pages_reserved",
            "Pages of the full pool that admission has promised to the "
            "live sequences (prompt + max_tokens each), under "
            "--kv-pool-pages; 0 where the pool holds every slot's whole "
            "context")
        self.admission_page_waits_total = r.counter(
            "admission_page_waits_total",
            "Requests that found a free slot and not the pages of the full "
            "pool their prompt and max_tokens need, and waited at the head "
            "of the queue for them (--kv-pool-pages)")
        self.kv_pool_page_bytes = r.gauge(
            "kv_pool_page_bytes",
            "Bytes one page of a pool holds over all the pool's layers, at "
            "the stored widths of its keys, values and scales, by kind "
            "(full | window); a model with window layers")
        self.kv_window_pages_released_total = r.counter(
            "kv_window_pages_released_total",
            "Window-layer pages released because they lay wholly behind "
            "their slot's window (not: pages of finished sequences)")
        self.kv_window_page_steps_total = r.counter(
            "kv_window_page_steps_total",
            "Window-layer pages summed over mixed dispatches, by state "
            "(held: in use at the dispatch; unreleased: what the same "
            "sequences would hold had no page been released)")
        # A model with linear-attention layers: a fixed state a slot beside
        # the GQA layers' pages (models/transformer.py::LinearState).
        self.linear_state_bytes = r.gauge(
            "linear_state_bytes",
            "Bytes of recurrent state (the delta rule's float32 state and "
            "the convolution carry of every linear layer) held by slots "
            "with a live sequence")
        self.kv_page_bytes = r.gauge(
            "kv_page_bytes",
            "Bytes of the full-attention layers' pages held by slots or "
            "the prefix index (a model with linear-attention layers: its "
            "GQA or latent layers')")
        self.linear_state_starts_total = r.counter(
            "linear_state_starts_total",
            "Sequences that took a slot at position 0, so that the step "
            "program read the slot's recurrent state as zeros")
        self.kv_held_byte_steps_total = r.counter(
            "kv_held_byte_steps_total",
            "Bytes held for live sequences summed over dispatches, by kind "
            "(state: linear_state_bytes; pages: kv_page_bytes), a model "
            "with linear-attention layers")
        self.linear_state_lane_steps_total = r.counter(
            "linear_state_lane_steps_total",
            "Slots summed over dispatches by what the linear layers' state "
            "update did with them (step: a lane of one row, the one-step "
            "kernel's list; chunk: a lane of more rows, the chunked scan; "
            "idle: no row, the state neither read nor written), a model "
            "with linear-attention layers")
        self.ssm_rows_total = r.counter(
            "ssm_rows_total",
            "Rows of mixed dispatches by the path they took through a "
            "state-space layer's state update (step: the row of a lane of "
            "one row, the one-step kernel; scan: the rows of a lane of "
            "more, the chunked scan), a model with state-space layers")
        self.mixed_kv_bytes_ideal_total = r.counter(
            "mixed_kv_bytes_ideal_total",
            "KV bytes a perfect once-per-page schedule would stream for "
            "the same mixed dispatches")
        # Windowed-residency decode (ARKS_RESIDENCY_WINDOW_PAGES): spans
        # attended and cold pages prefetched for contexts larger than the
        # device page pool.
        self.residency_spans_total = r.counter(
            "residency_spans_total",
            "Windowed-residency attention spans attended")
        self.residency_prefetch_pages_total = r.counter(
            "residency_prefetch_pages_total",
            "Cold KV pages restored into staging by residency prefetch")
        # Scheduler phase breakdown (seconds of engine-thread wall time):
        # where a serving cycle actually goes (admit vs chunk vs decode).
        self.scheduler_seconds_total = r.counter(
            "scheduler_seconds_total",
            "Engine-thread wall seconds by scheduler phase")
        # The step clock (obs/stepclock.py): every dispatch's cycle, on in
        # every window.  A cycle runs from one model dispatch call's return
        # to the next one's and has the kind of the dispatch that opened
        # it; the legs sum to it.  leg="wait" is the engine thread blocked
        # fetching results (what decode_resolve_wait_seconds_total{mode}
        # counted until PR 38: the device's leg), "starved" the time the
        # device provably had nothing queued, "overlap" host work beside a
        # busy device.  A stalled cycle is in none of the first three
        # families, only in the last two.
        self.step_leg_seconds_total = r.counter(
            "step_leg_seconds_total",
            "Engine-thread seconds of the step cycles by kind of dispatch "
            "(seq|seq_tail|pipe|spec|spec_pipe|decode) and leg "
            "(wait|starved|overlap); the legs sum to the cycles")
        self.step_call_seconds_total = r.counter(
            "step_call_seconds_total",
            "Seconds inside the model dispatch calls themselves (into the "
            "jitted step and back), by kind of program called; they lie "
            "inside the starved and overlap legs")
        self.step_cycle_seconds = r.histogram(
            "step_cycle_seconds",
            "A dispatch call's return to the next one's, by kind of the "
            "dispatch that opened the cycle (its count: the steps by "
            "shape)", buckets=stepclock_mod.CYCLE_BUCKETS)
        self.step_stalls_total = r.counter(
            "step_stalls_total",
            "Cycles over 8x the trailing median of their kind and over "
            "0.25 s, by where the time stood (dispatch|wait|host|compile)")
        self.step_stall_seconds_total = r.counter(
            "step_stall_seconds_total",
            "Seconds of the stalled cycles (whole cycles: they are left "
            "out of step_leg_seconds_total and step_cycle_seconds), by "
            "where the time stood")
        # A sound run reads 0, not nothing: every ``where`` stands on
        # /metrics from the first scrape.
        for where in stepclock_mod.WHERE:
            self.step_stalls_total.inc(0, where=where)
            self.step_stall_seconds_total.inc(0, where=where)
        # How late the tracer's collector woke from its timed wait, one
        # observation a flush, off the engine thread: a process that was
        # not scheduled (or a C call that kept the GIL) shows here whether
        # or not a step was running.
        self.host_wake_late_seconds = r.histogram(
            "host_wake_late_seconds",
            "How late the trace collector's timed wait returned (one "
            "observation a flush): every Python thread's stall",
            buckets=stepclock_mod.LAG_BUCKETS)
        # The handler threads' lag behind the door: one observation a
        # STREAM, at its end, of its worst frame's time from the engine's
        # put (_deliver / _flush_deferred) to the socket flush; and, for a
        # stream that had a deferred frame, of its worst time from the
        # frame's making to its put (what the deferral costs a client).
        self.stream_deliver_lag_seconds = r.histogram(
            "stream_deliver_lag_seconds",
            "A stream's worst lag from the engine's put of a frame to its "
            "flush on the wire (one observation a stream)",
            buckets=stepclock_mod.LAG_BUCKETS)
        self.stream_defer_lag_seconds = r.histogram(
            "stream_defer_lag_seconds",
            "A stream's worst lag from a deferred frame's making to its "
            "put behind the next dispatch (one observation a stream that "
            "had one)", buckets=stepclock_mod.LAG_BUCKETS)
        # Pipelined decode (ARKS_PIPELINE_DEPTH): in-flight dispatches
        # after each issue.  At depth N steady state this sits at N — a
        # histogram stuck at 1 means the engine keeps leaving the
        # pipelined path (admission churn, aborts, oversized stop sets).
        self.pipeline_depth_occupancy = r.histogram(
            "pipeline_depth_occupancy",
            "In-flight decode dispatches after each pipelined issue",
            buckets=[1, 2, 3, 4, 6, 8])
        # Fault isolation / recovery (engine.faults + _recover_from_fault):
        # the observability DeepServe-style request-preserving recovery
        # needs — who faulted (phase, kind), who survived, who was
        # quarantined, and how long the replay took.
        self.engine_faults_total = r.counter(
            "engine_faults_total",
            "Scheduler-step faults by phase and kind")
        self.requests_recovered_total = r.counter(
            "requests_recovered_total",
            "In-flight requests restored to serving after an engine fault "
            "(token-replay resume or re-queued admission)")
        self.requests_quarantined_total = r.counter(
            "requests_quarantined_total",
            "Culprit requests failed alone after exhausting "
            "ARKS_FAULT_RETRIES")
        self.engine_recovery_seconds = r.histogram(
            "engine_recovery_seconds",
            "Fault-to-resumed-decoding recovery latency",
            buckets=[0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120])
        # 0=serving 1=recovering 2=wedged (faults.STATE_CODES); /readiness
        # reports 503 "recovering"/"wedged" while nonzero.
        self.engine_state = r.gauge(
            "engine_state",
            "Engine serving state (0=serving, 1=recovering, 2=wedged)")
        # Resolved-config info gauge (value always 1, config as labels —
        # the kube-state-metrics "_info" idiom): which KV layout / decode
        # impl / overlap mode a replica ACTUALLY runs, so an operator can
        # tell the perf envelope from /metrics instead of reading logs.
        self.engine_config_info = r.gauge(
            "engine_config_info",
            "Resolved engine configuration (labels; value is always 1)")
        # The other build fact, a number: the form the sampler's window
        # search took for this pod's step shape (sampler.window_blocks).
        self.sampler_window_blocks = r.gauge(
            "sampler_window_blocks",
            "Blocks the sampler cuts a vocabulary row into to find its "
            "top-k window in two stages at this pod's [slots, vocab] step "
            "shape (0: one top_k call over the whole row)")
        # ---- Multi-model pool (engine.model_pool) ----------------------
        self.model_pool_resident_bytes = r.gauge(
            "model_pool_resident_bytes",
            "Device weight bytes per pool model (0 while evicted)")
        self.model_switch_seconds = r.histogram(
            "model_switch_seconds",
            "Model switch latency: first request parked for the model to "
            "the model serving (includes the overlapped weight load)",
            buckets=[0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                     60.0, 120.0])
        self.model_cold_starts_total = r.counter(
            "model_cold_starts_total",
            "Pool model loads from cold (weights not resident)")
        self.requests_parked = r.gauge(
            "requests_parked",
            "Requests parked by reason: guide compile, host-tier KV "
            "restore, a pending model switch, or a preemptive KV swap")
        # ---- Elastic parallelism (live resize / scale-from-zero) -------
        self.engine_resizes_total = r.counter(
            "engine_resizes_total",
            "Live topology resizes by mode (resize|scale_to_zero|rearm) "
            "and outcome (ok|error|rejected)")
        self.resize_seconds = r.histogram(
            "resize_seconds",
            "Live resize latency: drain boundary reached to serving at "
            "the new shape (reshard + rebuild + survivor resume issue)",
            buckets=[0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                     60.0, 120.0])
        self.scale_from_zero_seconds = r.histogram(
            "scale_from_zero_seconds",
            "Scale-from-zero re-arm latency: demand arrival to serving "
            "(weight stream + cache/program rebuild + warm-up issue)",
            buckets=[0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                     60.0, 120.0])
        # ---- SLO tiers + preemptive KV swap (arks_tpu.slo, ARKS_PREEMPT)
        # Per-tier latency families carry the tier NAME as a label so one
        # dashboard row per rung of the ladder can alert on its own
        # target (docs/monitoring.md); without ARKS_SLO_TIERS everything
        # lands in tier="default" and the families mirror the global
        # TTFT/TPOT histograms.
        self.ttft_seconds = r.histogram(
            "ttft_seconds", "TTFT by SLO tier",
            buckets=[0.01, 0.025, 0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 3.2, 6.4, 12.8])
        self.tpot_seconds = r.histogram(
            "tpot_seconds", "TPOT by SLO tier",
            buckets=[0.002, 0.005, 0.01, 0.02, 0.04, 0.08, 0.16, 0.32, 0.64])
        self.requests_preempted_total = r.counter(
            "requests_preempted_total",
            "Running requests preempted for a higher tier, by victim tier")
        self.preempt_swap_seconds = r.histogram(
            "preempt_swap_seconds",
            "Preemptive-swap leg latency (issue -> host copy landed, and "
            "resume issue -> slot live again)",
            buckets=[0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1, 2.5])
        # ---- Tenant-fair admission + overload ladder (engine.fairqueue)
        # The tenant label rides through TenantLabels (first-K tenants
        # keep their id, the rest share "other") so hostile key churn
        # cannot mint unbounded series — tests/test_metrics_conformance
        # enforces the bound.
        self.requests_shed_total = r.counter(
            "requests_shed_total",
            "Requests rejected by the overload ladder, by reason "
            "(queue_full|tenant_cap|deadline), tier, and bounded tenant "
            "label")
        self.admission_queue_depth = r.gauge(
            "admission_queue_depth",
            "Admission-queue depth across all tiers and tenants (compare "
            "against ARKS_QUEUE_MAX for the saturation fraction)")


def _scoped(phase: str):
    """Fault-context decorator for scheduler phases: any exception leaving
    the wrapped method is re-raised as a StepFault tagged with the phase
    and the culprit request ids (blast-radius attribution — the recovery
    loop's quarantine input).  Inner StepFaults (narrower attribution from
    a per-request handler) pass through untouched."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            hb = self._step_hb
            if hb is not None:
                self._step_hb = (phase, hb[1])
            self.trace.evt("", "phase." + phase, "B")
            try:
                return fn(self, *args, **kwargs)
            except StepFault:
                raise
            except Exception as e:
                raise StepFault(phase, faults_mod.classify(e),
                                culprits=self._phase_culprits(phase)) from e
            finally:
                self.trace.evt("", "phase." + phase, "E")
        return wrapper
    return deco


class _OperandPack:
    """The small host operands of one device program, of several 32-bit
    dtypes, laid out in ONE int32 host buffer that the program slices
    apart: a call then hands the device one transfer with its dispatch,
    where an operand each cost a transfer each (0.1 ms of the engine
    thread apiece on a v5e, and a drop of the GIL; PERF.md).  ``fields``
    is ``(name, dtype, shape, fill)``; bool fields are stored as 0 / 1."""

    def __init__(self, fields):
        self._fields = []
        off = 0
        for name, dtype, shape, _fill in fields:
            n = int(np.prod(shape))
            self._fields.append((name, np.dtype(dtype), tuple(shape), off, n))
            off += n
        self.size = off
        self._template = np.zeros((off,), np.int32)
        views = self._views(self._template)
        for name, _dtype, _shape, fill in fields:
            views[name][...] = fill

    def _views(self, buf: np.ndarray) -> dict:
        out = {}
        for name, dtype, shape, off, n in self._fields:
            seg = buf[off: off + n]
            if dtype != np.bool_:
                seg = seg.view(dtype)
            out[name] = seg.reshape(shape)
        return out

    def host(self) -> tuple[np.ndarray, dict]:
        """A fresh buffer at the fields' fill values, and the fields as
        named views into it (what is written through them is the operand)."""
        buf = self._template.copy()
        return buf, self._views(buf)

    def host_from(self, values: dict) -> np.ndarray:
        """The buffer holding ``values`` (a follower's payload)."""
        buf, views = self.host()
        for name, view in views.items():
            view[...] = values[name]
        return buf

    def unpack(self, buf) -> dict:
        """Inside the program: the fields as arrays of their own dtypes
        and shapes (slices and bitcasts of the one operand)."""
        out = {}
        for name, dtype, shape, off, n in self._fields:
            seg = jax.lax.slice(buf, (off,), (off + n,)).reshape(shape)
            if dtype == np.bool_:
                seg = seg != 0
            elif dtype != np.int32:
                seg = jax.lax.bitcast_convert_type(seg, dtype)
            out[name] = seg
        return out


def _named_jit(name: str, fn, **jit_kw):
    """``jax.jit`` under a name of the program's own: the profiler's
    ``XLA Modules`` line and the HLO module read ``jit_<name>`` instead of
    ``jit__unknown`` (a ``functools.partial``) or ``jit__lambda_``.  The
    fresh wrapper also keeps jit's trace cache per engine (it is keyed on
    the underlying callable, see _insert_fn)."""
    def prog(*args, **kwargs):
        return fn(*args, **kwargs)
    prog.__name__ = prog.__qualname__ = name
    return jax.jit(prog, **jit_kw)


# jax.monitoring listeners cannot be taken back, so ONE is registered per
# process and fans out to the engines alive (a serving pod has one).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_watchers: "weakref.WeakSet[InferenceEngine]" = weakref.WeakSet()
_compile_listener_lock = threading.Lock()
_compile_listener_on = False


def _watch_compilations(engine: "InferenceEngine") -> None:
    global _compile_listener_on
    with _compile_listener_lock:
        _compile_watchers.add(engine)
        if _compile_listener_on:
            return
        _compile_listener_on = True

    def on_duration(event: str, secs: float, **_) -> None:
        if event != _COMPILE_EVENT:
            return
        for eng in list(_compile_watchers):
            eng.metrics.xla_compilations_total.inc()
            eng.metrics.xla_compile_seconds_total.inc(secs)
            # Engine-scope instant: an idle gap of the device can be put
            # down to compiling (runs on whichever thread compiles).
            eng.trace.evt("", "compile", "I", round(secs, 4))

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _kv_page(cfg: ModelConfig) -> str:
    """What a slot of the model holds (the label ``kv_page``).  A page: K
    and V per KV head ("kv"), or ONE latent row a token (latent attention),
    which is key and value at once ("latent").  Beside its pages:
    "+window", a second pool, the window layers' pages released behind the
    window; "+state", a fixed recurrent state a slot (recurrent layers:
    linear attention's, or a state-space mixer's)."""
    return ("latent" if cfg.latent else "kv") + (
        "+window" if cfg.windowed else "+state" if cfg.recurrent else "")


@dataclasses.dataclass(frozen=True)
class _Block:
    """What is said of a block whose slot is NOT K and V pages of every
    layer in one pool, where it is refused something
    (InferenceEngine._block_preflight)."""
    what: str         # the sentence that names the block
    slot_keeps: str   # what the slot layout does, that such a slot is not
    moves: str        # what the movers of KV blocks move
    mesh_why: str     # why no mesh
    latent_page: bool = False   # kv_cache_dtype: bf16 only, "auto" is bf16
    # Why the device prefix index is OFF for such a model, whatever
    # --prefix-cache-mb says: no prompt is ever indexed or matched
    # (_register_prompt_pages).  None: the index shares PAGES by id and
    # keeps working.
    no_index: str | None = None


_BLOCKS = {
    # A bf16 latent pool.
    "latent": _Block(
        "latent attention, one latent row a token",
        "holds K and V per head", "K and V blocks",
        "the latent block has no sharding rules", latent_page=True),
    # TWO page pools: the full layers' pages live as long as the sequence,
    # the window layers' are released behind the window
    # (engine/paged.py::WindowPages).  A matched prefix's full pages would
    # still be there and its window pages gone.
    "kv+window": _Block(
        "window and full attention layers over two page pools",
        "keeps every position of every layer",
        "every layer's page of one pool",
        "layers of two head counts have no sharding rules",
        no_index="window pages would be gone"),
    # The GQA layers keep pages, the RECURRENT layers (linear attention's
    # delta rule, a state-space mixer's selective scan: ``{recurrent}`` is
    # the kind's name, ``cfg.recurrent_kind``) a fixed state a slot
    # (transformer.py::LinearState) that the step program rewrites in
    # place: a sequence's recurrent layers have no page, and their state is
    # not carried.  A prefix hit would need the state AT the prefix's end,
    # which nobody kept.
    "kv+state": _Block(
        "{recurrent} layers with a fixed state a slot beside GQA "
        "layers over pages",
        "keeps K and V of every layer",
        "every layer's page and no recurrent state",
        "{recurrent} layers and their state have no sharding rules",
        no_index="recurrent state at its end is not kept"),
    # A bf16 latent pool beside the state: the movers speak K and V blocks
    # and carry neither.
    "latent+state": _Block(
        "linear-attention layers with a fixed state a slot beside "
        "latent-attention layers over one latent row a token",
        "keeps K and V of every layer",
        "K and V blocks, and neither a latent row nor a recurrent state",
        "neither the latent block nor the linear layers' state has "
        "sharding rules", latent_page=True,
        no_index="recurrent state at its end is not kept"),
}


class InferenceEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        engine_cfg: EngineConfig,
        tokenizer: Tokenizer,
        params: tf.Params | None = None,
        mesh=None,
        registry: prom.Registry | None = None,
        draft_params: tf.Params | None = None,
        draft_cfg: ModelConfig | None = None,
        pool=None,
    ) -> None:
        self.tokenizer = tokenizer
        if engine_cfg.pipeline_parallel > 1 and (
                (engine_cfg.tensor_parallel or 1) * engine_cfg.data_parallel
                * engine_cfg.context_parallel > 1):
            raise ValueError(
                "pipeline_parallel cannot combine with tp/dp/cp in one "
                "engine; scale those via replica groups")
        if mesh is None and ((engine_cfg.tensor_parallel or 1)
                             * engine_cfg.data_parallel
                             * engine_cfg.context_parallel
                             * engine_cfg.pipeline_parallel > 1):
            from arks_tpu.parallel.mesh import make_mesh
            mesh = make_mesh(tensor_parallel=engine_cfg.tensor_parallel,
                             data_parallel=engine_cfg.data_parallel,
                             context_parallel=engine_cfg.context_parallel,
                             pipeline_parallel=engine_cfg.pipeline_parallel)
        self.mesh = mesh
        self.metrics = EngineMetrics(registry)
        # Effective parallelism comes from the MESH's axes (an explicitly
        # passed mesh wins over the config — keying off the config here
        # while _build_programs keys off the mesh would let them disagree).
        self._cp = mesh.shape.get("seq", 1) if mesh is not None else 1
        self._pp = mesh.shape.get("stage", 1) if mesh is not None else 1

        # ---- Engine-global (model-independent) machinery ---------------
        # Everything from here to the _init_model_state call survives a
        # model switch untouched: admission queue, abort/fault state,
        # deferred-admit plumbing, pipeline depth, and the model pool
        # itself.  Per-model state (weights, caches, mirrors, compiled
        # programs) is built by _init_model_state and swapped WHOLESALE on
        # switch — a saved context is byte-for-byte the state a
        # single-model engine of that model would hold.
        from collections import deque

        # Admission queue: tier-ordered (lower value first), weighted
        # deficit round-robin across tenants within a tier, FIFO within a
        # (tier, tenant) via a monotonic tiebreak — Request objects are
        # never compared.  Bounded (ARKS_QUEUE_MAX / ARKS_QUEUE_TENANT_MAX)
        # on the external add_request path only; with a single tenant the
        # pick order is exactly the old PriorityQueue order.
        self._queue = fairqueue.FairQueue()
        self._queue_seq = 0
        # Deferred delivery (_deliver / _flush_deferred): None while every
        # output goes straight to its reader; a list, in the order
        # produced, from a resolve that left nothing in flight on the
        # device until just after the next dispatch.
        self._deferred: list | None = None
        self._queued_rids: set[str] = set()
        # Deadline-aware shedding (ARKS_SHED_DEADLINE): a popped request
        # whose queue wait already exceeds factor x its tier's ttft_ms
        # budget is rejected at _preadmit instead of wasting prefill on a
        # stream its client has given up on.  0 = off.  Replay, swap-
        # resume, and disagg-prefilled requests are exempt.
        shed_factor = knobs.get_float("ARKS_SHED_DEADLINE")
        if shed_factor < 0:
            raise ValueError(
                f"ARKS_SHED_DEADLINE={shed_factor}: must be >= 0")
        self._shed_deadline_factor = shed_factor
        # Bounded tenant metric labels (ARKS_TENANT_LABEL_MAX): tenant ids
        # are unbounded user input; label cardinality must not be.
        self._tenant_labels = tenancy.TenantLabels()
        self._aborted: set[str] = set()
        self._abort_lock = threading.Lock()
        # Detached prefill (disaggregated mode) runs on server threads, not
        # the engine thread; serialize device access.
        self._prefill_lock = threading.Lock()
        self._running = False
        self._thread: threading.Thread | None = None
        self._request_seed = engine_cfg.seed
        # ---- Fault isolation (engine.faults) ---------------------------
        # Injector (ARKS_FAULT_INJECT chaos hook), per-request fault
        # counts (the quarantine budget), and the serving/recovering/
        # wedged state machine /readiness reports.
        self._faults = faults_mod.FaultInjector()
        self._fault_retries = knobs.get_int("ARKS_FAULT_RETRIES")
        if self._fault_retries < 0:
            raise ValueError(
                f"ARKS_FAULT_RETRIES={self._fault_retries}: must be >= 0")
        self._fault_counts: dict[str, int] = {}
        self._consec_faults = 0
        # Request ids currently replaying (re-executing behind a
        # _ReplayGate) after a fault; the recovery window closes when the
        # last one re-registers (or dies).  Engine-thread-only.
        self._replaying: set[str] = set()
        self._state = "serving"
        self.metrics.engine_state.set(faults_mod.STATE_SERVING)
        self._recover_t0 = 0.0
        # Watchdog heartbeat: (phase, t0) of the in-flight scheduler step,
        # None while idle.  Written by the engine thread, read by the
        # watchdog thread (a torn read degrades to one missed poll).
        self._step_hb: tuple[str, float] | None = None
        self._watchdog: faults_mod.Watchdog | None = None
        # Deferred admissions: issued batches whose first tokens haven't
        # been fetched yet (FIFO).  Resolving lazily (is_ready polling in
        # step) keeps the engine thread issuing decode dispatches instead
        # of blocking on every admit program's round-trip.
        self._pending_admits: "deque" = deque()
        # Request count across the deque, maintained by the engine thread
        # at every mutation: num_running reads it cross-thread (iterating
        # the deque there would race popleft/extend).
        self._pending_n = 0
        self._defer_admits = True
        # Decode/admission overlap: issue the decode dispatch async and do
        # admission host work while the device computes.  Pays off where
        # device compute and host logistics are truly parallel (TPU);
        # on CPU the "device" shares the host's cores, so the reorder only
        # delays new slots' first decode — sequential there.
        # ARKS_OVERLAP_DECODE=0/1 overrides.
        _ov = knobs.get_str("ARKS_OVERLAP_DECODE")
        self._overlap = (_ov == "1" or
                         (_ov != "0" and jax.default_backend() == "tpu"))
        # Multi-host: a DispatchLeader when this engine drives follower
        # processes (arks_tpu.engine.multihost); None single-host.
        self.dispatcher = None

        # ---- Pipelined decode depth (ARKS_PIPELINE_DEPTH) --------------
        # Parsed once per process (model-independent); the per-model pipe
        # state itself lives in _init_model_state.
        pipe_depth = knobs.get_int("ARKS_PIPELINE_DEPTH")
        if pipe_depth < 0:
            raise ValueError(
                f"ARKS_PIPELINE_DEPTH={pipe_depth}: must be >= 0")
        self._pipe_depth_cfg = pipe_depth

        # ---- SLO tiers + preemptive KV swap (ARKS_PREEMPT) -------------
        # Tier ladder (metric labels + admission semantics; arks_tpu.slo)
        # and the preemption knobs, all engine-global: a queued request
        # whose (aged) priority strictly outranks the lowest running tier
        # may seize that victim's slot by swapping its full decode state
        # to host RAM.  Default OFF — priority stays pure queue ordering.
        self._slo = slo_mod.from_env()
        # Per-tier SLO burn tracker: the engine thread appends one
        # (time, violated) sample per first token for tiers that declare
        # a ttft_ms target; slo_burn() folds the rolling window into
        # violation_fraction / ARKS_SLO_ERROR_BUDGET for /readiness and
        # the signals-mode autoscaler (control.autoscaler).
        self._slo_burn_window_s = knobs.get_float("ARKS_SLO_BURN_WINDOW_S")
        self._slo_error_budget = max(
            knobs.get_float("ARKS_SLO_ERROR_BUDGET"), 1e-6)
        self._slo_events: dict[str, list] = {}
        # ---- End-to-end tracing + profiler windows (arks_tpu.obs) ------
        # The tracer records span events from the scheduler seams into
        # per-thread rings (ARKS_TRACE=0 disables; the step loop may only
        # call trace.evt — tests/test_hotpath_guard.py enforces it) and
        # doubles as the flight recorder the watchdog/fault dumps attach.
        self.trace = trace_mod.Tracer()
        # The step loop's own clock, on in every window (obs/stepclock.py):
        # fed at the dispatch and wait sites below, by this thread alone.
        self.step_clock = stepclock_mod.StepClock(
            self.metrics, self.trace,
            state=lambda: (len(self._slots), self._queue.qsize()))
        # One control: a profiler window (engine.profiler.start/stop, the
        # /v1/profiler endpoints) also switches the step-section spans on
        # and returns them; with no window open a section site is one
        # attribute test (self.profiler.sections).  It reads the step clock
        # at its two ends and returns the cycles that closed in between.
        self.profiler = prof_mod.ProfilerWindows(tracer=self.trace,
                                                 clock=self.step_clock)
        self.trace.wake_hist = self.metrics.host_wake_late_seconds
        self._admit_popped = 0   # requests _admit() took off the queue
        _watch_compilations(self)
        self._pipe_seq = 0   # pipelined issue->resolve span pairing
        self._preempt_on = knobs.get_bool("ARKS_PREEMPT")
        preempt_max = knobs.get_int("ARKS_PREEMPT_MAX_INFLIGHT")
        if preempt_max < 1:
            raise ValueError(
                f"ARKS_PREEMPT_MAX_INFLIGHT={preempt_max}: must be >= 1")
        self._preempt_max = preempt_max
        preempt_cooldown = knobs.get_float("ARKS_PREEMPT_COOLDOWN_S")
        if preempt_cooldown < 0:
            raise ValueError(
                f"ARKS_PREEMPT_COOLDOWN_S={preempt_cooldown}: must be >= 0")
        self._preempt_cooldown_s = preempt_cooldown
        # Anti-thrash ledger: rid -> last preempt time; a victim inside
        # the cooldown window is skipped so two tiers can't ping-pong one
        # slot (swap-storm livelock).
        self._preempt_last: dict[str, float] = {}
        # Preempt-resumed rids mid-flight through replay-mode resume (the
        # re-queue path): _register_slot suppresses their TTFT — the
        # client saw the real first token long ago.
        self._resuming: set[str] = set()
        # ---- Priority-queue aging (ARKS_QUEUE_AGING_S) -----------------
        # A queued request's EFFECTIVE priority decays by one tier per
        # aging window, so sustained high-tier load cannot starve the
        # batch tier forever.  0 = off.
        queue_aging = knobs.get_float("ARKS_QUEUE_AGING_S")
        if queue_aging < 0:
            raise ValueError(
                f"ARKS_QUEUE_AGING_S={queue_aging}: must be >= 0")
        self._queue_aging_s = queue_aging
        self._queue_age_last = 0.0

        # ---- Multi-model pool (arks_tpu.engine.model_pool) -------------
        # Requests carry a model id; ones targeting a non-active pool
        # model park in _awaiting_model (mirroring guide_wait /
        # awaiting_restore — same abort/drain/recovery discipline) while
        # the pool streams the weights in the background, then the
        # scheduler switches contexts at a drained boundary
        # ("model_switch" fault phase).
        self.pool = pool
        self._awaiting_model: list[tuple[Request, str, float]] = []
        self._model_loads: dict[str, object] = {}   # name -> LoadTicket
        # Cold-start prefetch hints: add_request drops the model name here
        # so the load kicks the moment demand ARRIVES — a queued request
        # behind busy slots must not delay the weight stream until it
        # parks (GIL-atomic set ops; server threads write, engine reads).
        self._model_prefetch: set[str] = set()
        self._model_ctxs: dict[str, dict] = {}      # saved per-model state
        self._switch_target: str | None = None
        self._switch_policy = knobs.get_str("ARKS_MODEL_SWITCH_POLICY")
        switch_quantum = knobs.get_float("ARKS_MODEL_SWITCH_QUANTUM_S")
        if switch_quantum <= 0:
            raise ValueError(
                f"ARKS_MODEL_SWITCH_QUANTUM_S={switch_quantum}: must be > 0")
        self._switch_quantum = switch_quantum
        self._slice_t0 = time.monotonic()   # active model's timeslice epoch
        self._switch_t0: dict[str, float] = {}   # first-park time per model
        # Dispatch accounting while a model load is in flight: proves the
        # resident model kept full pipeline depth during the overlap
        # (tests/test_multi_model.py asserts on this).
        self._switch_stats = {"dispatches": 0, "max_depth": 0}
        self.last_switch_stats: dict | None = None

        # ---- Elastic parallelism (live resize / scale-from-zero) -------
        # A posted _ResizeRequest drives the drain -> reshard -> resume
        # state machine from the step loop; scale-to-zero disarms a fully
        # idle engine (weights + device KV dropped, host/disk prefix
        # tiers and swapped victims kept) until demand re-arms it.  All
        # engine-global: a resize outlives any one model context.
        self._resize_req: _ResizeRequest | None = None
        self._resize_active = False
        self._armed = True
        self._zero_t0 = 0.0
        self._idle_since: float | None = None
        self._rearm_loader = None   # optional (cfg, mesh) -> params
        idle_zero = knobs.get_float("ARKS_ELASTIC_IDLE_ZERO_S")
        if idle_zero < 0:
            raise ValueError(
                f"ARKS_ELASTIC_IDLE_ZERO_S={idle_zero}: must be >= 0")
        self._idle_zero_s = idle_zero
        self._elastic_warmup = knobs.get_bool("ARKS_ELASTIC_WARMUP")
        self._warmup_seq = 0
        self._rearm_fail_t = -1e9   # last failed re-arm (retry backoff)
        self._rearm_wake = threading.Event()   # interrupts the backoff
        self.last_resize_stats: dict | None = None
        self.last_rearm_stats: dict | None = None

        pre = set(vars(self))
        self._init_model_state(cfg, engine_cfg, params=params,
                               draft_params=draft_params, draft_cfg=draft_cfg)
        # Every per-model attribute name (weights, caches, mirrors, AND the
        # jit program objects _build_programs hangs on self): _switch_to
        # swaps exactly these, wholesale, between saved model contexts.
        self._model_attr_names = tuple(sorted(set(vars(self)) - pre))
        self._primary_model = cfg.name
        self._primary_ecfg = engine_cfg
        # Prefix-digest sketch exporter (cache-aware routing): summarizes
        # tier-0/tier-1 digest membership for GET /v1/cache/sketch.  One
        # per engine PROCESS, not per model — its epoch tracks this
        # engine's boot/reset lifecycle, which is what routers key sketch
        # staleness on.  Deliberately outside the _model_attr_names diff:
        # a model switch must not resurrect a pre-switch epoch.
        self._sketch = None
        if self._paged and self._chunk:
            from arks_tpu.prefix_sketch import SketchExporter
            self._sketch = SketchExporter(self._page_size())
        if self.pool is not None:
            from types import SimpleNamespace as _NS
            self.pool.adopt(cfg.name, cfg, self.params, pinned=True)
            self.pool.acquire(cfg.name)   # active-model ref, held until switch
            if self._draft_cfg is not None:
                # The draft rides the shared pool (pinned co-resident with
                # the flagship) instead of a second free-floating
                # load_params tree.
                self.pool.adopt(self._draft_cfg.name, self._draft_cfg,
                                self._draft_params, pinned=True)
            if self.pool.metrics is None:
                self.pool.metrics = _NS(
                    resident_bytes=self.metrics.model_pool_resident_bytes,
                    cold_starts=self.metrics.model_cold_starts_total)
                self.pool._publish_metrics()
            # Eviction must drop the saved context too — it holds a params
            # reference, so the HBM would not actually free.
            self.pool.on_evict = lambda n: self._model_ctxs.pop(n, None)

    def _init_model_state(self, cfg: ModelConfig, engine_cfg: EngineConfig,
                          params: tf.Params | None = None,
                          draft_params: tf.Params | None = None,
                          draft_cfg: ModelConfig | None = None,
                          keep_tiers: dict | None = None) -> None:
        """Build ALL per-model engine state: weights, KV cache/allocator,
        sampling state, guide registry, host mirrors, prefix tiers, draft
        state, mixed/pipe scheduling state, and the compiled programs.

        Called from __init__ for the primary model and from _switch_to for
        each cold activation of a pool model.  Every attribute assigned
        here (captured by the __init__ vars() diff) is saved/restored
        wholesale on model switch — which is only legal because switches
        happen at FULLY DRAINED boundaries, where the mutable scheduling
        members are at their empty state.

        ``keep_tiers`` (elastic resize / scale-from-zero re-arm, same
        model, possibly a new mesh): reuse the caller-snapshotted host/
        disk/swap tiers and their worker threads instead of building
        fresh ones.  Tier blocks are full logical host arrays keyed by a
        layout epoch that excludes the mesh shape, so warm prefixes and
        swapped-out victims survive the new topology verbatim — and the
        already-running writer/fetch threads keep their queues (a fresh
        spawn would orphan both)."""
        mesh = self.mesh
        tokenizer = self.tokenizer
        self.cfg = cfg
        self.ecfg = engine_cfg
        self._block_preflight(cfg, engine_cfg, draft_cfg)
        # The step returns four counts beside its token ids (held pairs,
        # a share's overflow tiles needed and looped, valid rows; a fifth
        # ahead of the rows where the routers score identity experts):
        # _count_held.
        self._held_stat = bool((cfg.latent or cfg.windowed or cfg.recurrent)
                               and cfg.num_experts)
        # Per-model KV dtype preference: a checkpoint that ships
        # kv_cache_dtype in its ModelConfig wins over the engine's "auto"
        # (an explicit EngineConfig setting still overrides the model).
        if (engine_cfg.kv_cache_dtype == "auto"
                and getattr(cfg, "kv_cache_dtype", "auto") != "auto"):
            engine_cfg.kv_cache_dtype = cfg.kv_cache_dtype
            log.info("kv_cache_dtype=%s from the model config",
                     cfg.kv_cache_dtype)
        # Under pp, chunked prefill (and with it the prefix cache) is off:
        # its dynamic layer indexing would gather the stage-sharded cache.
        # Derived locally — the caller's EngineConfig is not mutated.
        self._chunk_cfg = engine_cfg.prefill_chunk if self._pp == 1 else None
        if self._pp > 1 and engine_cfg.prefill_chunk:
            log.info("pipeline parallelism: chunked prefill and the prefix "
                     "cache are disabled for this engine")
        engine_cfg.align_cache_len()
        self._buckets = engine_cfg.resolve_buckets()
        if self._pp > 1 and self._buckets[-1] < engine_cfg.max_cache_len:
            # No chunked path under pp: one-shot buckets must cover the
            # window (mirrors resolve_buckets' no-chunk behavior).
            self._buckets.append(engine_cfg.max_cache_len)
        if self._cp > 1:
            # Ring prefill shards T over 'seq': buckets must divide evenly.
            kept = [b for b in self._buckets if b % self._cp == 0]
            if not kept:
                raise ValueError(
                    f"no prefill bucket in {self._buckets} is divisible by "
                    f"the mesh seq axis ({self._cp})")
            # The whole point of cp is prompts beyond one chip's prefill
            # budget: extend the one-shot buckets to the full cache window
            # (doubling) so long prompts ride the ring instead of falling
            # into the unsharded chunked path.  Chunked prefill still serves
            # prefix-cache tails; whole-prompt chunking is pointless when
            # the ring makes one-shot prefill cp-times faster.
            while kept[-1] < engine_cfg.max_cache_len:
                nxt = min(kept[-1] * 2, engine_cfg.max_cache_len)
                if nxt % self._cp:
                    break
                kept.append(nxt)
            self._buckets = kept
        dtype = jnp.dtype(engine_cfg.dtype or cfg.dtype)

        from arks_tpu.models.quant import weight_bits
        wbits = weight_bits(engine_cfg.weight_dtype)
        tp_shards = mesh.shape.get(tf.AXIS_MODEL, 1) if mesh is not None else 1
        if params is None:
            if wbits:
                # Direct quantized init: a full-width init of an HBM-limited
                # model would OOM before quantization could shrink it.
                from arks_tpu.models import quant
                params = quant.init_params_quantized(
                    cfg, jax.random.PRNGKey(engine_cfg.seed), dtype,
                    bits=wbits, shards=tp_shards)
            else:
                params = tf.init_params(cfg, jax.random.PRNGKey(engine_cfg.seed), dtype)
        elif wbits:
            from arks_tpu.models import quant
            if not quant.is_quantized(params["layers"].get("wo")):
                params = quant.quantize_params(params, bits=wbits,
                                               shards=tp_shards)
        if mesh is not None:
            if self._pp > 1:
                from arks_tpu.parallel.pipeline import shard_params_pp
                params = shard_params_pp(params, mesh)
            else:
                params = tf.shard_params(params, cfg, mesh)
        self.params = params

        # KV cache built below, once the chunk size (= page size for the
        # paged layout) is known.
        self._sampling = sampler_mod.init_sampling_state(
            engine_cfg.num_slots, engine_cfg.seed,
            vocab_size=cfg.vocab_size)

        # Guided decoding: compiler owns the host tables; fixed-budget
        # device copies are allocated up front so compiling a guide later
        # never changes program shapes (no mid-serving retrace).  The
        # engine thread re-uploads CONTENTS when the version bumps.
        from types import SimpleNamespace

        from arks_tpu.engine.guides import GuideCompiler
        eos_all = tuple(dict.fromkeys(
            list(cfg.eos_token_ids) + list(tokenizer.eos_token_ids)))
        self.guides = GuideCompiler(
            tokenizer, cfg.vocab_size, eos_all,
            metrics=SimpleNamespace(
                compile_seconds=self.metrics.guide_compile_seconds,
                hits=self.metrics.guide_cache_hits_total,
                misses=self.metrics.guide_cache_misses_total,
                evictions=self.metrics.guide_cache_evictions_total,
                guides_in_use=self.metrics.guide_registry_guides_in_use,
                rows_in_use=self.metrics.guide_registry_rows_in_use))
        self._guide_dev = (jnp.asarray(self.guides.class_ids),
                           jnp.asarray(self.guides.trans))
        self._guide_ver = self.guides.version
        # Requests whose guide is still compiling on the worker pool, each
        # with its CompileTicket: the scheduler re-checks them every step
        # (guide_wait phase) and re-queues/fails them — the engine thread
        # itself NEVER waits on a compile.  Engine-thread-only.
        self._awaiting_guide: list = []
        # request_id -> guide key for requests holding a registry pin
        # (acquired at admission, released at every end-of-life path);
        # pinned guides are never evicted.  Engine-thread-only.
        self._guide_pins: dict[str, tuple[str, str]] = {}

        # Host-authoritative mirrors.
        self._lengths = np.zeros((engine_cfg.num_slots,), np.int32)
        self._last_token = np.zeros((engine_cfg.num_slots,), np.int32)
        self._slots: dict[int, _Slot] = {}
        self._free: list[int] = list(range(engine_cfg.num_slots))
        # Chunked prefills in progress: slot -> _ChunkState (insertion order
        # = FIFO processing).  These slots are reserved but not yet decoding.
        self._prefilling: dict[int, _ChunkState] = {}

        # Effective chunk size: the largest divisor of the cache length not
        # exceeding the configured chunk.  Chunk starts are multiples of the
        # chunk size, so divisibility guarantees every chunk's write window
        # [start, start+C) stays inside the cache (dynamic_update_slice
        # would otherwise clamp the start and corrupt earlier rows).
        self._chunk = 0
        if self._chunk_cfg:
            c = min(self._chunk_cfg, engine_cfg.max_cache_len)
            while engine_cfg.max_cache_len % c:
                c -= 1
            self._chunk = c

        # ---- KV layout: paged pool or slot-contiguous cache ------------
        self._paged = self._resolve_kv_layout()
        self._residency_window = 0
        self._residency = None
        self._alloc = None
        self._tables = None
        self._slot_pages: dict[int, list[int]] = {}
        # The window layers' pages (engine/paged.py::WindowPages): a model
        # with window and full attention layers only.
        self._win = None
        # Bytes of state a slot over the linear layers (LinearState), set by
        # the slots and never by a context; 0: the model has none.  A taken
        # slot is the whole reservation and the whole count.
        self._lin_slot_bytes = 0
        # The pages of the full pool that admission may promise (0: the
        # pool holds every slot's whole context, and a free slot is
        # promise enough), what each slot was promised, and the request
        # that waits for pages at the head of the queue (_pool_fits).
        self._pool_budget = 0
        self._pool_reserved: dict[int, int] = {}
        self._pool_waiting = None
        if engine_cfg.kv_pool_pages and not (self._paged and cfg.windowed):
            raise ValueError(
                f"kv_pool_pages={engine_cfg.kv_pool_pages}: an admission "
                "that reserves pages exists for a model with window and "
                "full attention layers on the paged layout only (every "
                "other admission path counts slots, and its pool holds "
                "every slot's whole context)")
        if self._paged:
            from arks_tpu.engine.paged import PageAllocator
            page = self._page_size()
            max_pages = engine_cfg.max_cache_len // page
            self._max_pages = max_pages
            # Worst case (every slot full) always fits; the prefix budget
            # adds retention headroom on top.
            kv_bits = (engine_cfg.kv_bits if engine_cfg.kv_quantized
                       else jnp.dtype(self._cache_dtype(dtype)).itemsize * 8)
            d_store = tf.cache_head_dim(cfg, self._pad_head())
            # K and V, each at its stored width; a latent page holds its
            # one row once.
            # (A page of the full-attention pool: every layer, or the
            # full layers of a model that also has window layers; a
            # shortcut layer's two attention sublayers each.)
            page_bytes = (cfg.num_attn_sublayers * cfg.num_kv_heads * page
                          * (d_store + (0 if cfg.latent else
                                        tf.cache_value_dim(
                                            cfg, self._pad_head())))
                          * kv_bits // 8)
            if engine_cfg.kv_quantized:
                page_bytes += (cfg.num_full_layers * cfg.num_kv_heads
                               * page * 4 * 2)
            extra = 0
            # Retention pages only help when prefix sharing can actually
            # register/match them, which rides the chunk path — under pp
            # (chunking off) they would be permanently dead HBM; nor does a
            # model with window layers register any
            # (_register_prompt_pages).
            if (engine_cfg.prefix_cache_mb and self._chunk
                    and not cfg.windowed and not cfg.recurrent):
                extra = max(engine_cfg.prefix_cache_mb * 2**20 // page_bytes, 0)
                # The byte budget is tuned for 7B-class pools; cap by
                # proportion so tiny test models don't allocate huge pools.
                extra = min(extra, engine_cfg.num_slots * max_pages * 4)
            # Windowed residency (ARKS_RESIDENCY_WINDOW_PAGES): bound the
            # RESIDENT per-slot page budget below the logical table width
            # — slots whose context outgrows the window engage the
            # span-streaming decode path (engine/residency.py) instead of
            # holding their whole KV on device.  The logical tables keep
            # the full max_cache_len width; only the pool shrinks.
            window = knobs.get_int("ARKS_RESIDENCY_WINDOW_PAGES")
            if window < 0:
                raise ValueError(
                    f"ARKS_RESIDENCY_WINDOW_PAGES={window}: must be >= 0")
            per_slot = max_pages
            if window and window < max_pages:
                if window < 4:
                    raise ValueError(
                        f"ARKS_RESIDENCY_WINDOW_PAGES={window}: the window "
                        "must cover 2 hot-tail pages + 2 staging halves "
                        "(>= 4)")
                per_slot = window
                self._residency_window = window
            num_pages = engine_cfg.num_slots * per_slot + extra
            if engine_cfg.kv_pool_pages:
                # The full pool sized to what the callers can occupy, not
                # to num_slots x max_cache_len: admission then reserves
                # pages.  One sequence of the whole context must fit, or
                # its request could never be admitted.
                if not max_pages <= engine_cfg.kv_pool_pages <= num_pages:
                    raise ValueError(
                        f"kv_pool_pages={engine_cfg.kv_pool_pages}: must "
                        f"hold one whole context ({max_pages} pages of "
                        f"{page} tokens) and at most every slot's "
                        f"({num_pages})")
                num_pages = self._pool_budget = engine_cfg.kv_pool_pages
            self._page_bytes = page_bytes
            if cfg.windowed:
                from arks_tpu.engine.paged import (WindowPages,
                                                   window_pages_per_slot)
                # Rows one dispatch burst writes a slot: a prefill chunk's
                # budget, or a decode row a dispatch in flight.
                rows = max(self._mixed_budget_cfg(),
                           self._pipe_depth_cfg + 1)
                self._win = WindowPages(
                    engine_cfg.num_slots, max_pages, page,
                    cfg.sliding_window, window_pages_per_slot(
                        cfg.sliding_window, rows, page, max_pages))
            self._cache = self._init_paged_cache(num_pages, dtype)
            if mesh is not None:
                self._cache = self._shard_paged(self._cache)
            if cfg.recurrent:
                self._lin_slot_bytes = self._cache.lin.slot_bytes
            self._alloc = PageAllocator(num_pages, page)
            self._tables = np.zeros((engine_cfg.num_slots, max_pages),
                                    np.int32)
            # Free slots park at the coverage sentinel: their garbage
            # dispatch rows are dropped by the kernels instead of landing
            # in (possibly shared) pages.
            self._lengths[:] = self._park_sentinel()
            log.info("paged KV: %d pages x %d tokens (%d retention extra), "
                     "%d bytes a token%s", num_pages, page, extra,
                     self._cache.token_bytes,
                     " (one latent row, stored once)" if cfg.latent else "")
            if self._win is not None:
                for kind, pool in (("full", self._cache),
                                   ("window", self._cache.win)):
                    self.metrics.kv_pool_page_bytes.set(
                        pool.token_bytes * page, kind=kind)
                log.info("window layers: %d pages x %d tokens of their own "
                         "(%d a slot: window %d + the rows of a step), %d "
                         "bytes a token over %d layers; the full pool "
                         "above holds %d layers", self._win.alloc.num_pages,
                         page, self._win.per_slot, cfg.sliding_window,
                         self._cache.win.token_bytes, cfg.num_window_layers,
                         cfg.num_full_layers)
            if self._lin_slot_bytes:
                log.info("recurrent layers: %d bytes of %s state a slot over "
                         "%d layers (%d slots, %.2f GB, whatever the "
                         "context); the pool above holds the %d other "
                         "layers",
                         self._lin_slot_bytes, self._cache.lin.s.dtype,
                         cfg.num_linear_layers, engine_cfg.num_slots,
                         engine_cfg.num_slots * self._lin_slot_bytes / 1e9,
                         cfg.num_full_layers)
        else:
            self._max_pages = 0
            self._page_bytes = 0
            self._cache = tf.init_cache(cfg, engine_cfg.num_slots,
                                        engine_cfg.max_cache_len,
                                        self._cache_dtype(dtype),
                                        quantized=engine_cfg.kv_quantized,
                                        pad_head=self._pad_head())
            if mesh is not None:
                self._cache = self._shard_cache(self._cache)

        # Host-resident prefix KV cache (slot layout only — the paged pool
        # shares pages ON DEVICE through the allocator's index instead).
        self._prefix = None
        if engine_cfg.prefix_cache_mb and self._chunk and not self._paged:
            from arks_tpu.engine.prefix_cache import PrefixKVCache
            self._prefix = PrefixKVCache(
                self._chunk, engine_cfg.prefix_cache_mb * 2**20)

        # ---- Host-RAM spill tier behind the paged prefix index ---------
        # Tier 0 = the allocator's on-device page index (zero-copy hits);
        # tier 1 = HostPrefixTier, fed by ASYNC spills of pages the index
        # evicts under pool pressure (the pool used to DESTROY them) and
        # consulted at admission: a tier-1 hit restores the blocks with
        # one H2D scatter dispatch instead of re-prefilling them, while
        # the request parks in awaiting_restore.  Host RAM is 10-100x
        # HBM, so the shared-prefix working set a production fleet sees
        # (system prompts, few-shot preambles, multi-turn histories)
        # survives far beyond the pool's retention surplus.
        from collections import deque as _deque
        self._host = None
        self._spill_victims: list = []      # (digest, page) since last flush
        self._spills: "_deque" = _deque()   # in-flight D2H spill records
        self._awaiting_restore: list[_RestoreState] = []
        host_mb = knobs.get_int("ARKS_PREFIX_HOST_MB")
        if host_mb < 0:
            raise ValueError(
                f"ARKS_PREFIX_HOST_MB={host_mb}: must be >= 0")
        self._host_mb = host_mb if (self._paged and self._chunk and host_mb
                                    and _kv_page(cfg) not in _BLOCKS) else 0
        if keep_tiers is not None:
            # Elastic rebuild: adopt the surviving tier-1 store (blocks
            # are full logical host arrays — mesh-shape-independent).
            self._host = keep_tiers["host"]
            if self._host is not None:
                self._alloc.on_evict = self._note_evicted
        elif self._host_mb:
            from arks_tpu.engine.prefix_cache import HostPrefixTier
            self._host = HostPrefixTier(self._page_size(),
                                        self._host_mb * 2**20)
            self._alloc.on_evict = self._note_evicted
        # Fixed spill/restore group sizes: each is ONE compiled program
        # shape (short groups pad), keeping the variant budget flat.
        self._spill_group = min(8, max(self._max_pages, 1))
        self._restore_group = min(8, max(self._max_pages, 1))

        # ---- Tier-2 disk block store + fleet peer fetch ----------------
        # Tier 2 = DiskPrefixTier: a byte-budgeted local-disk store fed
        # ASYNCHRONOUSLY from tier-1 LRU evictions (host.on_evict queues
        # the victim block; a writer thread does the file IO — the step
        # loop only drains the queue).  Same chain-digest keys, same
        # pool-native blocks, so warm prefixes survive an engine restart.
        # Peer fetch makes the tiers fleet-wide: an admission miss whose
        # prefix a peer replica advertises (router X-Arks-Peer-Hint, or
        # the ARKS_PEER_ADDRS probe list) parks in _awaiting_fetch while
        # a worker pulls the raw AKV1 blocks into the host tier — the
        # unpark then rides the ordinary tier-1 restore path.
        self._disk = None
        self._disk_spill_pending: "_deque" = _deque()   # (digest, block)
        self._awaiting_fetch: list[_FetchState] = []
        self._disk_write_queue = None
        self._disk_writer = None
        self._fetch_queue = None
        self._kv_epoch = self._kv_layout_epoch()
        self._disk_stats_lock = threading.Lock()
        self._disk_evict_seen = 0
        self._disk_corrupt_seen = 0
        disk_mb = knobs.get_int("ARKS_PREFIX_DISK_MB")
        if disk_mb < 0:
            raise ValueError(
                f"ARKS_PREFIX_DISK_MB={disk_mb}: must be >= 0")
        self._peer_timeout = knobs.get_float("ARKS_PEER_FETCH_TIMEOUT_S")
        if self._peer_timeout <= 0:
            raise ValueError(
                f"ARKS_PEER_FETCH_TIMEOUT_S={self._peer_timeout}: "
                "must be > 0")
        self._peer_addrs = [a.strip() for a in knobs.get_list(
            "ARKS_PEER_ADDRS") if a.strip()]
        self._peer_fetch = (knobs.get_bool("ARKS_PEER_FETCH")
                            and self._host is not None
                            and self.dispatcher is None)
        if keep_tiers is not None:
            # Elastic rebuild: the tier-2 store, its writer/fetch worker
            # threads, and their queues all survive as-is — the threads
            # captured their queues at spawn, so fresh ones here would
            # leave the old workers consuming orphaned queues forever.
            self._disk = keep_tiers["disk"]
            self._disk_write_queue = keep_tiers["disk_write_queue"]
            self._disk_writer = keep_tiers["disk_writer"]
            self._fetch_queue = keep_tiers["fetch_queue"]
            self._disk_stats_lock = keep_tiers["disk_stats_lock"]
            self._disk_evict_seen = keep_tiers["disk_evict_seen"]
            self._disk_corrupt_seen = keep_tiers["disk_corrupt_seen"]
            if self._disk is not None and self._host is not None:
                self._host.on_evict = self._note_host_evicted
        elif disk_mb and self._host is not None and self.dispatcher is None:
            import tempfile

            from arks_tpu.engine.prefix_cache import DiskPrefixTier
            ddir = knobs.get_str("ARKS_PREFIX_DISK_DIR") or os.path.join(
                tempfile.gettempdir(), "arks-prefix-disk")
            self._disk = DiskPrefixTier(
                self._page_size(), disk_mb * 2**20, ddir,
                self._kv_layout_epoch())
            self._host.on_evict = self._note_host_evicted
            # Bounded: a spill storm drops blocks (best-effort warmth)
            # instead of growing an unbounded host-RAM backlog.
            self._disk_write_queue = queue.Queue(maxsize=256)
            self._disk_writer = threading.Thread(
                target=self._disk_write_loop, name="disk-spill",
                daemon=True)
            self._disk_writer.start()
        if keep_tiers is None and (self._disk is not None
                                   or self._peer_fetch):
            self._fetch_queue = queue.Queue()
            threading.Thread(target=self._fetch_loop,
                             name="prefix-fetch", daemon=True).start()

        # ---- Preemptive KV swap state (ARKS_PREEMPT) -------------------
        # Victim decode state (KV page blocks + sampler row) parks in a
        # keyed SwapStore sharing the host tier's byte budget; swap-mode
        # preemption therefore requires the host tier.  Engines without
        # it (slot layout, pp>1, host tier off) and spec engines (the
        # draft cache mirror has no cheap snapshot) preempt in REPLAY
        # mode instead: the victim re-queues behind a _ReplayGate and
        # deterministically re-executes (docs/application-usage.md has
        # the fallback matrix).
        self._swap = None
        if keep_tiers is not None:
            # Elastic rebuild: swapped-out victims' KV blocks are full
            # logical host pages — they resume byte-identically into the
            # new topology's pool via the ordinary restore path.
            self._swap = keep_tiers["swap"]
            self._swapped = keep_tiers["swapped"]
        elif self._host is not None:
            from arks_tpu.engine.prefix_cache import SwapStore
            self._swap = SwapStore(self._host)
        if keep_tiers is None:
            self._swapped: dict[str, _SwapRecord] = {}  # rid -> victim
        self._swap_pending: list[_SwapState] = []   # in-flight D2H swaps

        # Speculative decoding: draft model params + its own slot cache.
        self._draft_cfg = None
        self._draft_params = None
        self._draft_cache = None
        if engine_cfg.draft_model:
            if self._pp > 1:
                raise ValueError(
                    "speculative decoding is incompatible with pipeline_parallel")
            if tf.batch_axis_for(mesh) is not None:
                raise ValueError(
                    "speculative decoding requires data_parallel == 1 "
                    "(and no slice axis)")
            if engine_cfg.draft_len < 2:
                raise ValueError("draft_len must be >= 2")
            from arks_tpu.models import get_config
            dcfg = draft_cfg or get_config(engine_cfg.draft_model)
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {dcfg.vocab_size} != target {cfg.vocab_size}"
                    " — the draft must share the target's tokenizer")
            self._draft_cfg = dcfg
            dparams = draft_params
            if dparams is None:
                dparams = tf.init_params(
                    dcfg, jax.random.PRNGKey(engine_cfg.seed + 1), dtype)
            if mesh is not None:
                dparams = tf.shard_params(dparams, dcfg, mesh)
            self._draft_params = dparams
            self._draft_cache = tf.init_cache(
                dcfg, engine_cfg.num_slots, engine_cfg.max_cache_len,
                self._cache_dtype(dtype), quantized=engine_cfg.kv_quantized,
                pad_head=self._pad_head())
            if mesh is not None:
                self._draft_cache = tf.shard_cache(self._draft_cache, dcfg, mesh)

        self._spec_proposed = 0
        self._spec_accepted = 0

        # ---- Mixed prefill+decode step (ARKS_MIXED_STEP) ---------------
        # ONE token-budget dispatch per scheduler iteration: every decoding
        # slot's next token plus up to ARKS_MIXED_CHUNK_TOKENS prefill-chunk
        # tokens spread round-robin across ALL prefilling sequences, sampled
        # in the same program.  Replaces the admit_batch x chunk_step x
        # decode_loop program family for paged engines — default ON where
        # supported; non-paged and no-chunk (pp) engines stay on the legacy
        # paths.  Speculative engines RIDE the mixed step (verify lanes are
        # q_len=draft_len rows of the same dispatch) and nothing else.
        _mx = knobs.get_str("ARKS_MIXED_STEP")
        mixed_capable = self._paged and bool(self._chunk)
        self._mixed = mixed_capable and _mx != "0"
        if _mx == "1" and not mixed_capable:
            log.warning(
                "ARKS_MIXED_STEP=1 requested but unsupported here "
                "(paged=%s chunk=%s); staying on the legacy scheduler",
                self._paged, self._chunk)
        if engine_cfg.draft_model and not self._mixed:
            raise ValueError(
                "speculative decoding rides the mixed scheduler and "
                "requires the paged KV layout with chunked prefill "
                f"(resolved kv_layout={'paged' if self._paged else 'slot'}, "
                f"prefill_chunk={self._chunk or None}, "
                f"ARKS_MIXED_STEP={_mx})")
        if (cfg.latent or cfg.windowed or cfg.recurrent) and not self._mixed:
            raise ValueError(
                f"model {cfg.name!r} (latent attention, window layers or "
                "recurrent layers) is served by the "
                "mixed scheduler only; the legacy scheduler speaks K and V "
                f"(resolved kv_layout={'paged' if self._paged else 'slot'}, "
                f"prefill_chunk={self._chunk or None}, "
                f"ARKS_MIXED_STEP={_mx})")
        self._mixed_budget = 0
        # Per-qmax grid plans memoized for the padding-waste counters
        # (_mixed_grid_counters): the plan is static per engine shape, so
        # the issue path pays one dict hit per dispatch.
        self._grid_plans: dict[int, dict] = {}
        # Token positions a block of the KV row write holds (set with the
        # first plan: the pool's width decides it).
        self._kv_write_block = 0
        # A SECOND, smaller shape of the sequential step's program, for
        # the steps that carry a prompt's tail or no prompt row at all: a
        # step costs its whole shape whatever it carries, and with a
        # budget of several pages most of a short step was padding
        # (PERF.md).  A quarter of the budget, where the budget is four
        # pages or more; 0 where it is not (one shape, as ever).
        self._mixed_tail = 0
        if self._mixed:
            self._mixed_budget = self._mixed_budget_cfg()
            if (self._paged and self._draft_cfg is None
                    and self._mixed_budget >= 4 * self._page_size()):
                self._mixed_tail = self._mixed_budget // 4
        self._decode_impl = self._resolve_decode_impl()
        if (self._mixed and self._decode_impl == "pallas"
                and jax.default_backend() == "tpu"):
            # The row-write kernels' block tables must fit SMEM: a v5e
            # refused 16 slots + a 2048-token budget at its first dispatch.
            # Refused here instead, by name.
            from arks_tpu.engine.paged import mixed_step_row_limit
            rows = engine_cfg.num_slots + self._mixed_budget
            if rows > mixed_step_row_limit(self._max_pages):
                raise ValueError(
                    f"num_slots {engine_cfg.num_slots} + "
                    f"ARKS_MIXED_CHUNK_TOKENS {self._mixed_budget} = {rows} "
                    "rows a mixed step: the KV row-write kernel prefetches "
                    "a block-table row per flat token into 1 MiB of SMEM, "
                    f"which holds {mixed_step_row_limit(self._max_pages)} "
                    f"rows of {self._max_pages} pages; lower the chunk "
                    "budget or the slots")

        # ---- Windowed residency (ARKS_RESIDENCY_WINDOW_PAGES) ----------
        # Created only once the mixed scheduler is resolved: the manager's
        # jitted helpers replicate the mixed program's batch shapes, and
        # the span chain needs the Pallas ragged kernel (the XLA oracle
        # attend cannot carry online-softmax state across page spans).
        if self._residency_window:
            if not self._mixed:
                raise ValueError(
                    "ARKS_RESIDENCY_WINDOW_PAGES requires the mixed "
                    "scheduler (paged KV + chunked prefill, "
                    "ARKS_MIXED_STEP!=0)")
            if self._draft_cfg is not None:
                raise ValueError(
                    "ARKS_RESIDENCY_WINDOW_PAGES is incompatible with "
                    "speculative decoding (spec verify blocks never ride "
                    "the span-streaming path)")
            if self._decode_impl != "pallas":
                raise ValueError(
                    "ARKS_RESIDENCY_WINDOW_PAGES requires "
                    "ARKS_ATTN_IMPL=pallas — the span chain carries "
                    "online-softmax state through the ragged kernel; the "
                    "XLA oracle attend is one-shot")
            from arks_tpu.engine.residency import ResidencyManager
            self._residency = ResidencyManager(self, self._residency_window)
            log.info("windowed residency: %d-page window (2x%d staging), "
                     "%d-page logical tables", self._residency_window,
                     self._residency.chunk, self._max_pages)

        # ---- Pipelined decode (ARKS_PIPELINE_DEPTH) --------------------
        # Steady-state decoding free of blocking host syncs: the decode
        # state (last token / lengths / liveness) lives ON DEVICE and each
        # dispatch consumes the previous dispatch's arrays, so up to
        # ``depth`` dispatches ride the stream while results drain through
        # async copies and resolve one full pipeline slot later.  Dead
        # slots self-mask (pad token, KV writes dropped at the slot
        # sentinel) until the host retires them at resolve.  0 disables
        # (pure sequential issue/resolve).  Speculative engines pipeline
        # too: the spec_pipe program threads accepted-length/last-token
        # state on device (draft propose + ragged verify + accept inside
        # every in-flight dispatch), so the draft's propose dispatches
        # fill the bubble the resolve queue exposes instead of forcing
        # depth 0.  (The depth itself is parsed once in __init__ — it is
        # model-independent.)
        # Rows a pipelined dispatch writes per slot: spec engines write a
        # draft_len verify block, mixed engines pipeline their own
        # one-token mixed step (kernel parity across the pipeline
        # boundary), legacy engines the K-step fused loop.  Also the
        # cache-cap margin for dead_len.
        if self._draft_cfg is not None:
            self._pipe_rows = engine_cfg.draft_len
        else:
            self._pipe_rows = (1 if self._mixed
                               else engine_cfg.steps_per_dispatch)
        # The pipe programs serve single-device engines only: a meshed
        # engine resolves to depth 0 and says so.  They have never
        # served under a mesh, and turning them on there changes what
        # tp > 1 replicas return (two SPMD compilations of the step round
        # differently; the one four-chip run that compared the greedy
        # streams found them different), so that waits for a
        # teacher-forced comparison on chips (ROADMAP S11).
        meshed = mesh is not None and mesh.size > 1
        self._pipe_depth = 0 if meshed else self._pipe_depth_cfg
        if meshed and self._pipe_depth_cfg:
            log.warning("ARKS_PIPELINE_DEPTH=%d: pipelined decode is off "
                        "under a device mesh (%s); this engine runs at "
                        "depth 0", self._pipe_depth_cfg, dict(mesh.shape))
        # In-flight dispatch records (FIFO), the threaded device state,
        # and the per-run device stop columns.  Engine-thread-only.
        self._pipe_inflight: "_deque" = _deque()
        self._pipe_state = None       # (tokens, lengths, alive) on device
        self._pipe_cols = None        # (stop_ids, dead_len) on device
        self._pipe_cols_np = None     # host copies for follower payloads
        self._pipe_last_resolve = None
        # Off-thread warmup of the pipe programs: jit's dispatch cache is
        # NOT populated by AOT lower/compile on this jax, so the warmed
        # executables are kept and called directly.  Until they exist the
        # engine stays on the (already warm) sequential path — a first
        # steady-state entry must never freeze live token streams behind
        # an inline compile.
        self._pipe_exec: dict = {}    # want_lp -> AOT-compiled executable
        self._pipe_warm_state = None  # None|"compiling"|"ready"|"failed"
        self._pipe_warm_thread = None
        # Slot registration generations: a pipelined dispatch snapshots
        # (slot, gen) pairs, so a resolve arriving after the slot was
        # retired AND re-admitted can never fan overshoot tokens into the
        # new request's stream.
        self._slot_gen = np.zeros((engine_cfg.num_slots,), np.int64)

        # Surface the RESOLVED configuration — the auto decisions, not the
        # requested ones — as an _info gauge and one startup log line, so
        # the benchmark's expect_labels, Grafana and an operator can tell
        # which path this replica actually runs.
        from arks_tpu.ops import autotune
        self._admit_sizes = self._admit_batch_sizes()
        self.resolved_config = {
            "kv_layout": "paged" if self._paged else "slot",
            "decode_impl": self._decode_impl,
            "admit_batch_sizes": ",".join(map(str, self._admit_sizes)),
            "pad_head": str(bool(self._pad_head())).lower(),
            "overlap": str(bool(self._overlap)).lower(),
            "kv_cache_dtype": self.ecfg.resolve_kv_cache_dtype(),
            "kv_dtype": self.ecfg.resolve_kv_cache_dtype(),
            "kv_page": _kv_page(cfg),
            # KV heads a layer ("full/window" where the window layers have
            # a count of their own) and the kinds of layer whose softmax
            # carries a sink logit a head ("none": no layer's does).
            "kv_heads": (f"{cfg.num_kv_heads}/{cfg.window_kv_heads}"
                         if cfg.window_kv_heads else str(cfg.num_kv_heads)),
            "attn_sink": ",".join(cfg.attn_sink) or "none",
            # The dtype the delta rule's state IS kept in, read off the
            # cache this engine built ("none": no linear layer): a
            # deployment's expect_labels holds the step to the precision
            # its configuration states.
            "state_dtype": (str(self._cache.lin.s.dtype)
                            if self._lin_slot_bytes else "none"),
            # This chip's share of each routed layer ("rank/size"; "0/1":
            # every expert is held here).
            "expert_share": f"{cfg.expert_parallel_rank}/"
                            f"{cfg.expert_parallel_size}",
            "kernel_tune": autotune.mode(),
            # The one grid the mixed attention call has (the label stays
            # for the dashboards and deploy files that expect it).
            "mixed_grid": "ragged",
            "weight_dtype": self.ecfg.weight_dtype or "native",
            "model": self.ecfg.model,
            "mixed_step": str(bool(self._mixed)).lower(),
            "pipeline_depth": str(self._pipe_depth),
            "prefix_host_mb": str(self._host_mb),
            # Spec engines run draft+verify inside the mixed dispatch (the
            # legacy fused spec loop is gone) — "true" whenever a draft
            # model is configured, since the mixed scheduler is a hard
            # requirement for speculation.
            "spec_mixed": str(self._draft_cfg is not None).lower(),
            # "swap" = preemption spills victim decode state to host RAM;
            # "replay" = victims re-queue and re-execute; "off" = priority
            # is pure queue ordering (the fallback matrix in
            # docs/application-usage.md).
            "preempt": ("off" if not self._preempt_on else
                        "swap" if self._preempt_swap_capable() else
                        "replay"),
            # Live topology (elastic resize rewrites these in place): the
            # mesh axes actually populated, not the requested config.
            "tensor_parallel": str(
                self.mesh.shape.get(tf.AXIS_MODEL, 1)
                if self.mesh is not None else 1),
            "data_parallel": str(
                self.mesh.shape.get("data", 1)
                if self.mesh is not None else 1),
        }
        self.metrics.engine_config_info.set(1, **self.resolved_config)
        log.info("engine resolved config: %s",
                 " ".join(f"{k}={v}" for k, v in
                          sorted(self.resolved_config.items())))
        # Static, like the labels above: chosen at trace time from the
        # step's [slots, vocab] shape, so every step takes it or none does.
        blocks = sampler_mod.window_blocks(engine_cfg.num_slots,
                                           cfg.vocab_size)
        self.metrics.sampler_window_blocks.set(blocks)
        log.info("sampler window: %s at [%d, %d]",
                 f"two stages over {blocks} blocks of "
                 f"{sampler_mod.WINDOW_BLOCK} columns" if blocks
                 else "one top_k call", engine_cfg.num_slots, cfg.vocab_size)

        # ARKS_KERNEL_TUNE=sweep benchmarks candidate kernel blocks for
        # THIS shape now, so _build_programs (and every later dispatch)
        # resolves tuned statics by pure table lookup only.
        self._warm_autotune()
        self._build_programs()

    def _warm_autotune(self) -> None:
        """ARKS_KERNEL_TUNE=sweep warm-up: benchmark the mixed kernel's
        (block_q, dma_depth) candidates at THIS engine's shape and persist
        the winner (ops.autotune.sweep).  Runs once, before any program is
        built — the serving step loop can only reach autotune.lookup (the
        hot-path guard asserts this split), and the table entry resolves
        to the same statics every time, so a persisted winner costs zero
        extra compiled variants."""
        from arks_tpu.ops import autotune
        if autotune.mode() != "sweep" or not self._paged or not self._mixed:
            return
        from arks_tpu.ops.paged_attention import paged_mixed_attention_flat
        cfg = self.cfg
        hkv = cfg.num_kv_heads
        g = cfg.num_heads // hkv
        d = tf.cache_head_dim(cfg, self._pad_head())
        page = self._page_size()
        qmax = self._mixed_budget + 1
        kvd = self.ecfg.resolve_kv_cache_dtype()
        kv = kvd if kvd in ("int8", "int4") else str(self._cache.k.dtype)
        sig = autotune.mixed_signature(hkv=hkv, g=g, d=d, page=page,
                                       qmax=qmax, kv=kv)
        if autotune.lookup("paged_mixed", sig) is not None:
            return
        s = self.ecfg.num_slots
        # Representative traffic on the engine's own (zeroed) pool, in the
        # sequential step's flat shape: every lane but the last decoding
        # one row, the last taking the whole chunk budget; tables pointing
        # at real pages.
        t_flat = s + self._mixed_budget
        q = jnp.ones((t_flat, hkv, g, d), jnp.float32)
        tables = jnp.zeros((s, self._max_pages), jnp.int32)
        pos = np.full((s,), page // 2, np.int32)
        ql = np.ones((s,), np.int32)
        ql[-1] = qmax
        pos[-1] = 0
        slot = np.minimum(np.arange(t_flat), s - 1).astype(np.int32)
        slot_j, start_j = jnp.asarray(slot), jnp.arange(s, dtype=jnp.int32)
        pos_j, ql_j = jnp.asarray(pos), jnp.asarray(ql)
        layer = jnp.asarray(0, jnp.int32)
        interpret = jax.default_backend() != "tpu"

        def bench(block_q: int, dma_depth: int,
                  head_group: int = hkv) -> None:
            out = paged_mixed_attention_flat(
                q, self._cache.k, self._cache.v, tables, slot_j, start_j,
                ql_j, pos_j, layer, self._cache.k_scale,
                self._cache.v_scale, block_q=block_q, interpret=interpret,
                dma_depth=dma_depth, head_group=head_group)
            np.asarray(out)  # block until the kernel actually ran

        # GQA head grouping shrinks per-item VMEM by hkv/head_group, so
        # grouped candidates may afford proportionally larger q blocks —
        # the block_q growth is where the bytes-moved win comes from.
        hgs = sorted({h for h in (1, 2, hkv) if hkv % h == 0})
        cands = [{"block_q": min(bq * (hkv // hg), qmax), "dma_depth": dd,
                  "head_group": hg}
                 for bq in (8, 16, 32)
                 for dd in (2, 4)
                 for hg in hgs]
        # De-dup candidates that clamp to the same statics.
        cands = [dict(t) for t in
                 sorted({tuple(sorted(c.items())) for c in cands})]
        autotune.sweep("paged_mixed", sig, cands, bench)

    # ------------------------------------------------------------------
    # Compiled programs
    # ------------------------------------------------------------------

    def _build_programs(self) -> None:
        cfg, mesh = self.cfg, self.mesh
        batch_axis = tf.batch_axis_for(mesh)  # ("slice","data") on multislice
        # Context parallelism: prefill's T shards over 'seq' and attention
        # runs as a ring (parallel.ring) — serving reaches the same
        # long-context path the trainer and dryrun exercise.
        seq_axis = "seq" if self._cp > 1 else None
        K = self.ecfg.steps_per_dispatch
        # Pipeline parallelism: stage-sharded prefill/decode programs with
        # microbatch overlap when slots divide evenly (else M=1, a plain
        # sequential pipeline — still correct, no overlap).
        if self._pp > 1:
            from arks_tpu.parallel import pipeline as pp_mod
            num_mb = self._pp if self.ecfg.num_slots % self._pp == 0 else 1

            def model_prefill(params, tokens, length):
                return pp_mod.pp_prefill(params, cfg, tokens, length, mesh)

            def model_decode(params, cache, tokens, lengths, tables=None):
                if tables is not None:
                    return pp_mod.pp_decode_step_paged(
                        params, cfg, cache, tables, tokens, lengths, mesh,
                        num_mb)
                return pp_mod.pp_decode_step(params, cfg, cache, tokens,
                                             lengths, mesh, num_mb)
        else:
            def model_prefill(params, tokens, length):
                return tf.prefill(params, cfg, tokens, length, mesh,
                                  seq_axis=seq_axis)

            def model_decode(params, cache, tokens, lengths, tables=None):
                return tf.decode_step(params, cfg, cache, tokens, lengths,
                                      mesh, batch_axis, tables=tables)

        # Detached (disaggregated) prefill: same math, but the KV comes
        # back REPLICATED over the mesh — on a multi-host gang the leader
        # must materialize the full [L,1,T,Hkv,D] block for the wire
        # transfer, and sharded outputs are not addressable across hosts.
        # (No-op constraint single-host.)
        def _replicate(x):
            if mesh is None or mesh.size == 1:
                return x
            from jax.sharding import NamedSharding, PartitionSpec
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, PartitionSpec()))

        def prefill_detached_prog(params, tokens, length, temperature,
                                  top_p, top_k, key, bias_ids, bias_vals,
                                  sup_ids, min_first, guide, guide_row,
                                  gtables, want_lp: bool):
            logits, ks, vs = model_prefill(params, tokens, length)
            state = sampler_mod.transient_state(
                temperature, top_p, top_k, key, cfg.vocab_size,
                bias_ids, bias_vals, sup_ids, min_first,
                guide=guide, guide_row=guide_row)
            ids, _ = sampler_mod.sample(logits, state, guide_tables=gtables)
            ks, vs = _replicate(ks), _replicate(vs)
            if want_lp:
                clp, vals, lids = sampler_mod.top_logprobs(logits, ids)
                return ids[0], clp[0], vals[0], lids[0], ks, vs
            return ids[0], ks, vs

        self._prefill_detached_fn = _named_jit(
            "arks_prefill_detached",
            functools.partial(prefill_detached_prog, want_lp=False))
        self._prefill_detached_lp_fn = _named_jit(
            "arks_prefill_detached_lp",
            functools.partial(prefill_detached_prog, want_lp=True))
        # _named_jit's fresh wrapper (here and for the other module-level
        # tf.* jits below) matters beyond the name: jit's trace cache is
        # keyed on the underlying callable, so a bare jax.jit(tf.insert)
        # would share one process-wide cache across engines and leak other
        # engines' shape variants into compiled_program_variants().
        self._insert_fn = _named_jit(
            "arks_insert", tf.insert,
            donate_argnums=(0,))

        # Fused BATCHED admission: M queued prompts prefill + sample +
        # insert + set_slot in ONE dispatch: batching amortizes the
        # per-dispatch round-trip AND raises prefill MXU utilization.  One
        # compiled program per (bucket, M, lp) combination — M is drawn
        # from _admit_batch_sizes() so the variant count stays bounded.
        def admit_batch(params, cache, sampling, tokens, lengths, slots,
                        pages, n_pages, temps, top_ps, top_ks, keys, pres,
                        freqs, bias_ids, bias_vals, sup_ids, min_first,
                        min_until, guide, guide_row, gtables, want_lp: bool):
            logits, ks, vs = model_prefill(params, tokens, lengths)
            tstate = sampler_mod.transient_state_batch(
                temps, top_ps, top_ks, keys, cfg.vocab_size,
                bias_ids, bias_vals, sup_ids, min_first,
                guide=guide, guide_row=guide_row)
            ids, tstate = sampler_mod.sample(logits, tstate,
                                             guide_tables=gtables)
            if self._paged:
                # Buckets smaller than a page: pad T up so the page-insert
                # loop can slice whole pages (tail rows masked by length).
                pad = (-ks.shape[2]) % self._page_size()
                if pad:
                    width = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
                    ks_in = jnp.pad(ks, width)
                    vs_in = jnp.pad(vs, width)
                else:
                    ks_in, vs_in = ks, vs
                cache = tf.insert_pages_batch(cache, ks_in, vs_in, pages,
                                              n_pages)
            else:
                cache = tf.insert_batch(cache, ks, vs, slots)
            fold = jax.vmap(lambda k: jax.random.fold_in(k, 1))(keys)
            # tstate's guide_row was advanced by the first sampled token —
            # the decode loop continues the DFA from there.
            sampling = sampler_mod.set_slots(
                sampling, slots, temps, top_ps, top_ks, fold, pres, freqs,
                bias_ids, bias_vals, sup_ids, min_until,
                guide=guide, guide_row=tstate.guide_row)
            if want_lp:
                clp, vals, lids = sampler_mod.top_logprobs(logits, ids)
                return ids, clp, vals, lids, cache, sampling, ks, vs
            return ids, cache, sampling, ks, vs

        self._admit_fn = _named_jit(
            "arks_admit", functools.partial(admit_batch, want_lp=False),
            donate_argnums=(1, 2))
        self._admit_lp_fn = _named_jit(
            "arks_admit_lp", functools.partial(admit_batch, want_lp=True),
            donate_argnums=(1, 2))

        if self._paged:
            def chunk_step(params, cache, tables_row, tokens, start, valid):
                return tf.prefill_chunk_paged(params, cfg, cache, tables_row,
                                              tokens, start, valid, mesh)
        else:
            def chunk_step(params, cache, slot, tokens, start, valid):
                return tf.prefill_chunk(params, cfg, cache, slot, tokens,
                                        start, valid, mesh)

        self._chunk_fn = _named_jit(
            "arks_chunk", chunk_step,
            donate_argnums=(1,))
        if self._paged:
            self._insert_pages_fn = _named_jit(
                "arks_insert_pages", tf.insert_pages,
                donate_argnums=(0,))
            # Host-tier spill/restore: gather evicted pages into a D2H
            # staging block; scatter host blocks back into fresh pool
            # pages.  The restore returns a marker READ FROM the written
            # pool, so marker.is_ready() == "the scatter landed" (a
            # passed-through input would alias and read ready instantly).
            self._spill_gather_fn = _named_jit(
                "arks_spill_gather", tf.gather_pool_pages)
            self._spill_warm = False

            def restore_scatter(cache, kb, vb, ksb, vsb, pages, n_valid):
                cache = tf.scatter_pool_pages(cache, kb, vb, pages, n_valid,
                                              k_scale=ksb, v_scale=vsb)
                return cache, cache.k[0, 0, 0, 0, 0]

            self._restore_fn = _named_jit(
                "arks_restore", restore_scatter,
                donate_argnums=(0,))

            # Preemptive swap (ARKS_PREEMPT): one victim slot's sampler
            # row out (the D2H decode-state snapshot: PRNG key, penalty
            # counts, DFA row — everything sample() evolves per slot) and
            # its counts back on resume (key/guide_row ride set_slot,
            # which RESETS counts — hence the separate restore).
            self._sampler_row_fn = _named_jit(
                "arks_sampler_row",
                lambda st, slot: (st.key[slot], st.counts[slot],
                                  st.guide_row[slot]))
            self._restore_counts_fn = _named_jit(
                "arks_restore_counts",
                lambda st, slot, row: st._replace(
                    counts=st.counts.at[slot].set(row)),
                donate_argnums=(0,))

        def sample_one(logits, temperature, top_p, top_k, key,
                       bias_ids, bias_vals, sup_ids, min_first,
                       guide, guide_row, gtables):
            state = sampler_mod.transient_state(
                temperature, top_p, top_k, key, cfg.vocab_size,
                bias_ids, bias_vals, sup_ids, min_first,
                guide=guide, guide_row=guide_row)
            ids, _ = sampler_mod.sample(logits, state, guide_tables=gtables)
            return ids[0]

        self._sample_one_fn = _named_jit(
            "arks_sample_one", sample_one)

        def sample_one_lp(logits, temperature, top_p, top_k, key,
                          bias_ids, bias_vals, sup_ids, min_first,
                          guide, guide_row, gtables):
            state = sampler_mod.transient_state(
                temperature, top_p, top_k, key, cfg.vocab_size,
                bias_ids, bias_vals, sup_ids, min_first,
                guide=guide, guide_row=guide_row)
            ids, _ = sampler_mod.sample(logits, state, guide_tables=gtables)
            clp, vals, lids = sampler_mod.top_logprobs(logits, ids)
            return ids[0], clp[0], vals[0], lids[0]

        self._sample_one_lp_fn = _named_jit(
            "arks_sample_one_lp", sample_one_lp)

        dtype = jnp.dtype(self.ecfg.dtype or cfg.dtype)
        self._extract_fn = _named_jit(
            "arks_extract",
            lambda cache, slot: tf.extract(cache, slot, dtype))

        # Donated slot-state writes: eager .at[].set() would copy the whole
        # [num_slots, vocab] penalty-counts buffer on EVERY admission
        # (~117MB at 192 slots x 152k vocab); donation updates in place.
        # Per-engine wrappers (_named_jit): jax.jit's trace cache is keyed
        # on the underlying callable, so jitting the module-level functions
        # directly would share one process-wide cache across engines and
        # make compiled_program_variants() report shapes traced by OTHER
        # engines (order-dependent compile-budget counts under pytest).
        #
        # The sizes M the promotion program is compiled for: a step's
        # completing prompts are padded up to the next one (a step cannot
        # complete more prompts than it has lanes or chunk tokens), with
        # rows that name no slot and so write nothing.  Few sizes: each
        # costs the first step 0.4 s (1.5 s with a cold compile cache) on
        # a v5e, a padded row costs 2.4 KB of operand.
        cap = max(1, min(self.ecfg.num_slots, self._mixed_budget or 1))
        nb, ns = sampler_mod.LOGIT_BIAS_MAX, sampler_mod.SUPPRESS_MAX
        self._promote_packs = packs = {
            m: _OperandPack([
                ("slots", np.int32, (m,), self.ecfg.num_slots),
                ("scalars_f", np.float32, (m, 4), 0.0),
                ("scalars_i", np.int32, (m, 4), 0),
                ("keys", np.uint32, (m, 2), 0),
                ("fold", bool, (m,), False),
                ("bias_ids", np.int32, (m, nb), -1),
                ("bias_vals", np.float32, (m, nb), 0.0),
                ("suppress_ids", np.int32, (m, ns), -1)])
            for m in sorted({m for m in (1, 8) if m < cap} | {cap})}
        by_len = {pk.size: pk for pk in packs.values()}
        self._promote_fn = _named_jit(
            "arks_promote",
            lambda state, operands: sampler_mod.promote_slots(
                state, **by_len[operands.shape[0]].unpack(operands)),
            donate_argnums=(0,))
        self._promote_warm = False
        self._clear_pen_fn = _named_jit(
            "arks_clear_penalties", sampler_mod.clear_slot_penalties,
            donate_argnums=(0,))

        # Free/pending slots park their lengths at this write-drop value;
        # the fused loop derives the active mask from it so PRNG keys and
        # penalty counts only advance for REGISTERED slots (deferred
        # admissions put decode dispatches between a slot's admit program
        # and its registration — see _drain_ready_admits).
        sentinel = self._park_sentinel()

        def decode_loop(params, cache, tokens, lengths, sstate, tables,
                        gtables):
            def body(carry, _):
                cache, tokens, lengths, sstate = carry
                active = lengths < sentinel
                # Feed-time counting: every generated token is fed exactly
                # once, which keeps the presence/frequency counts right
                # across the one-shot, chunked, and disagg admission paths.
                sstate = sampler_mod.count_tokens(sstate, tokens, active)
                logits, cache = model_decode(params, cache, tokens, lengths,
                                             tables)
                nxt, sstate = sampler_mod.sample(logits, sstate, active,
                                                 lengths,
                                                 guide_tables=gtables)
                return (cache, nxt, lengths + 1, sstate), nxt

            (cache, tokens, lengths, sstate), toks = jax.lax.scan(
                body, (cache, tokens, lengths, sstate), None, length=K)
            return cache, sstate, toks  # toks [K, B]

        self._decode_fn = _named_jit(
            "arks_decode", decode_loop,
            donate_argnums=(1, 4))

        def decode_loop_lp(params, cache, tokens, lengths, sstate, tables,
                           gtables):
            # The logprob variant: selected per dispatch when any live slot
            # asked for logprobs (separate compiled program — the common
            # case never pays the full-vocab log-softmax).
            def body(carry, _):
                cache, tokens, lengths, sstate = carry
                active = lengths < sentinel
                sstate = sampler_mod.count_tokens(sstate, tokens, active)
                logits, cache = model_decode(params, cache, tokens, lengths,
                                             tables)
                nxt, sstate = sampler_mod.sample(logits, sstate, active,
                                                 lengths,
                                                 guide_tables=gtables)
                clp, vals, lids = sampler_mod.top_logprobs(logits, nxt)
                return (cache, nxt, lengths + 1, sstate), (nxt, clp, vals, lids)

            (cache, tokens, lengths, sstate), outs = jax.lax.scan(
                body, (cache, tokens, lengths, sstate), None, length=K)
            return cache, sstate, outs  # ([K,B], [K,B], [K,B,L], [K,B,L])

        self._decode_lp_fn = _named_jit(
            "arks_decode_lp", decode_loop_lp,
            donate_argnums=(1, 4))

        # Pipelined decode program (ARKS_PIPELINE_DEPTH): the fused loop
        # with DEVICE-RESIDENT state — tokens/lengths/liveness come in as
        # arrays threaded from the PREVIOUS dispatch and go back out
        # updated, so the next dispatch needs no host values at all.  Dead
        # slots run masked at the park sentinel (pad fed, KV writes
        # dropped, keys/penalties frozen) and end-of-dispatch liveness
        # replicates the host's retire condition exactly
        # (sampler.advance_liveness) — which is what keeps token streams
        # byte-identical to the sequential path at any depth.
        if self._pp > 1:
            def model_decode_state(params, cache, tokens, lengths, alive,
                                   tables=None):
                eff = jnp.where(alive, lengths, jnp.int32(sentinel))
                return model_decode(params, cache, tokens, eff, tables)
        else:
            def model_decode_state(params, cache, tokens, lengths, alive,
                                   tables=None):
                return tf.decode_state_step(params, cfg, cache, tokens,
                                            lengths, alive, sentinel, mesh,
                                            batch_axis, tables=tables)

        def decode_pipe(params, cache, tokens, lengths, alive, stop_ids,
                        dead_len, sstate, tables, gtables, want_lp: bool):
            def body(carry, _):
                cache, tokens, lengths, sstate = carry
                eff = jnp.where(alive, lengths, jnp.int32(sentinel))
                active = eff < sentinel
                sstate = sampler_mod.count_tokens(sstate, tokens, active)
                logits, cache = model_decode_state(params, cache, tokens,
                                                   lengths, alive, tables)
                nxt, sstate = sampler_mod.sample(logits, sstate, active,
                                                 eff, guide_tables=gtables)
                nxt = jnp.where(alive, nxt, jnp.int32(0))
                if want_lp:
                    clp, vals, lids = sampler_mod.top_logprobs(logits, nxt)
                    out = (nxt, clp, vals, lids)
                else:
                    out = nxt
                return (cache, nxt, lengths + 1, sstate), out

            (cache, tokens, lengths, sstate), outs = jax.lax.scan(
                body, (cache, tokens, lengths, sstate), None, length=K)
            toks = outs[0] if want_lp else outs          # [K, B]
            alive = sampler_mod.advance_liveness(toks, alive, lengths,
                                                 stop_ids, dead_len)
            tokens = jnp.where(alive, tokens, jnp.int32(0))
            if want_lp:
                return (cache, sstate, toks, outs[1], outs[2], outs[3],
                        tokens, lengths, alive)
            return cache, sstate, toks, tokens, lengths, alive

        self._decode_pipe_fn = _named_jit(
            "arks_decode_pipe", functools.partial(decode_pipe, want_lp=False),
            donate_argnums=(1, 2, 3, 4, 7))
        self._decode_pipe_lp_fn = _named_jit(
            "arks_decode_pipe_lp",
            functools.partial(decode_pipe, want_lp=True),
            donate_argnums=(1, 2, 3, 4, 7))

        if self._mixed:
            # The unified mixed prefill+decode program: count the decode
            # feed, run ONE model forward over the flat token batch, then
            # ONE sampler.sample over every lane — persistent rows for
            # decoding slots, transient override columns (packed per lane)
            # for sequences whose prompt completes this step.  Only key and
            # guide-row advances of DECODE lanes merge back into the
            # persistent state; completion lanes are written by the host's
            # set_slot at registration, exactly like the legacy chunk path.
            held_stat = self._held_stat

            def with_counts(ids, held, valid):
                """The step's token ids with, for a latent routed model,
                four counts behind them (held pairs, overflow tiles
                needed, those of them the loop ran, valid rows; with
                identity experts their pairs ahead of the rows): they
                ride the ids' transfer (_count_held)."""
                if not held_stat:
                    return ids
                return jnp.concatenate(
                    [ids, held[0], jnp.sum(valid).astype(jnp.int32)[None]])

            self._mixed_pack = _OperandPack(self._mixed_fields(
                self.ecfg.num_slots + self._mixed_budget))
            # The tail shape's operands (None: one shape).  The program
            # knows its shape by the operand's length.
            self._mixed_tail_pack = _OperandPack(self._mixed_fields(
                self.ecfg.num_slots + self._mixed_tail)) \
                if self._mixed_tail else None
            self._mixed_tail_warm = not self._mixed_tail
            packs = {pk.size: pk for pk in (self._mixed_pack,
                                            self._mixed_tail_pack) if pk}

            def mixed_prog(params, cache, sampling, operands, gtables,
                           want_lp: bool):
                return mixed_body(params, cache, sampling, gtables, want_lp,
                                  **packs[operands.shape[0]].unpack(operands))

            def mixed_body(params, cache, sampling, gtables, want_lp: bool,
                           *, tokens, token_slot, token_pos, tables,
                           feed_tokens, feed_active, lengths, sample_src,
                           seq_q_start, seq_q_len, seq_pos_start, ov_mask,
                           ov_temp, ov_top_p, ov_top_k, ov_key, ov_bias_ids,
                           ov_bias_vals, ov_sup, ov_min_until, ov_guide,
                           ov_guide_row, win_tables=None):
                sampling = sampler_mod.count_tokens(sampling, feed_tokens,
                                                    feed_active)
                logits, cache, *held = tf.mixed_step(
                    params, cfg, cache, tables, tokens, token_slot,
                    token_pos, sample_src, seq_q_start, seq_q_len,
                    seq_pos_start, mesh, with_held=held_stat,
                    win_tables=win_tables)
                # The override columns are sampler work too (arks.sampler
                # in a profile, like the sampler's own functions).
                with jax.named_scope("arks.sampler"):
                    ovc = ov_mask[:, None]
                    # Completion lanes sample with transient first-token
                    # semantics: penalties are identity (their output is
                    # empty — counts don't matter once presence/frequency
                    # are zeroed), bias/suppression/guide come from the
                    # override columns, and min_until is pre-shifted by
                    # the host so ``lengths < min_until`` reads as the
                    # min_first flag.
                    eff = sampling._replace(
                        temperature=jnp.where(ov_mask, ov_temp,
                                              sampling.temperature),
                        top_p=jnp.where(ov_mask, ov_top_p, sampling.top_p),
                        top_k=jnp.where(ov_mask, ov_top_k, sampling.top_k),
                        key=jnp.where(ovc, ov_key, sampling.key),
                        presence=jnp.where(ov_mask, 0.0,
                                           sampling.presence),
                        frequency=jnp.where(ov_mask, 0.0,
                                            sampling.frequency),
                        bias_ids=jnp.where(ovc, ov_bias_ids,
                                           sampling.bias_ids),
                        bias_vals=jnp.where(ovc, ov_bias_vals,
                                            sampling.bias_vals),
                        suppress_ids=jnp.where(ovc, ov_sup,
                                               sampling.suppress_ids),
                        min_until=jnp.where(ov_mask, ov_min_until,
                                            sampling.min_until),
                        guide=jnp.where(ov_mask, ov_guide, sampling.guide),
                        guide_row=jnp.where(ov_mask, ov_guide_row,
                                            sampling.guide_row))
                ids, eff2 = sampler_mod.sample(logits, eff, feed_active,
                                               lengths,
                                               guide_tables=gtables)
                sampling = sampling._replace(
                    key=jnp.where(feed_active[:, None], eff2.key,
                                  sampling.key),
                    guide_row=jnp.where(feed_active, eff2.guide_row,
                                        sampling.guide_row))
                if want_lp:
                    clp, vals, lids = sampler_mod.top_logprobs(logits, ids)
                    return (with_counts(ids, held, token_slot >= 0), clp,
                            vals, lids, cache, sampling)
                return with_counts(ids, held, token_slot >= 0), cache, sampling

            self._mixed_fn = _named_jit(
                "arks_mixed_seq", functools.partial(mixed_prog, want_lp=False),
                donate_argnums=(1, 2))
            self._mixed_lp_fn = _named_jit(
                "arks_mixed_seq_lp",
                functools.partial(mixed_prog, want_lp=True),
                donate_argnums=(1, 2))

            # Device-state mixed variant (ARKS_PIPELINE_DEPTH): the
            # steady-state (decode-only) mixed step consuming threaded
            # token/length/liveness arrays.  ONE token per dispatch like
            # every mixed dispatch, and the SAME mixed kernel — the fused
            # K-step loop is mathematically equal but not bitwise equal
            # (fp reassociation), and a kernel switch at the pipeline
            # boundary would let sampled streams diverge across depths.
            B = self.ecfg.num_slots
            lane = jnp.arange(B, dtype=jnp.int32)

            def mixed_pipe(params, cache, tokens, lengths, alive, stop_ids,
                           dead_len, sstate, tables, gtables, want_lp: bool):
                eff = jnp.where(alive, lengths, jnp.int32(sentinel))
                sstate = sampler_mod.count_tokens(sstate, tokens, alive)
                # A model with window layers hands over both kinds of
                # table (_tables_arg).
                win_tables = None
                if isinstance(tables, tuple):
                    tables, win_tables = tables
                # Decode-only flat batch, lane t == slot t: dead lanes
                # park at the sentinel position (writes dropped, nothing
                # attended) exactly like the host-built batch's padding.
                logits, cache, *held = tf.mixed_step(
                    params, cfg, cache, tables, tokens,
                    jnp.where(alive, lane, jnp.int32(-1)), eff,
                    lane, lane, alive.astype(jnp.int32), eff, mesh,
                    with_held=held_stat, win_tables=win_tables)
                fed = alive
                nxt, sstate = sampler_mod.sample(logits, sstate, alive,
                                                 eff, guide_tables=gtables)
                nxt = jnp.where(alive, nxt, jnp.int32(0))
                lengths = lengths + 1
                alive = sampler_mod.advance_liveness(
                    nxt[None], alive, lengths, stop_ids, dead_len)
                tokens_out = jnp.where(alive, nxt, jnp.int32(0))
                toks = with_counts(nxt, held, fed)[None]
                if want_lp:
                    clp, vals, lids = sampler_mod.top_logprobs(logits, nxt)
                    # [1, B]-shaped outputs so the resolve fanout shares
                    # the K-step record format.
                    return (cache, sstate, toks, clp[None],
                            vals[None], lids[None], tokens_out, lengths,
                            alive)
                return (cache, sstate, toks, tokens_out, lengths,
                        alive)

            self._mixed_pipe_fn = _named_jit(
                "arks_mixed_pipe",
                functools.partial(mixed_pipe, want_lp=False),
                donate_argnums=(1, 2, 3, 4, 7))
            self._mixed_pipe_lp_fn = _named_jit(
                "arks_mixed_pipe_lp",
                functools.partial(mixed_pipe, want_lp=True),
                donate_argnums=(1, 2, 3, 4, 7))

        if self._draft_cfg is not None:
            dcfg = self._draft_cfg
            DK = self.ecfg.draft_len
            B = self.ecfg.num_slots
            lane = jnp.arange(B, dtype=jnp.int32)
            blk = jnp.arange(DK, dtype=jnp.int32)

            def draft_prefill_insert(dparams, dcache, tokens, length, slot):
                _, ks, vs = tf.prefill(dparams, dcfg, tokens, length, mesh)
                return tf.insert(dcache, ks, vs, slot)

            self._draft_prefill_fn = _named_jit(
                "arks_draft_prefill", draft_prefill_insert,
                donate_argnums=(1,))

            def draft_propose(dparams, dcache, tokens, lengths, sstate):
                """DK-step draft scan: propose DK-1 tokens per lane (greedy
                lanes argmax, sampled lanes draw from their effective
                filtered distribution).  DK steps, not DK-1: the extra
                step writes the LAST draft token's KV row, so after a
                fully-accepted block the next dispatch's draft attends a
                complete prefix (without it, row L+DK-1 is garbage and
                the draft mispredicts every DK-th token even when
                draft == target).  Parked lanes (lengths at the sentinel)
                drop their slot-cache writes like any other decode."""
                def body(carry, _):
                    dcache, tok, ln, keys = carry
                    logits, dcache = tf.decode_step(dparams, dcfg, dcache,
                                                    tok, ln, mesh)
                    tok, q, qp, qi, keys = sampler_mod.draft_sample(
                        logits, sstate, keys)
                    return (dcache, tok, ln + 1, keys), (tok, q, qp, qi)

                (dcache, _, _, keys), (toks, qs, qps, qis) = jax.lax.scan(
                    body, (dcache, tokens, lengths, sstate.key), None,
                    length=DK)
                drafts = jnp.swapaxes(toks, 0, 1)[:, : DK - 1]   # [B, DK-1]
                q_sel = jnp.swapaxes(qs, 0, 1)[:, : DK - 1]
                q_probs = jnp.swapaxes(qps, 0, 1)[:, : DK - 1]   # [B,DK-1,W]
                q_idx = jnp.swapaxes(qis, 0, 1)[:, : DK - 1]
                return dcache, drafts, q_sel, q_probs, q_idx, keys

            # Ragged spec-mixed program: draft propose + multi-token
            # verify + acceptance INSIDE the one mixed dispatch that also
            # carries prefill chunks.  Every decoding lane owns a fixed
            # q_len=DK verify block (rows [b*DK, (b+1)*DK) of the flat
            # batch — row 0 its last token, rows 1.. the draft's
            # proposals, scattered in ON DEVICE so no host sync touches
            # them); the chunk region starts at B*DK.  Verify logits are
            # just DK extra sample positions of the same tf.mixed_step
            # call — the per-spec verify program family is gone.
            spec_rows = (lane[:, None] * DK + 1
                         + jnp.arange(DK - 1, dtype=jnp.int32)[None, :]
                         ).reshape(-1)
            vsrc = jnp.arange(B * DK, dtype=jnp.int32)

            self._spec_pack = spack = _OperandPack(self._mixed_fields(
                B * DK + self._mixed_budget, spec=True))

            def spec_mixed_prog(params, dparams, cache, dcache, sampling,
                                operands, gtables, want_lp: bool):
                return spec_mixed_body(params, dparams, cache, dcache,
                                       sampling, gtables, want_lp,
                                       **spack.unpack(operands))

            def spec_mixed_body(params, dparams, cache, dcache, sampling,
                                gtables, want_lp: bool, *, tokens,
                                token_slot, token_pos, tables, feed_tokens,
                                feed_active, lengths, sample_src,
                                seq_q_start, seq_q_len, seq_pos_start,
                                spec_enable, ov_mask, ov_temp, ov_top_p,
                                ov_top_k, ov_key, ov_bias_ids, ov_bias_vals,
                                ov_sup, ov_min_until, ov_guide,
                                ov_guide_row):
                # Feed-time counting: spec-DISABLED penalized lanes
                # advance one normally-sampled token per dispatch, so
                # their counts must evolve; eligible lanes are
                # penalty-free and reset at slot reuse.
                sampling = sampler_mod.count_tokens(sampling, feed_tokens,
                                                    feed_active)
                dcache, drafts, q_sel, q_probs, q_idx, dkeys = \
                    draft_propose(dparams, dcache, feed_tokens, lengths,
                                  sampling)
                # Proposals land in every lane's verify block; lanes that
                # are not decoding this step keep padding rows
                # (token_slot=-1), so the scattered values write nothing.
                tokens = tokens.at[spec_rows].set(drafts.reshape(-1))
                src = jnp.concatenate([vsrc, sample_src])
                logits_all, cache = tf.mixed_step(
                    params, cfg, cache, tables, tokens, token_slot,
                    token_pos, src, seq_q_start, seq_q_len, seq_pos_start,
                    mesh)
                vlogits = logits_all[: B * DK].reshape(B, DK, -1)
                samp_logits = logits_all[B * DK:]               # [B, V]
                # Prompt-completing lanes: transient first-token sampling
                # with the override columns — identical semantics to the
                # plain mixed program (their persistent rows are written
                # by set_slot at registration).
                ovc = ov_mask[:, None]
                eff = sampling._replace(
                    temperature=jnp.where(ov_mask, ov_temp,
                                          sampling.temperature),
                    top_p=jnp.where(ov_mask, ov_top_p, sampling.top_p),
                    top_k=jnp.where(ov_mask, ov_top_k, sampling.top_k),
                    key=jnp.where(ovc, ov_key, sampling.key),
                    presence=jnp.where(ov_mask, 0.0, sampling.presence),
                    frequency=jnp.where(ov_mask, 0.0, sampling.frequency),
                    bias_ids=jnp.where(ovc, ov_bias_ids, sampling.bias_ids),
                    bias_vals=jnp.where(ovc, ov_bias_vals,
                                        sampling.bias_vals),
                    suppress_ids=jnp.where(ovc, ov_sup,
                                           sampling.suppress_ids),
                    min_until=jnp.where(ov_mask, ov_min_until,
                                        sampling.min_until),
                    guide=jnp.where(ov_mask, ov_guide, sampling.guide),
                    guide_row=jnp.where(ov_mask, ov_guide_row,
                                        sampling.guide_row))
                comp_ids, _ = sampler_mod.sample(samp_logits, eff, ov_mask,
                                                 lengths,
                                                 guide_tables=gtables)
                # Decoding lanes (enabled AND disabled) advance through
                # the rejection kernel — verify-aware guide advancement
                # included, so guided lanes speculate instead of being
                # carved out.
                out, counts, carry_keys, grow = \
                    sampler_mod.speculative_accept(
                        drafts, q_sel, q_probs, q_idx, vlogits, sampling,
                        dkeys, enable=spec_enable, lengths=lengths,
                        guide_tables=gtables)
                sampling = sampling._replace(
                    key=jnp.where(feed_active[:, None], carry_keys,
                                  sampling.key),
                    guide_row=jnp.where(feed_active, grow,
                                        sampling.guide_row))
                counts = jnp.maximum(counts, 1)
                if want_lp:
                    # Raw-distribution logprobs for the ONE token each
                    # disabled lp lane advanced (enabled lanes never carry
                    # logprobs — eligibility excludes them) and for
                    # completing lanes' first tokens, in one call.
                    lane_logits = jnp.where(ovc, samp_logits,
                                            vlogits[:, 0])
                    chosen = jnp.where(ov_mask, comp_ids, out[:, 0])
                    clp, vals, lids = sampler_mod.top_logprobs(lane_logits,
                                                               chosen)
                    return (out, counts, comp_ids, clp, vals, lids, cache,
                            dcache, sampling)
                return out, counts, comp_ids, cache, dcache, sampling

            self._spec_mixed_fn = _named_jit(
                "arks_spec_mixed",
                functools.partial(spec_mixed_prog, want_lp=False),
                donate_argnums=(2, 3, 4))
            self._spec_mixed_lp_fn = _named_jit(
                "arks_spec_mixed_lp",
                functools.partial(spec_mixed_prog, want_lp=True),
                donate_argnums=(2, 3, 4))

            # Device-state spec variant (ARKS_PIPELINE_DEPTH): the
            # steady-state (decode-only) spec step consuming threaded
            # token/length/liveness arrays — draft propose + ragged verify
            # + accept per dispatch with NO host values, so the draft's
            # propose work fills the resolve-queue bubble instead of
            # forcing spec engines sequential.  Same tf.mixed_step kernel
            # as the fresh-entry program (per-row math is lane-local, so
            # streams stay byte-identical across depths).
            def spec_pipe(params, dparams, cache, dcache, tokens, lengths,
                          alive, stop_ids, dead_len, spec_col, sstate,
                          tables, gtables, want_lp: bool):
                eff = jnp.where(alive, lengths, jnp.int32(sentinel))
                sstate = sampler_mod.count_tokens(sstate, tokens, alive)
                dcache, drafts, q_sel, q_probs, q_idx, dkeys = \
                    draft_propose(dparams, dcache, tokens, eff, sstate)
                block = jnp.concatenate([tokens[:, None], drafts], axis=1)
                flat_slot = jnp.repeat(
                    jnp.where(alive, lane, jnp.int32(-1)), DK)
                flat_pos = (eff[:, None] + blk[None, :]).reshape(-1)
                src = jnp.concatenate([vsrc, lane * DK])
                logits_all, cache = tf.mixed_step(
                    params, cfg, cache, tables, block.reshape(-1),
                    flat_slot, flat_pos, src, lane * DK,
                    jnp.where(alive, DK, 0).astype(jnp.int32), eff, mesh)
                vlogits = logits_all[: B * DK].reshape(B, DK, -1)
                out, counts, carry_keys, grow = \
                    sampler_mod.speculative_accept(
                        drafts, q_sel, q_probs, q_idx, vlogits, sstate,
                        dkeys, enable=spec_col & alive, lengths=eff,
                        guide_tables=gtables)
                sstate = sstate._replace(
                    key=jnp.where(alive[:, None], carry_keys, sstate.key),
                    guide_row=jnp.where(alive, grow, sstate.guide_row))
                counts = jnp.maximum(counts, 1)
                # Liveness over the ACCEPTED prefix only: tokens past
                # counts are rejected drafts the host never sees — they
                # must not trip the stop check.
                valid = blk[None, :] < counts[:, None]
                masked = jnp.where(valid & alive[:, None], out,
                                   jnp.int32(-1))
                lengths = lengths + jnp.where(alive, counts, jnp.int32(1))
                alive = sampler_mod.advance_liveness(
                    jnp.swapaxes(masked, 0, 1), alive, lengths, stop_ids,
                    dead_len)
                last = jnp.take_along_axis(out, (counts - 1)[:, None],
                                           axis=1)[:, 0]
                tokens_out = jnp.where(alive, last, jnp.int32(0))
                toks = jnp.swapaxes(out, 0, 1)              # [DK, B]
                if want_lp:
                    clp, vals, lids = sampler_mod.top_logprobs(
                        vlogits[:, 0], out[:, 0])
                    # [1, B]-shaped so the resolve fanout shares the
                    # K-step record format (lp lanes always land c == 1).
                    return (cache, dcache, sstate, toks, counts,
                            clp[None], vals[None], lids[None], tokens_out,
                            lengths, alive)
                return (cache, dcache, sstate, toks, counts, tokens_out,
                        lengths, alive)

            self._spec_pipe_fn = _named_jit(
                "arks_spec_pipe", functools.partial(spec_pipe, want_lp=False),
                donate_argnums=(2, 3, 4, 5, 6, 10))
            self._spec_pipe_lp_fn = _named_jit(
                "arks_spec_pipe_lp",
                functools.partial(spec_pipe, want_lp=True),
                donate_argnums=(2, 3, 4, 5, 6, 10))

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------

    def min_tokens_suppress_ids(self, p) -> list[int]:
        """Deduped token ids suppressed on device while a request is below
        min_tokens (eos unless ignore_eos, plus stop_token_ids).  The ONE
        definition shared by admission validation, _shape_cols, and the
        HTTP validator — divergence would let np_suppress_col raise on the
        engine thread, tripping _run's blanket fault handler."""
        if p.min_tokens <= 0:
            return []
        stop: list[int] = []
        if not p.ignore_eos:
            stop += list(self.cfg.eos_token_ids)
            stop += list(self.tokenizer.eos_token_ids)
        stop += list(p.stop_token_ids)
        return list(dict.fromkeys(stop))

    def add_request(self, request: Request) -> None:
        # Validate the min_tokens suppress set HERE, on the caller's
        # thread: np_suppress_col raising inside the scheduler would trip
        # _run's blanket fault handler and abort every in-flight request,
        # while a ValueError here fails only the offender (the HTTP layer
        # 400s the same condition before it ever reaches the engine).
        sampler_mod.np_suppress_col(
            self.min_tokens_suppress_ids(request.params))
        if request.params.guide is not None:
            # Cheap syntactic validation on the CALLER's thread: malformed
            # patterns raise GuideError (ValueError -> HTTP 400) here.
            # The seconds-scale DFA build is handed to the compiler's
            # worker pool (ensure) — this call never blocks, and the
            # scheduler parks the request until the guide publishes
            # (compile failure -> per-request "error" output, not a
            # dropped stream).
            if self.guides.lookup(*request.params.guide) is None:
                self.guides.validate(*request.params.guide)
            # Only kick the background compile when the request targets
            # the ACTIVE model: guide registries are per-model context, so
            # compiling into the current model's tables for a request that
            # will park on a model switch would waste a registry row (the
            # guide gate re-ensures after the switch).  Racy read of
            # self.cfg across a switch degrades to exactly that waste.
            want = request.model or getattr(self, "_primary_model", None)
            if want in (None, self.cfg.name):
                self.guides.ensure(*request.params.guide)
            self.metrics.guided_requests_total.inc(
                1, kind=request.params.guide[0])
        if (request.model is not None and self.pool is not None
                and request.model != self.cfg.name
                and self.pool.has(request.model)):
            # Cold-start prefetch: start streaming this model's weights
            # NOW — a queued request behind busy slots would otherwise
            # only kick the load once it parks.  Racy read of self.cfg
            # across a switch at worst hints the active model; the
            # scheduler drops stale hints.
            self._model_prefetch.add(request.model)
        if self.trace.enabled:
            # Register the trace context (caller's thread — locking is
            # fine here) and open the queue span.
            self.trace.register(
                request.request_id, ctx=request.trace,
                tier=self._slo.tier_of(request.params.priority)
                if self._slo else None)
            self.trace.evt(request.request_id, "queue", "B")
            with logctx.bound(request.request_id,
                              request.trace.trace_id
                              if request.trace is not None else None):
                log.debug("request queued: %d prompt tokens, priority %d",
                          len(request.prompt_ids), request.params.priority)
        self.metrics.num_requests_waiting.inc(1)
        with self._abort_lock:
            self._queued_rids.add(request.request_id)
            self._queue_seq += 1
            seq = self._queue_seq
        try:
            # Bounded put: external admissions hit the overload ladder's
            # first rung HERE, on the caller's (server) thread — the
            # QueueFullError carries a drain-rate-derived Retry-After the
            # HTTP layer maps to 429 (tenant cap) / 503 (total cap).
            self._queue.put((request.params.priority, seq, request),
                            bounded=True)
        except fairqueue.QueueFullError as e:
            with self._abort_lock:
                self._queued_rids.discard(request.request_id)
            self.metrics.num_requests_waiting.inc(-1)
            self.metrics.requests_shed_total.inc(
                1,
                reason="queue_full" if e.scope == "queue" else "tenant_cap",
                tier=self._slo.tier_of(request.params.priority),
                tenant=self._tenant_labels.label(request.tenant))
            raise
        self.metrics.admission_queue_depth.set(self._queue.qsize())

    def abort(self, request_id: str) -> None:
        """Free the request's slot at the next scheduler boundary (client
        disconnect, stop-string hit in the server, etc.)."""
        with self._abort_lock:
            self._aborted.add(request_id)

    def start(self) -> None:
        self._running = True
        self.trace.start()
        deadline = knobs.get_float("ARKS_DISPATCH_DEADLINE_S", fallback=0.0)
        if deadline > 0:
            # Wedged-dispatch escalation: a device call that never returns
            # (hung DMA, deadlocked collective) cannot be cancelled from
            # Python — flip state (readiness 503s), dump diagnostics, exit
            # 70 so the supervisor restarts the pod.  The deadline must
            # exceed the worst in-step jit compile (docs/runbook.md).
            self._watchdog = faults_mod.Watchdog(
                deadline, lambda: self._step_hb, self._on_wedged)
            self._watchdog.start()
        self._thread = threading.Thread(target=self._run, name="engine", daemon=True)
        self._thread.start()

    def _on_wedged(self, phase: str, age_s: float) -> None:
        """Watchdog callback: record the wedged state (readiness reads it)
        and log the in-flight picture an operator needs post-mortem."""
        self._set_state("wedged")
        log.critical(
            "wedged dispatch diagnostics: phase=%s age=%.1fs slots=%s "
            "prefilling=%s pending_admits=%d pipe_inflight=%d queue=%d",
            phase, age_s,
            {s: st.request.request_id for s, st in self._slots.items()},
            {s: cs.request.request_id for s, cs in self._prefilling.items()},
            self._pending_n, len(self._pipe_inflight), self._queue.qsize())
        # Flight recorder: the wedge dump ships its own timeline — the
        # last N span events across every thread ring (this runs on the
        # watchdog thread; the wedged step loop never pays for it).
        tail = self.trace.tail()
        if tail:
            log.critical("flight recorder (last %d events): %s", len(tail),
                         "; ".join(
                             f"{e['t']:.3f} {e['rid'] or '<engine>'} "
                             f"{e['name']}/{e['ph']}" for e in tail))

    def _set_state(self, state: str) -> None:
        self._state = state
        self.metrics.engine_state.set(faults_mod.STATE_CODES[state])

    @property
    def state(self) -> str:
        """"serving" | "recovering" | "wedged" — the /readiness gate."""
        return self._state

    def stop(self) -> None:
        self._running = False
        self.trace.stop()
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._thread is not None:
            self._thread.join(timeout=120.0)
            if self._thread.is_alive():
                # Engine thread wedged (e.g. a hung device call inside
                # _resolve_admit_batch): _pending_admits/_pending_n/_free
                # are engine-thread-owned, so touching them here would
                # race a thread that may still wake up.  _run()'s finally
                # aborts the deferred admissions itself if it ever exits.
                log.warning(
                    "engine thread did not exit within 120s; it aborts "
                    "deferred admissions itself on exit")
        # The off-thread pipe-program build runs a bound method: until it
        # returns it keeps this engine, and with it the weights and the
        # pool on the device, alive past stop().  The next engine built in
        # the process needs that room.
        warm = [self._pipe_warm_thread] + [
            ctx.get("_pipe_warm_thread") for ctx in self._model_ctxs.values()]
        for t in warm:
            if t is not None:
                t.join(timeout=120.0)
                if t.is_alive():
                    log.warning("pipe-program build still compiling 120s "
                                "after stop(); it holds the engine until "
                                "it returns")
        # Graceful-stop persistence: publish the warm prefixes still
        # resident on-device / in tier 1 into the disk store BEFORE the
        # writer gets its exit sentinel, so a relaunch on the same
        # ARKS_PREFIX_DISK_DIR re-serves them without re-prefilling.
        if self._disk is not None:
            try:
                self._flush_warm_to_disk()
            except Exception as e:  # best-effort: warmth, not shutdown
                faults_mod.swallowed("disk_tier.flush", e)
        # Disk-spill writer / prefix-fetch workers: daemon threads, but
        # hand them their exit sentinel so a clean stop doesn't leave
        # them blocked on an empty queue.
        for wq in (self._disk_write_queue, self._fetch_queue):
            if wq is not None:
                try:
                    wq.put_nowait(None)
                except queue.Full:
                    pass
        if self._disk_writer is not None:
            # Queued spill writes land before the process exits.
            self._disk_writer.join(timeout=30.0)
        # Deferred admissions are drained by _run()'s finally on the
        # engine thread itself; a never-started engine has none.

    @property
    def num_running(self) -> int:
        # Deferred admit batches hold slots too — external drivers poll
        # this to detect completion, and a pending admission is running
        # work in every sense that matters to them.
        return len(self._slots) + self._pending_n

    def compiled_program_variants(self) -> dict[str, int]:
        """Program name -> number of compiled variants, for every jitted
        function this engine owns.  The compile-budget regression surface:
        the mixed scheduler exists partly to collapse the (bucket, M, lp)
        admit-program family into ONE budget-shaped program, and a future
        scheduler edit that silently reintroduces per-shape retraces shows
        up here long before it shows up as TPU compile stalls."""
        out: dict[str, int] = {}
        for name, fn in vars(self).items():
            size = getattr(fn, "_cache_size", None)
            if callable(size):
                try:
                    out[name] = int(size())
                except Exception as e:  # jax internals may shift across versions
                    faults_mod.swallowed("compiled_program_variants", e)
                    continue
        return out

    @property
    def idle(self) -> bool:
        """No decoding slots, no queued admissions, no chunked prefills,
        deferred admit batches or output frames, or requests parked on a
        guide compile, host-tier restore, or model switch — the drain gate
        (servers must not poke at privates)."""
        return (not self._slots and self._queue.empty()
                and not self._prefilling and not self._pending_admits
                and not self._awaiting_guide
                and not self._awaiting_restore
                and not self._awaiting_fetch
                and not self._awaiting_model
                and self._pool_waiting is None
                and not self._swap_pending and not self._swapped
                and self._deferred is None)

    # ------------------------------------------------------------------
    # Scheduler loop
    # ------------------------------------------------------------------

    def _mixed_budget_cfg(self) -> int:
        """ARKS_MIXED_CHUNK_TOKENS as a mixed engine takes it: the prefill
        rows of one step (default: one chunk), inside the cache."""
        budget = knobs.get_int("ARKS_MIXED_CHUNK_TOKENS",
                               fallback=self._chunk)
        if budget < 1:
            raise ValueError(
                f"ARKS_MIXED_CHUNK_TOKENS={budget}: must be >= 1")
        return min(budget, self.ecfg.max_cache_len)

    def _init_paged_cache(self, num_pages: int, dtype):
        """The paged pool(s) as this engine's configuration shapes them: a
        model with window layers gets its window pool beside."""
        win = ({"win_pages": self._win.alloc.num_pages}
               if self._win is not None else
               {"state_slots": self.ecfg.num_slots} if self.cfg.recurrent
               else {})
        return tf.init_paged_cache(
            self.cfg, num_pages, self._page_size(), self._cache_dtype(dtype),
            quantized=self.ecfg.kv_quantized, pad_head=self._pad_head(),
            kv_bits=min(self.ecfg.kv_bits, 8), **win)

    def _tables_arg(self):
        """The block tables as a pipe program takes them: a model with
        window layers hands over both kinds."""
        if self._win is None:
            return jnp.asarray(self._tables)
        return (jnp.asarray(self._tables), jnp.asarray(self._win.tables))

    def _cache_dtype(self, engine_dtype):
        kvd = self.ecfg.resolve_kv_cache_dtype()
        return jnp.bfloat16 if kvd == "bf16" else engine_dtype

    def _pad_head(self) -> bool:
        """Lane-pad the stored KV head dim to 128 for d<128 models so they
        ride the compiled Pallas decode kernels instead of the XLA
        fallback (exact math — zero K lanes add 0 to scores, padded V
        columns are sliced off; ops/attention prescales q).  Costs
        128/head_dim x KV HBM; ARKS_PAD_HEAD_DIM=0 opts out."""
        if not knobs.get_bool("ARKS_PAD_HEAD_DIM"):
            return False
        from arks_tpu.ops.attention import default_decode_impl
        return (jax.default_backend() == "tpu"
                and default_decode_impl() == "pallas"
                and (self.cfg.head_dim % 128 != 0
                     or self.cfg.value_dim % 128 != 0)
                and self._pp == 1)

    def _park_sentinel(self) -> int:
        """Write-drop length for parked (free/pending) slots: cache ops
        drop KV writes at/beyond it, and the fused decode loop's active
        mask freezes PRNG keys + penalty counts there.  ONE definition —
        the mask is only correct while every parking site agrees."""
        return (self._max_pages * self._page_size() if self._paged
                else self.ecfg.max_cache_len)

    def _page_size(self) -> int:
        """Page size = chunk size (a reused prefix then ends exactly where
        the tail chunk prefill starts), or 256 when chunking is off —
        capped by the cache window so small configs (pp disables chunking)
        still page."""
        return self._chunk or min(256, self.ecfg.max_cache_len)

    def _page_align(self) -> int:
        """Kernel alignment for the page size (compiled TPU only): int8
        scale RMW chunks are 128-wide, bf16 row chunks 16-wide."""
        if jax.default_backend() != "tpu":
            return 1
        return 128 if self.ecfg.kv_quantized else 16

    def _grow_slot_pages(self, rows_per_slot: int, ahead: int = 0) -> None:
        """Paged layout: before a dispatch that writes ``rows_per_slot``
        rows per active slot (K for the fused decode loop, draft_len for a
        speculative verify), extend each slot's block table to cover them.
        ``ahead`` counts dispatches already in flight (pipelined decode):
        the host's lagged lengths must pre-own pages for EVERY unresolved
        dispatch's write window, not just the next one.  Host-only
        bookkeeping; the pool is sized so allocation cannot fail for
        active slots (pages_needed clamps at the per-slot table width —
        the device's dead_len mask retires a slot before any write could
        land past it)."""
        from arks_tpu.engine.paged import pages_needed
        self._faults.fire("pages")
        page = self._page_size()
        rows = rows_per_slot * (ahead + 1)
        for slot in self._slots:
            if self._residency is not None and slot in self._residency.slots:
                # Engaged slots own staging + hot-tail pages only; the
                # residency manager grows their tail itself.
                continue
            need = pages_needed(int(self._lengths[slot]), rows, page,
                                self._max_pages)
            row = self._slot_pages[slot]
            if len(row) < need:
                new = self._alloc.alloc(need - len(row))
                self._tables[slot, len(row): len(row) + len(new)] = new
                row.extend(new)
            if self._win is not None:
                # From the RESOLVED length: whatever is in flight reads at
                # or past it (WindowPages).
                self._win_cover(slot, int(self._lengths[slot]), rows)
        if self._lin_slot_bytes:
            self._count_state_bytes()
        # Any eviction the allocations caused must spill BEFORE the
        # caller's dispatch can write the recycled pages (stream order).
        self._spill_flush()

    def _count_state_bytes(self) -> None:
        """A model with linear-attention layers, once a dispatch: what the
        live sequences hold of each kind, as gauges and summed over the
        dispatches (docs/monitoring.md)."""
        m = self.metrics
        state = (self.ecfg.num_slots - len(self._free)) \
            * self._lin_slot_bytes
        pages = (self._alloc.num_pages - self._alloc.free_pages) \
            * self._page_bytes
        m.linear_state_bytes.set(state)
        m.kv_page_bytes.set(pages)
        m.kv_held_byte_steps_total.inc(state, kind="state")
        m.kv_held_byte_steps_total.inc(pages, kind="pages")

    def _count_state_lanes(self, step: int, chunk: int,
                           scan_rows: int = 0) -> None:
        """Beside :meth:`_count_state_bytes`, once the dispatch's rows a
        slot are known: how many slots the state update's kernel steps
        (``step``: one row), how many the chunked scan walks (``chunk``,
        ``scan_rows`` rows in all), and the rest, which neither touches."""
        c = self.metrics.linear_state_lane_steps_total
        c.inc(step, path="step")
        c.inc(chunk, path="chunk")
        c.inc(self.ecfg.num_slots - step - chunk, path="idle")
        if self.cfg.ssm:
            self.metrics.ssm_rows_total.inc(step, path="step")
            self.metrics.ssm_rows_total.inc(scan_rows, path="scan")

    def _resolve_kv_layout(self) -> bool:
        layout = self.ecfg.kv_layout
        if layout not in ("auto", "slot", "paged"):
            raise ValueError(f"kv_layout={layout!r}")
        int4 = self.ecfg.kv_bits == 4
        if layout == "slot":
            if int4:
                raise ValueError(
                    "kv_cache_dtype=int4 requires the paged KV layout "
                    "(packed pages + fused dequant live in the paged mixed "
                    "kernel; there is no int4 slot cache)")
            return False
        from arks_tpu.parallel.mesh import AXIS_SLICE
        dp = (self.mesh.shape.get(tf.AXIS_DATA, 1)
              * self.mesh.shape.get(AXIS_SLICE, 1)) \
            if self.mesh is not None else 1
        blockers = []
        if dp > 1:
            blockers.append("data parallelism")
        if (jax.default_backend() == "tpu"
                and self.cfg.head_dim % 128 != 0
                and not self._pad_head()):
            blockers.append("head_dim not 128-lane aligned (and lane "
                            "padding disabled)")
        page = self._page_size()
        if page % self._page_align() != 0:
            blockers.append(f"page size {page} not {self._page_align()}-aligned")
        if self.ecfg.max_cache_len % page != 0:
            blockers.append(f"max_cache_len not a multiple of page {page}")
        if layout == "paged":
            if blockers:
                raise ValueError(
                    "kv_layout=paged is incompatible with: "
                    + ", ".join(blockers))
            return True
        # auto: paged wherever supported — the layout every benchmark
        # cell runs, with on-device prefix sharing.  CPU stays on the slot
        # layout (interpret-mode kernels are test-only) EXCEPT for draft
        # engines: speculation requires the mixed scheduler, whose CPU path
        # runs the XLA oracle — resolving slot there would turn a valid
        # spec config into an init error.
        if blockers:
            if int4:
                raise ValueError(
                    "kv_cache_dtype=int4 requires the paged KV layout, "
                    "which this shape cannot use: " + ", ".join(blockers))
            return False
        if jax.default_backend() != "tpu":
            # int4 forces paged wherever the shape allows it (there is no
            # int4 slot cache — see the kv_cache_dtype=int4 ValueError).
            return (int4 or (self.ecfg.draft_model is not None
                             and bool(self._chunk)))
        return True

    def _resolve_decode_impl(self) -> str:
        """The decode attention path this engine's programs TRACE —
        'pallas' | 'xla' — decided from the same blocker list the
        attention dispatchers read (ops.attention.kernel_blockers), so the
        ``engine_config_info{decode_impl}`` label cannot say pallas while
        the XLA gather path serves.  Kernels asked for by name that this
        shape cannot take are an error here, not a quiet fallback."""
        from arks_tpu.ops.attention import default_decode_impl, kernel_blockers
        want = default_decode_impl()
        if self.cfg.latent:
            # No quiet fallback: on a TPU the latent kernel runs or the
            # engine refuses; off a TPU "xla" is the gather oracle unless
            # the kernel was asked for by name (interpret mode).
            from arks_tpu.ops.attention import latent_kernel_blockers
            blockers = latent_kernel_blockers(
                tf.cache_head_dim(self.cfg, self._pad_head()),
                self.cfg.kv_lora_rank, self.mesh)
            if blockers and (want == "pallas"
                             or jax.default_backend() == "tpu"):
                raise ValueError(
                    f"model {self.cfg.name!r}: the latent attention "
                    "kernel cannot serve this engine: "
                    + "; ".join(blockers))
            if want != "pallas" and jax.default_backend() == "tpu":
                raise ValueError(
                    f"model {self.cfg.name!r}: ARKS_ATTN_IMPL={want} on a "
                    "TPU would serve the latent pool through the XLA "
                    "gather (a per-token copy of every page)")
            return want
        if want != "pallas":
            return want
        mesh = self.mesh
        kv_sharded = mesh is not None and tf.shard_kv_heads(
            self.cfg, mesh.shape.get(tf.AXIS_MODEL, 1))
        blockers = kernel_blockers(
            tf.cache_head_dim(self.cfg, self._pad_head()), mesh, kv_sharded,
            tf.AXIS_MODEL,
            int4_decode=self.ecfg.kv_bits == 4 and not self._mixed,
            pp=self._pp > 1)
        if not blockers:
            return "pallas"
        if knobs.get_str("ARKS_ATTN_IMPL") == "pallas":
            raise ValueError(
                "ARKS_ATTN_IMPL=pallas, but this engine cannot take the "
                "Pallas decode kernels: " + "; ".join(blockers))
        log.warning("decode attention runs the XLA path, not the Pallas "
                    "kernels: %s", "; ".join(blockers))
        return "xla"

    def _shard_cache(self, cache):
        if self._pp > 1:
            from arks_tpu.parallel.pipeline import shard_cache_pp
            return shard_cache_pp(cache, self.mesh)
        return tf.shard_cache(cache, self.cfg, self.mesh)

    def _shard_paged(self, cache):
        """Paged-pool sharding, pp-aware — used by BOTH engine init and
        _reset_device_state (a reset that replicated a stage-sized pool
        onto every stage device would OOM inside the recovery path)."""
        if self._pp > 1:
            from arks_tpu.parallel.pipeline import shard_paged_cache_pp
            return shard_paged_cache_pp(cache, self.mesh)
        return tf.shard_paged_cache(cache, self.cfg, self.mesh)

    @_scoped("guide")
    def _ensure_guides_uploaded(self) -> None:
        """Refresh the device guide tables when the compiler's version
        bumped (server threads compile guides on THEIR threads; only the
        upload happens here, on the engine thread, between dispatches).
        Multi-host: the leader replicates the host tables first so
        followers re-upload the same contents before mirroring the next
        dispatch."""
        if self._guide_ver == self.guides.version:
            return
        self._faults.fire("guide")
        cls_host, trans_host, ver = self.guides.snapshot()
        self._emit("guides", class_ids=cls_host, trans=trans_host,
                   version=ver)
        self._guide_dev = jax.device_put((cls_host, trans_host))
        self._guide_ver = ver

    def _emit(self, op: str, **payload) -> None:
        """Broadcast a device dispatch to follower processes (multi-host);
        no-op single-host.  MUST precede the local dispatch at every site —
        followers replay the identical jit sequence, which is what keeps
        the gang's collectives in lockstep.

        A broken dispatch channel is fatal to the whole gang: without it the
        followers stop mirroring and the next collective hangs forever, with
        every process alive — invisible to the gang driver's liveness checks.
        Exit instead, so the driver restarts the group (the same policy
        jax's own coordination service applies when a peer dies)."""
        if self.dispatcher is None:
            return
        try:
            self.dispatcher.broadcast(op, payload)
        except OSError:
            log.critical(
                "dispatch channel to followers broke; exiting so the gang "
                "driver restarts the whole group", exc_info=True)
            os._exit(70)

    def _run(self) -> None:
        try:
            self._run_loop()
        finally:
            # Loop exit (stop(), or a late wake-up after a wedged device
            # call outlived stop()'s join window): no scheduler remains to
            # resolve deferred admissions, so fail their clients here ON
            # the engine thread — the only thread allowed to touch
            # _pending_admits/_pending_n/_free.  What the last resolve
            # held back for the next dispatch goes out first.
            self._flush_deferred()
            self._abort_pending_admits()
            self._abort_awaiting_guide()
            self._abort_awaiting_restores()
            self._abort_awaiting_fetches()
            self._abort_awaiting_model()
            self._abort_pool_waiting()
            self._abort_swapped()

    def _run_loop(self) -> None:
        prof = self.profiler
        while self._running:
            t0 = time.monotonic()
            self._step_hb = ("step", t0)
            try:
                if prof.active:
                    # Stamp the live span ids into the device timeline so
                    # the profile correlates back to the trace store.
                    # ``phase.step.loop`` is the step as this loop calls
                    # it.  Its sections are begin/end pairs, and a thread
                    # asked for the GIL gives it up right AFTER a call
                    # returns, so a wait to get it back falls between one
                    # section's end and the next one's begin (on the chip:
                    # 5-15 ms at the return of step(), behind the handler
                    # threads the fan-out woke); this span's end comes
                    # after that wait, so the time has a name.
                    with prof.annotate("arks_step", self.trace.live_ids()):
                        self.trace.evt("", "phase.step.loop", "B")
                        try:
                            progressed = self.step()
                        finally:
                            self.trace.evt("", "phase.step.loop", "E")
                else:
                    progressed = self.step()
                self._consec_faults = 0
            except Exception as e:
                # Fault-isolated recovery (engine.faults): quarantine the
                # culprit request(s), REBUILD the device state (the
                # dispatch donated cache+sampler buffers, so they may
                # already be invalidated), and token-replay every other
                # in-flight request so its stream resumes byte-identically.
                progressed = self._recover_from_fault(e)
            finally:
                self._step_hb = None
            # Auto-arm hook: a step whose wall time jumps past
            # ARKS_PROF_AUTO_ARM x the trailing median of the cycles (the
            # step clock's, the one the stall rule reads) opens a profiler
            # window by itself (closed after ARKS_PROF_WINDOW_S).  A step
            # that waited for a request is judged by nothing.
            prof.on_step(time.monotonic() - t0,
                         self.step_clock.last_median if progressed else None)
            if not progressed:
                time.sleep(0.001)

    # ------------------------------------------------------------------
    # Fault-isolated recovery
    # ------------------------------------------------------------------

    def _recover_from_fault(self, exc: Exception) -> bool:
        """Top-level fault handler: attempt quarantine + token-replay
        recovery, escalating to the blanket abort-everything path only
        when recovery itself keeps faulting (crash-loop guard)."""
        # What the last resolve held back was produced before the
        # fault and counts as emitted: the clients get it before any
        # error frame or replayed token.
        self._flush_deferred()
        self._set_state("recovering")
        self._recover_t0 = time.monotonic()
        attempts = max(self._fault_retries + 2, 3)
        for _ in range(attempts):
            try:
                self._do_recovery(exc)
                return True
            except Exception as e:  # routed back into _do_recovery
                exc = e
        log.error("recovery kept faulting after %d attempts; falling back "
                  "to abort-everything", attempts)
        self._blanket_abort(exc)
        return True

    def _do_recovery(self, exc: Exception) -> None:
        """One recovery round: attribute, quarantine culprits over budget,
        snapshot every other in-flight request, rebuild the device state,
        and re-admit the survivors (token-replay for streams that already
        emitted, plain re-queue for the rest)."""
        if isinstance(exc, StepFault):
            phase, kind = exc.phase, exc.kind
            culprits = set(exc.culprits)
            survivors: list[_Survivor] = list(exc.survivors)
            cause = exc.__cause__ or exc
        else:
            phase, kind = "step", faults_mod.classify(exc)
            culprits, survivors = set(), []
            cause = exc
        self._consec_faults += 1
        self.metrics.engine_faults_total.inc(1, phase=phase, kind=kind)
        log.error("engine fault in phase %r (kind=%s, culprits=%s, "
                  "consecutive=%d); recovering",
                  phase, kind, sorted(culprits) or "-", self._consec_faults,
                  exc_info=cause)
        # Flight recorder: snapshot the ring tail ONCE and pin it onto
        # every culprit's eventual trace; the fault dump ships its own
        # timeline.  (Recovery is a slow path — assembly is allowed here.)
        self.trace.evt("", "recover", "B", f"{phase}/{kind}")
        flight_tail = self.trace.tail()
        if flight_tail:
            log.error("flight recorder (last %d events): %s",
                      len(flight_tail), "; ".join(
                          f"{e['t']:.3f} {e['rid'] or '<engine>'} "
                          f"{e['name']}/{e['ph']}" for e in flight_tail))
        for rid in culprits:
            self._fault_counts[rid] = self._fault_counts.get(rid, 0) + 1
            self.trace.evt(rid, "fault", "I", f"{phase}/{kind}")
            self.trace.attach_tail(rid, flight_tail)
        if self._consec_faults > max(self._fault_retries + 1, 2):
            # Unattributed (or mis-attributed) fault storm: per-request
            # budgets cannot bound it — stop the crash loop.
            raise RuntimeError(
                f"{self._consec_faults} consecutive step faults") from cause

        # ---- snapshot every in-flight request --------------------------
        for st in self._slots.values():
            survivors.append(_Survivor(
                request=st.request, seed=st.seed, num_prompt=st.num_prompt,
                generated=list(st.generated), num_emitted=st.num_emitted,
                logprobs=list(st.logprobs),
                first_token_time=st.first_token_time))
        for cs in self._prefilling.values():
            # Mid-prefill sequences re-run from the top (nothing emitted);
            # a replaying one keeps its gate — _do_recovery's re-admit
            # detects it on the request and restarts the cursor.
            survivors.append(_Survivor(
                request=cs.request, seed=cs.seed, num_prompt=len(cs.ids)))
        for rec in self._pending_admits:
            for req, ids, _ in rec[0]:
                survivors.append(_Survivor(
                    request=req, seed=self._resolve_seed(req),
                    num_prompt=len(ids)))
        for rst in self._awaiting_restore:
            self.metrics.num_requests_waiting.inc(-1)
            if isinstance(rst, _ResumeState):
                # A mid-restore preempt resume replays like any decoding
                # survivor — its generated prefix re-executes behind the
                # gate (the safe backstop when the swap path itself may
                # be what faulted).
                survivors.append(self._swap_survivor(rst.rec))
            else:
                # Restore-parked requests emitted nothing: plain
                # re-queue.  The host tier SURVIVES the device reset, so
                # the re-run's admission hits tier 1 again instead of
                # re-prefilling.
                survivors.append(_Survivor(
                    request=rst.request, seed=rst.seed,
                    num_prompt=len(rst.ids)))
        self._awaiting_restore = []
        for fs in self._awaiting_fetch:
            # Fetch-parked requests emitted nothing and hold no pages:
            # plain re-queue.  The host tier survives the reset, so any
            # blocks the worker already staged still pay off on the
            # re-run's admission; a worker still mid-fetch harmlessly
            # finishes against the surviving tiers.
            self.metrics.num_requests_waiting.inc(-1)
            survivors.append(_Survivor(
                request=fs.request, seed=fs.seed,
                num_prompt=len(fs.ids)))
        self._awaiting_fetch = []
        # Preempted victims (spill in flight or parked in host RAM):
        # token-replay instead of trusting a snapshot that may share the
        # fault's poisoned stream.  Their SwapStore bytes come back.
        for sw in self._swap_pending:
            self.metrics.num_requests_waiting.inc(-1)
            survivors.append(self._swap_survivor(sw.rec))
        self._swap_pending = []
        for rid_sw, rec_sw in self._swapped.items():
            self.metrics.num_requests_waiting.inc(-1)
            if self._swap is not None:
                self._swap.discard(rid_sw)
            survivors.append(self._swap_survivor(rec_sw))
        self._swapped.clear()
        if self._swap is not None:
            self.metrics.prefix_cache_usage_bytes.set(
                self._swap.bytes_used, tier="swap")
        self._slots.clear()
        self._prefilling.clear()
        self._pending_admits.clear()
        self._pending_n = 0
        self.metrics.num_requests_running.set(0)

        # ---- quarantine / abort / keep ---------------------------------
        with self._abort_lock:
            aborted = set(self._aborted)
        keep: list[_Survivor] = []
        seen: set[str] = set()
        err = f"engine_fault: {phase}/{kind}"
        for sv in survivors:
            rid = sv.request.request_id
            if rid in seen:
                continue
            seen.add(rid)
            if rid in aborted:
                # Abort raced the fault: honor it instead of replaying.
                with self._abort_lock:
                    self._aborted.discard(rid)
                self._fail_survivor(sv, "abort", None)
                continue
            if self._fault_counts.get(rid, 0) > self._fault_retries:
                # The culprit fails ALONE: finish_reason="error" maps to
                # an OpenAI-style 500 at the HTTP layer.
                self.metrics.requests_quarantined_total.inc(1)
                with logctx.bound(rid):
                    log.warning("quarantining %s after %d faults (%s)", rid,
                                self._fault_counts[rid], err)
                self.trace.attach_tail(rid, flight_tail)
                self.trace.evt(rid, "quarantined", "I", err)
                self._fail_survivor(sv, "error", err)
                continue
            keep.append(sv)

        # ---- re-admit survivors ----------------------------------------
        # BEFORE the device reset: the admission queue is untouched by a
        # reset, so if the rebuild itself faults the survivors ride the
        # queue into the next recovery round instead of vanishing with
        # this frame's locals (clients blocked forever).  Nothing admits
        # until recovery returns, so ordering is otherwise free.
        replay_n = 0
        for sv in keep:
            req = sv.request
            rid = req.request_id
            gate = (req.outputs
                    if isinstance(req.outputs, _ReplayGate) else None)
            if sv.generated or gate is not None:
                # Token-replay resume by deterministic re-execution: wrap
                # (or restart) the emission gate, then re-run the request
                # through its ORIGINAL admission path with its pinned
                # seed — the same compiled programs that produced the
                # recorded stream reproduce it bitwise, the gate
                # suppresses the already-delivered prefix and verifies
                # every regenerated token.  Replayers jump the admission
                # queue: they were already decoding before the fault.
                if gate is None:
                    req.outputs = _ReplayGate(req.outputs, self, rid,
                                              sv.generated, sv.num_emitted)
                else:
                    gate.restart(sv.generated)
                self._replaying.add(rid)
                self.trace.evt(rid, "replay", "I", len(sv.generated))
                prio = req.params.priority - (1 << 20)
                replay_n += 1
            else:
                # Nothing emitted yet: plain re-queue (the pinned seed
                # makes the re-run byte-identical to a fault-free
                # admission).
                prio = req.params.priority
                self.metrics.requests_recovered_total.inc(1)
            with self._abort_lock:
                self._queued_rids.add(rid)
                self._queue_seq += 1
                seq = self._queue_seq
            self.metrics.num_requests_waiting.inc(1)
            self._queue.put((prio, seq, req))

        # ---- rebuild device state; tell followers ----------------------
        self._emit("recover", manifest=[
            (sv.request.request_id, sv.num_prompt, len(sv.generated))
            for sv in keep], phase=phase, kind=kind)
        self._reset_device_state()
        self.trace.evt("", "recover", "E")
        # Assemble NOW so quarantined timelines are retained even if the
        # process dies before the collector's next pass.
        self.trace.flush()
        if not replay_n:
            self._finish_recovery()

    def _phase_culprits(self, phase: str):
        """Blast-radius attribution for a phase-scoped fault: the requests
        the failing operation was doing work for.  Guide-table uploads
        serve no specific request — nobody's retry budget burns for one."""
        if phase in ("guide", "disk_spill"):
            # Guide-table uploads and tier-2 spill drains serve no
            # specific request — nobody's retry budget burns for one.
            return ()
        if phase == "peer_fetch":
            # Fetch faults are raised with the explicit fetching request
            # at every fire site; an unattributed one can only be the
            # park bookkeeping — blame the parked fetches, not the
            # decoding slots.
            return [st.request.request_id for st in self._awaiting_fetch]
        if phase == "model_switch":
            # The switch serves the requests parked for the target model;
            # nobody else was in flight (switches run fully drained).
            return [req.request_id for req, want, _ in self._awaiting_model
                    if want == self._switch_target]
        if phase == "resize":
            # A topology resize serves no specific request: it runs at a
            # fully drained boundary, and every in-flight stream was
            # already moved to the host (swap entry or replay requeue)
            # before the first seam — those survive a resize fault in
            # layout-independent form, so nobody's retry budget burns.
            return ()
        if phase == "preempt":
            # Preempt faults are raised with explicit single-victim
            # culprits at every fire site; an unattributed one can only
            # be host-side scheduling code — blame the in-flight swap
            # traffic, not the decoding slots.
            return ([sw.rec.request.request_id for sw in self._swap_pending]
                    + list(self._swapped)
                    + [r.request.request_id for r in self._awaiting_restore
                       if isinstance(r, _ResumeState)])
        if phase == "residency":
            # The span-streaming step only does work for ENGAGED slots —
            # co-resident classic-path slots never touch its dispatches.
            if self._residency is not None:
                return [self._slots[s].request.request_id
                        for s in self._residency.slots if s in self._slots]
            return ()
        rids = [st.request.request_id for st in self._slots.values()]
        if phase == "mixed":
            rids += [cs.request.request_id
                     for cs in self._prefilling.values()]
        return rids

    def _live_rids(self) -> set:
        """Request ids somewhere in the engine's in-flight structures
        (everything except the admission queue) — the abort-purge and
        replay-liveness universe."""
        live = {st.request.request_id for st in self._slots.values()}
        live |= {st.request.request_id for st in self._prefilling.values()}
        live |= {req.request_id for rec in self._pending_admits
                 for req, _, _ in rec[0]}
        live |= {req.request_id for req, _ in self._awaiting_guide}
        live |= {rec.request.request_id for rec in self._awaiting_restore}
        live |= {req.request_id for req, _, _ in self._awaiting_model}
        if self._pool_waiting is not None:
            live.add(self._pool_waiting[0].request_id)
        live |= {sw.rec.request.request_id for sw in self._swap_pending}
        live |= set(self._swapped)
        return live

    def _purge_stale_aborts(self, consumed=()) -> None:
        """Drop abort flags that no live request can ever consume.  Aborts
        for requests still waiting in the admission queue stay until
        _preadmit consumes them; anything else (request already finished,
        or never existed) is garbage — without this, an abort racing
        _finish would sit in the set forever (and the set could grow
        without bound under abort-heavy clients)."""
        active = self._live_rids()
        with self._abort_lock:
            self._aborted -= set(consumed)
            self._aborted &= active | self._queued_rids

    def _fail_survivor(self, sv: "_Survivor", reason: str,
                       error: str | None) -> None:
        self._unpin_guide(sv.request)
        self._fault_counts.pop(sv.request.request_id, None)
        self._deliver(sv.request, RequestOutput(
            request_id=sv.request.request_id, token_ids=[], finished=True,
            finish_reason=reason, error=error,
            num_prompt_tokens=sv.num_prompt,
            num_generated_tokens=len(sv.generated)))
        if reason == "error":
            self.metrics.request_success_total.inc(reason="error")

    def _finish_recovery(self) -> None:
        self.metrics.engine_recovery_seconds.observe(
            time.monotonic() - self._recover_t0)
        self._set_state("serving")
        log.info("recovery complete in %.3fs",
                 time.monotonic() - self._recover_t0)

    def _maybe_finish_recovery(self) -> None:
        """Close the recovery window once the last replaying request has
        re-registered into a decoding slot (or died on the way):
        engine_recovery_seconds measures fault -> every surviving stream
        decoding again."""
        if self._state != "recovering":
            return
        if self._replaying:
            # Drop replayers that went terminal without re-registering
            # (an abort or per-request rejection raced the re-run).
            live = self._live_rids()
            with self._abort_lock:
                live |= self._queued_rids
            self._replaying &= live
            if self._replaying:
                return
        self._finish_recovery()

    def _blanket_abort(self, exc: Exception) -> None:
        """Last-resort path (recovery crash loop): fail EVERY in-flight
        request and rebuild — the pre-recovery behavior, kept as the
        backstop so an unattributable fault storm cannot spin forever."""
        log.exception("engine step failed; aborting in-flight requests",
                      exc_info=exc)
        for slot in list(self._slots):
            self._finish(slot, "abort")
        for slot, st in list(self._prefilling.items()):
            self._unpin_guide(st.request)
            self._deliver(st.request, RequestOutput(
                request_id=st.request.request_id, token_ids=[],
                finished=True, finish_reason="abort",
                num_prompt_tokens=len(st.ids)))
        self._prefilling.clear()
        self._abort_pending_admits()
        self._abort_awaiting_restores()
        self._abort_awaiting_fetches()
        self._abort_awaiting_model()
        self._abort_pool_waiting()
        # Preempted victims fail too, and their SwapStore entries go with
        # them — swapped-out KV may carry the poison back on resume.
        self._abort_swapped()
        if self._prefix is not None:
            # Deep clean: cached prefix KV may itself be the poison.
            self._prefix.clear()
        if self._host is not None:
            # Same deep clean for the host tier: spilled blocks may carry
            # the poisoned KV back on the next restore.
            self._host.clear()
            self.metrics.prefix_cache_usage_bytes.set(0, tier="host")
        if self._disk is not None:
            # The disk tier goes with it — AND its files, or the poison
            # would resurrect on the next boot's directory scan.
            self._disk_spill_pending.clear()
            self._disk.clear()
            self.metrics.prefix_cache_usage_bytes.set(0, tier="disk")
        self._fault_counts.clear()
        self._consec_faults = 0
        self._reset_device_state()
        self._finish_recovery()

    def _reset_device_state(self) -> None:
        # Pipelined decode: in-flight records reference donated-away device
        # buffers; drop them rather than resolve (their requests were
        # already aborted by the fault path).
        self._pipe_reset()
        # In-flight spill gathers may share the fault's poisoned stream;
        # drop them (losing a spill costs one future re-prefill).  The
        # host tier itself SURVIVES the reset — that is the "warm across
        # restarts" property the tier exists for.
        self._spill_victims.clear()
        self._spills.clear()
        # In-flight preempt swaps reference the same stream; their
        # victims were snapshotted as replay survivors by _do_recovery
        # (or aborted by _blanket_abort) — drop the device refs.
        self._swap_pending = []
        # The rebuilt allocator starts with an EMPTY tier-0 index: move
        # the sketch epoch so routers drop the pre-reset sketch the
        # moment they next poll, instead of keeping this backend winning
        # placement on membership it no longer holds.
        if self._sketch is not None:
            self._sketch.bump_epoch()
        # Followers rebuild too (their _run path never sees the exception).
        if self.dispatcher is not None:
            self._emit("reset")
        dtype = jnp.dtype(self.ecfg.dtype or self.cfg.dtype)
        if self._paged:
            from arks_tpu.engine.paged import PageAllocator
            page = self._page_size()
            if self._win is not None:
                from arks_tpu.engine.paged import WindowPages
                w = self._win
                self._win = WindowPages(self.ecfg.num_slots, w.max_pages,
                                        page, w.window, w.per_slot)
            self._cache = self._init_paged_cache(self._alloc.num_pages,
                                                 dtype)
            if self.mesh is not None:
                self._cache = self._shard_paged(self._cache)
            self._alloc = PageAllocator(self._alloc.num_pages, page)
            if self._host is not None:
                self._alloc.on_evict = self._note_evicted
            self._tables[:] = 0
            self._slot_pages.clear()
            self._pool_reserved.clear()
            if self._residency is not None:
                # Windowed slots' host stores reference the pre-reset
                # stream; their requests token-replay from the top, so the
                # windowed state drops wholesale (the staging/tail pages
                # died with the rebuilt allocator).
                self._residency.slots.clear()
        else:
            self._cache = tf.init_cache(self.cfg, self.ecfg.num_slots,
                                        self.ecfg.max_cache_len,
                                        self._cache_dtype(dtype),
                                        quantized=self.ecfg.kv_quantized,
                                        pad_head=self._pad_head())
            if self.mesh is not None:
                self._cache = self._shard_cache(self._cache)
        self._sampling = sampler_mod.init_sampling_state(
            self.ecfg.num_slots, self.ecfg.seed,
            vocab_size=self.cfg.vocab_size)
        if self._draft_cfg is not None:
            self._draft_cache = tf.init_cache(
                self._draft_cfg, self.ecfg.num_slots, self.ecfg.max_cache_len,
                self._cache_dtype(dtype), quantized=self.ecfg.kv_quantized,
                pad_head=self._pad_head())
            if self.mesh is not None:
                self._draft_cache = tf.shard_cache(
                    self._draft_cache, self._draft_cfg, self.mesh)
        # Paged: park every slot at the sentinel.  Slot layout: empty
        # slots start at 0 (their pre-insert garbage rows are private).
        self._lengths[:] = self._park_sentinel() if self._paged else 0
        self._last_token[:] = 0
        # A fault between _free.pop() and slot registration would otherwise
        # leak the slot index permanently.
        self._free = [s for s in range(self.ecfg.num_slots)
                      if s not in self._slots]

    def step(self, block_s: float = 0.05) -> bool:
        """One scheduler iteration: issue ONE decode dispatch (async),
        admit pending requests and advance at most one prefill chunk WHILE
        it computes, then fan the decode results out.  The overlap hides
        admission host work (numpy packing, digests, page allocation, the
        dispatch-issue latency) behind decode compute; device work still
        executes in issue order on the stream.  The chunk/decode interleave
        bounds how long a long-prompt burst can stall decoding slots: one
        chunk dispatch, not one whole prefill.  Returns True if any work
        was done.

        Speculative engines ride the mixed branch like any other mixed
        engine — their dispatch is the spec-mixed program (draft propose +
        ragged verify + accept), issued async and resolved after the
        overlapped admission work exactly like a plain mixed dispatch.
        Phase-seconds note: with the overlap, waits on the shared device
        stream land in whichever phase fetches first — the breakdown
        attributes WALL time, not device time."""
        t0 = time.monotonic()
        # ``phase.step.head``: everything of a step before its dispatch
        # (recovery, elastic, guide, park and swap servicing, the pipeline
        # checks), so that host time there has a name in a traced slice.
        sec = self.profiler.sections
        if sec:
            self.trace.evt("", "phase.step.head", "B")
        self._maybe_finish_recovery()
        if not self._armed:
            # Scaled to zero: no device state exists — the only work is
            # re-arming on demand (a queue arrival or a posted resize).
            if sec:
                self.trace.evt("", "phase.step.head", "E")
            return self._step_disarmed(block_s)
        worked = False
        if self._resize_req is not None or self._idle_zero_s:
            # Elastic servicing: progress a posted resize's drain ->
            # reshard -> resume machine, or scale a long-idle engine to
            # zero.  Cheap no-op when neither condition holds.
            worked = self._service_elastic()
            if not self._armed:
                # This step scaled the engine to zero; nothing below may
                # touch the dropped device state.
                if sec:
                    self.trace.evt("", "phase.step.head", "E")
                return True
            te = time.monotonic()
            if te - t0 > 1e-4:
                self.metrics.scheduler_seconds_total.inc(te - t0,
                                                         phase="elastic")
                t0 = te
        self._ensure_guides_uploaded()
        if self._awaiting_guide:
            # Requests parked on a worker-pool guide compile: re-queue the
            # ones whose guide published, fail the ones whose compile
            # failed, keep waiting on the rest.  Never blocks — a step
            # with only parked requests falls through to the idle sleep.
            worked = self._service_awaiting_guides() or worked
            tg = time.monotonic()
            self.metrics.scheduler_seconds_total.inc(tg - t0,
                                                     phase="guide_wait")
            t0 = tg
        if self._awaiting_model or self._model_loads or self._model_prefetch:
            # Multi-model park servicing: kick/poll the next model's
            # background weight load, fail/abort dead parked requests, and
            # switch contexts once the target is resident AND the engine
            # is fully drained.  Cheap and non-blocking — while the load
            # is in flight the RESIDENT model keeps pipelining at full
            # depth (the fast path below still runs every step).
            worked = self._issue_model_load() or worked
            tm = time.monotonic()
            self.metrics.scheduler_seconds_total.inc(tm - t0,
                                                     phase="model_wait")
            t0 = tm
        if self._pipe_ready():
            # Steady-state pipelined decoding: exactly ONE dispatch issued
            # per iteration, up to ARKS_PIPELINE_DEPTH in flight; the
            # oldest resolves (lagged host view) only once the pipeline is
            # full, so the device never waits on Python between
            # dispatches.
            if sec:
                self.trace.evt("", "phase.step.head", "E")
            self._step_pipelined()
            self.metrics.scheduler_seconds_total.inc(
                time.monotonic() - t0, phase="decode")
            return True
        if self._pipe_inflight or self._pipe_state is not None:
            # Leaving steady state (admission possible, abort raised,
            # prefill work, or a slot's stop set outgrew the device
            # column): resolve every in-flight dispatch so the host
            # mirrors are authoritative again before any host-side
            # mutation touches scheduler state.
            self._pipe_drain()
            worked = True
            td = time.monotonic()
            self.metrics.scheduler_seconds_total.inc(td - t0, phase="decode")
            t0 = td
        if self._residency_active():
            # Windowed-residency slots: span-by-span decode on the host
            # loop (cold pages stream through staging while resident
            # spans attend).  Runs before the classic mixed dispatch so
            # windowed slots never enter its lanes.  Its forward is a
            # host-sync round trip of its own: deliver first.
            self._flush_deferred()
            worked = self._residency_step() or worked
            tw = time.monotonic()
            self.metrics.scheduler_seconds_total.inc(tw - t0,
                                                     phase="residency")
            t0 = tw
        if self._awaiting_restore:
            # Host-tier restores whose scatter landed unpark into the
            # chunked-tail path (needs authoritative mirrors — the
            # pipeline drained above); in-flight ones stay parked.
            worked = self._resolve_restores() or worked
            tr = time.monotonic()
            self.metrics.scheduler_seconds_total.inc(tr - t0,
                                                     phase="restore")
            t0 = tr
        if self._awaiting_fetch:
            # Disk/peer fetch parks whose worker finished re-enter the
            # admission match; in-flight ones stay parked (the worker
            # thread owns them — the step loop never blocks on IO).
            worked = self._resolve_fetches() or worked
            tq = time.monotonic()
            self.metrics.scheduler_seconds_total.inc(tq - t0,
                                                     phase="fetch")
            t0 = tq
        if self._spills:
            worked = self._resolve_spills() or worked
        if self._disk_spill_pending:
            worked = self._drain_disk_spills() or worked
        if self._swap_pending or self._swapped or self._preempt_on:
            # Preemptive KV swap: harvest landed victim spills into the
            # SwapStore, serve aborts / schedule resumes for swapped-out
            # victims, then seize slots for outranking queued requests —
            # all BEFORE the issue block, so a freed slot admits (and a
            # resumed scatter dispatches) in this same step.
            tp = time.monotonic()
            self._queue_age_tick()
            if self._swap_pending:
                worked = self._resolve_preempt_swaps() or worked
            # During a resize drain, swapped victims stay parked (resuming
            # one would fight the eviction) and natural preemption pauses;
            # both resume at the new shape.
            if self._swapped and not self._resize_active:
                worked = self._service_swapped() or worked
            if not self._resize_active:
                worked = self._maybe_preempt() or worked
            dt = time.monotonic() - tp
            if dt > 1e-4:
                self.metrics.scheduler_seconds_total.inc(dt, phase="preempt")
        elif self._queue_aging_s:
            self._queue_age_tick()
        if sec:
            self.trace.evt("", "phase.step.head", "E")
        pending = None
        issued = False
        if self._mixed:
            # Mixed scheduling: ONE model dispatch per iteration carries
            # every decoding slot's next token AND all prefilling
            # sequences' chunk tokens — admission host work overlaps the
            # in-flight dispatch exactly as in the legacy issue/resolve
            # split.
            spec = self._draft_cfg is not None
            phase = "spec" if spec else "mixed"
            if self._slots or self._prefilling:
                pending = (self._issue_spec_mixed() if spec
                           else self._issue_mixed())
                issued = pending is not None
            # A deferral leaves right behind the dispatch, inside the
            # issue; a step that issued nothing delivers it here, so none
            # outlives the step after the resolve that opened it.
            self._flush_deferred()
            t1 = time.monotonic()
            if issued:
                self.metrics.scheduler_seconds_total.inc(t1 - t0,
                                                         phase=phase)
            worked = self._admit_section() or worked or issued
            t2 = time.monotonic()
            if t2 - t1 > 1e-4:
                self.metrics.scheduler_seconds_total.inc(t2 - t1,
                                                         phase="admit")
            if pending is not None:
                if spec:
                    self._resolve_spec_mixed(pending, exclude_s=t2 - t1)
                else:
                    self._resolve_mixed(pending, exclude_s=t2 - t1)
                self.metrics.scheduler_seconds_total.inc(
                    time.monotonic() - t2, phase=phase)
        else:
            if self._slots and self._overlap:
                pending = self._issue_decode()  # may retire/abort even if None
                issued = True
            # A legacy engine defers at its pipeline's last resolve only.
            self._flush_deferred()
            t1 = time.monotonic()
            if issued:
                self.metrics.scheduler_seconds_total.inc(t1 - t0, phase="decode")
            worked = self._admit_section() or worked or issued
            t2 = time.monotonic()
            if t2 - t1 > 1e-4:
                self.metrics.scheduler_seconds_total.inc(t2 - t1, phase="admit")
            if self._prefilling:
                self._process_chunk()
                t3 = time.monotonic()
                self.metrics.scheduler_seconds_total.inc(t3 - t2, phase="chunk")
                t2 = t3
                worked = True
            if pending is not None:
                self._resolve_decode(pending, exclude_s=t2 - t1)
                self.metrics.scheduler_seconds_total.inc(
                    time.monotonic() - t2, phase="decode")
            elif self._slots and not self._overlap:
                # Sequential order: platforms where the overlap cannot pay
                # (see _overlap above).
                self._decode_dispatch()
                self.metrics.scheduler_seconds_total.inc(
                    time.monotonic() - t2, phase="decode")
                worked = True
        # ``phase.step.tail``: what is left of a step after its resolve (the
        # deferred admissions below have phase.admit inside it).
        if sec:
            self.trace.evt("", "phase.step.tail", "B")
        try:
            if self._pending_admits:
                # Deferred admissions: resolve whatever the device finished
                # while this step ran (the decode resolve above usually means
                # earlier admit programs are done too).  When nothing else
                # made progress, BLOCK on the oldest — a pending admission
                # must never starve behind an empty queue.
                t4 = time.monotonic()
                if sec:
                    self.trace.evt("", "phase.admit", "B")
                try:
                    worked = (self._drain_ready_admits(force_one=not worked)
                              or worked)
                finally:
                    if sec:
                        self.trace.evt("", "phase.admit", "E", 0)
                self.metrics.scheduler_seconds_total.inc(
                    time.monotonic() - t4, phase="admit")
            if not worked and (self._awaiting_restore or self._spills
                               or self._awaiting_fetch
                               or self._disk_spill_pending
                               or self._swap_pending or self._swapped
                               or self._awaiting_model or self._model_loads
                               or self._resize_req is not None):
                # Parked restores / in-flight spills / pending model loads
                # resolve on DEVICE (or loader-thread) time, not queue
                # arrivals: poll again shortly instead of blocking on the
                # admission queue for block_s.
                time.sleep(0.001)
                return True
            if not worked:
                # Idle housekeeping: an abort that raced _finish (or targeted
                # a request that never existed) must not linger in the set
                # forever — the busy-path purges only run while slots exist.
                self._purge_stale_aborts()
                # Idle: wait briefly for a request, then try admission again.
                # The time belongs to no leg of any cycle.
                self.step_clock.idle()
                try:
                    _, _, req = self._queue.get(timeout=block_s)
                except queue.Empty:
                    return False
                pre = self._preadmit(req)
                if pre is not None:
                    self._resolve_admit_batch(
                        self._issue_admit_batch([pre], pre[0].params.logprobs
                                                is not None))
            return True
        finally:
            if sec:
                self.trace.evt("", "phase.step.tail", "E")

    @staticmethod
    def _admit_batch_sizes() -> tuple[int, ...]:
        """Admission batch sizes (largest-first greedy fill).  Each size is
        one compiled program per (bucket, lp); the cap keeps variants
        bounded.  ARKS_ADMIT_BATCH_SIZES overrides (comma-separated) so
        the serving sweep can probe bigger fills (e.g. "16,8,4,2,1" — at
        b192 with ~24 finishes per dispatch cycle, deeper batches may
        amortize more of the per-dispatch round-trip) without a code
        change.  Normalized descending; 1 is always present (the greedy
        fill's floor)."""
        raw = knobs.raw("ARKS_ADMIT_BATCH_SIZES") or "8,4,2,1"
        try:
            sizes = {int(x) for x in raw.split(",") if x.strip()}
        except ValueError as e:
            raise ValueError(
                f"ARKS_ADMIT_BATCH_SIZES={raw!r}: expected comma-separated "
                "integers (e.g. \"16,8,4,2,1\")") from e
        if any(s < 1 for s in sizes):
            raise ValueError(
                f"ARKS_ADMIT_BATCH_SIZES={raw!r}: sizes must be >= 1")
        return tuple(sorted(sizes | {1}, reverse=True))

    def _admit_section(self) -> bool:
        """``_admit()`` as the step loop calls it: inside a profiler
        window it is the ``phase.admit`` section (arg: requests popped)."""
        if not self.profiler.sections:
            return self._admit()
        n0 = self._admit_popped
        self.trace.evt("", "phase.admit", "B")
        try:
            return self._admit()
        finally:
            self.trace.evt("", "phase.admit", "E", self._admit_popped - n0)

    def _admit(self) -> bool:
        """Admit waiting requests.  One-shot prompts are GROUPED by
        (prefill bucket, logprobs) and issued as fused batch dispatches —
        all batches go out back-to-back (async); first tokens are fetched
        DEFERRED (self._pending_admits, resolved by step() as they become
        ready) so the engine thread never blocks on an admit program's
        device round-trip while decode work is available."""
        if self._resize_active:
            # Resize drain: new admissions wait in the queue until the
            # engine resumes at its new shape.
            return False
        admitted = False
        groups: dict[tuple[int, bool], list] = {}
        recs = []
        try:
            # The grouping loop sits INSIDE the try: _preadmit can re-raise
            # after failing only its own request (_admit_prefilled dispatch
            # error, _start_chunked page-alloc failure), and any one-shot
            # requests already collected in ``groups`` hold no slot and are
            # invisible to _run's recovery — the handler below must abort
            # them or their clients block forever.
            while self._free and (self._queue.qsize() > 0
                                  or self._pool_waiting is not None):
                if self._pool_waiting is not None:
                    # The head of the queue waits for pages of the full
                    # pool; nothing overtakes it (_pool_wait).
                    if not self._pool_admit_waiting():
                        break
                    admitted = True
                    continue
                n_grouped = sum(len(v) for v in groups.values())
                if n_grouped >= len(self._free):
                    break
                try:
                    _, _, req = self._queue.get_nowait()
                except queue.Empty:
                    break
                try:
                    # Chaos hook at the WDRR pick point: the popped
                    # request is the sole culprit (its retry budget
                    # burns; over budget it quarantines alone) AND a
                    # survivor (nothing was emitted — recovery plain-
                    # requeues it through the fair queue again).
                    self._faults.fire("admit_fair")
                except Exception as e:
                    self.metrics.num_requests_waiting.inc(-1)
                    with self._abort_lock:
                        self._queued_rids.discard(req.request_id)
                    raise StepFault(
                        "admit_fair", faults_mod.classify(e),
                        culprits=[req.request_id],
                        survivors=[_Survivor(
                            request=req, seed=self._resolve_seed(req),
                            num_prompt=len(req.prompt_ids))]) from e
                admitted = True
                self._admit_popped += 1
                pre = self._preadmit(req)
                if pre is not None:
                    req, ids, padded = pre
                    key = (padded.shape[1], req.params.logprobs is not None)
                    groups.setdefault(key, []).append(pre)
            for (bucket, want_lp), items in groups.items():
                while items:
                    m = next(s for s in self._admit_sizes
                             if s <= len(items))
                    # Detach BEFORE issuing: _issue_admit_batch fails its
                    # own items on error, and the handler below must not
                    # abort them a second time.
                    batch = items[:m]
                    del items[:m]
                    recs.append(self._issue_admit_batch(batch, want_lp))
            if self._defer_admits:
                # Hand the issued batches to the deferred queue; step()
                # resolves them as their first tokens become ready, so the
                # engine thread goes back to issuing decode dispatches
                # instead of blocking here.  (Anything already computed
                # resolves immediately — the no-load TTFT path.)
                self._pending_n += sum(len(r[0]) for r in recs)
                self._pending_admits.extend(recs)
                recs = []
                self._drain_ready_admits()
            else:
                while recs:
                    self._resolve_admit_batch(recs.pop(0))
        except Exception as e:
            # A failing batch must not strand its SIBLINGS: un-issued items
            # and unresolved already-issued batches hold no registered slot
            # (invisible to the recovery snapshot) — carry them as
            # survivors on the StepFault so recovery re-queues them.  (The
            # failing operation's own requests ride its inner StepFault.)
            survivors = []
            for sib_items in groups.values():
                for req, ids, _ in sib_items:
                    survivors.append(_Survivor(
                        request=req, seed=self._resolve_seed(req),
                        num_prompt=len(ids)))
            for rec in recs:
                for (req, ids, _), slot in zip(rec[0], rec[1]):
                    if slot not in self._slots:
                        self._free.append(slot)
                    survivors.append(_Survivor(
                        request=req, seed=self._resolve_seed(req),
                        num_prompt=len(ids)))
            if isinstance(e, StepFault):
                e.survivors.extend(survivors)
                raise
            raise StepFault("admit", faults_mod.classify(e),
                            survivors=survivors) from e
        return admitted

    def _drain_ready_admits(self, force_one: bool = False) -> bool:
        """Resolve deferred admission batches whose first tokens are ready
        (FIFO — emission order matches issue order).  ``force_one`` blocks
        on the oldest batch even if unready: the idle path uses it so a
        pending admission can never starve behind an empty queue.  Returns
        True if anything resolved."""
        did = False
        while self._pending_admits:
            rec = self._pending_admits[0]
            if not (force_one and not did) and not rec[2].is_ready():
                break
            self._pending_admits.popleft()
            self._pending_n -= len(rec[0])
            self._resolve_admit_batch(rec)
            did = True
        return did

    def _abort_pending_admits(self) -> None:
        """Fail every deferred admission batch (fault/stop paths): their
        requests hold popped slots but are registered nowhere, so no other
        recovery can reach them."""
        while self._pending_admits:
            items, slots_l = self._pending_admits.popleft()[:2]
            self._pending_n -= len(items)
            for (req, ids, _), slot in zip(items, slots_l):
                if slot not in self._slots:
                    self._release_slot_pages(slot)
                    self._free.append(slot)
                self._unpin_guide(req)
                self._deliver(req, RequestOutput(
                    request_id=req.request_id, token_ids=[], finished=True,
                    finish_reason="abort", num_prompt_tokens=len(ids)))

    def _resolve_seed(self, req: Request) -> int:
        """The request's sampling seed, assigned ONCE per request: an
        explicit params.seed wins; otherwise the engine counter value is
        pinned on the request (fault recovery re-admits with the identical
        key stream instead of drawing a fresh counter value)."""
        if req.params.seed is not None:
            return req.params.seed
        if req.assigned_seed is None:
            self._request_seed += 1
            req.assigned_seed = self._request_seed
        return req.assigned_seed

    def _preadmit(self, req: Request):
        """Admission front half: aborts, disagg-transferred KV, rejects,
        and the chunked/prefix paths are handled HERE (individually);
        one-shot prompts return (req, ids, padded) for batch grouping."""
        self.metrics.num_requests_waiting.inc(-1)
        self.metrics.admission_queue_depth.set(self._queue.qsize())
        with self._abort_lock:
            self._queued_rids.discard(req.request_id)
            if req.request_id in self._aborted:
                self._aborted.discard(req.request_id)
                self._unpin_guide(req)
                self._deliver(req, RequestOutput(
                    request_id=req.request_id, token_ids=[], finished=True,
                    finish_reason="abort"))
                return
        if self._shed_due(req):
            # Deadline-aware shedding: the queue wait already burned the
            # tier's whole TTFT budget (x ARKS_SHED_DEADLINE) — prefill
            # would be wasted on a stream the client has written off.
            # Reject with a machine-readable code; the server maps it to
            # 503 + Retry-After.  Exempt: replayers/swap-resumes (already
            # decoding before their fault/preemption — shedding them
            # breaks the byte-identity contract) and disagg-prefilled
            # requests (the expensive half is already paid for).
            waited = time.monotonic() - req.arrival_time
            tier = self._slo.tier_of(req.params.priority)
            self._unpin_guide(req)
            self.metrics.requests_shed_total.inc(
                1, reason="deadline", tier=tier,
                tenant=self._tenant_labels.label(req.tenant))
            self.trace.evt(req.request_id, "shed", "I", round(waited, 3))
            self._deliver(req, RequestOutput(
                request_id=req.request_id, token_ids=[], finished=True,
                finish_reason="error",
                error=(f"shed_deadline: queued {waited:.2f}s, tier "
                       f"{tier} ttft budget already unmeetable"),
                num_prompt_tokens=len(req.prompt_ids)))
            return
        if isinstance(req.outputs, _ReplayGate):
            # Fault-recovery re-admission: a per-request injectable point
            # ("replay" phase) so the chaos suite can kill one survivor's
            # resume specifically — the StepFault attributes the fault to
            # THIS request alone and carries its replay state.
            try:
                self._faults.fire("replay")
            except Exception as e:
                raise StepFault(
                    "replay", faults_mod.classify(e),
                    culprits=[req.request_id],
                    survivors=[_Survivor(
                        request=req, seed=self._resolve_seed(req),
                        num_prompt=len(req.prompt_ids),
                        generated=list(req.outputs.expect),
                        num_emitted=req.outputs.client_total)]) from e
        want = getattr(req, "model", None) or self._primary_model
        if want != self.cfg.name or (self._switch_target is not None
                                     and self._switch_target != self.cfg.name):
            # Multi-model routing: the request targets a pool model that is
            # not active — or a switch away from the active model is
            # already committed, in which case even active-model requests
            # park (admitting them would keep the drain from converging).
            # Parked BEFORE the guide gate: guide registries are per-model
            # context, so a pin taken here would reference the wrong
            # model's tables after the switch.
            return self._park_awaiting_model(req, want)
        if req.params.guide is not None:
            # Cold-guide gate: park the request while its guide compiles
            # on the worker pool (the scheduler never blocks on
            # compilation); fail it on compile error; PIN the published
            # guide for the request's lifetime so eviction can't repack
            # the rows its slot decodes against.
            gate = self._gate_guide(req)
            if gate == "park":
                return
            if gate is not None:
                self._deliver(req, RequestOutput(
                    request_id=req.request_id, token_ids=[], finished=True,
                    finish_reason="error",
                    error=f"guide_compile_failed: {gate}",
                    num_prompt_tokens=len(req.prompt_ids)))
                log.info("rejected %s: guide compile failed: %s",
                         req.request_id, gate)
                return
        if req.prefilled is not None:
            return self._admit_prefilled(req)
        try:
            ids, padded = self._prepare_prompt(req.prompt_ids)
        except ContextLengthExceededError as e:
            self._unpin_guide(req)
            self._deliver(req, RequestOutput(
                request_id=req.request_id, token_ids=[], finished=True,
                finish_reason="error", error="context_length_exceeded",
                num_prompt_tokens=len(req.prompt_ids)))
            log.info("rejected %s: %s", req.request_id, e)
            return

        # Prefix reuse.  Paged layout: the allocator's digest index maps
        # shared prefixes to pages already ON DEVICE — the new slot's table
        # points at them (zero copies, works on multi-host gangs since the
        # pages travel as dispatch args) and only the tail is chunk-
        # prefilled.  Slot layout: host-resident blocks are re-uploaded
        # (single-host only).  At least one tail token is always computed —
        # its logits feed first-token sampling.
        if self._paged and self._chunk:
            from arks_tpu.engine.paged import chain_digests
            page = self._page_size()
            nfull = (len(ids) - 1) // page
            digests = chain_digests(ids, page, nfull) if nfull else []
            shared = self._alloc.match(digests)
            plen = len(shared) * page
            # Tier 1: blocks beyond the device hit that survive in host
            # RAM (spilled on eviction, or published by a disagg prefill
            # peer) — restored asynchronously instead of re-prefilled.
            host_blocks: list = []
            if self._host_tier_on() and len(shared) < nfull:
                host_blocks = self._host.match_blocks(digests, len(shared))
            hlen = len(host_blocks) * page
            self._alloc.record_query(len(ids), plen + hlen)
            self.metrics.prefix_cache_query_tokens_total.inc(len(ids))
            if plen:
                self.metrics.prefix_cache_hit_tokens_total.inc(
                    plen, tier="device")
            if hlen:
                self.metrics.prefix_cache_hit_tokens_total.inc(
                    hlen, tier="host")
            self.metrics.prefix_cache_hit_rate.set(self._alloc.hit_rate)
            covered = len(shared) + len(host_blocks)
            if covered < nfull and self._fetch_candidate(req, digests,
                                                         covered):
                # Tier 2 / fleet: the uncovered span exists on local
                # disk or (per the router's hint) on a peer replica —
                # park for an async fetch into the host tier instead of
                # re-prefilling it.  Shared device refs are RELEASED
                # across the park (the resolve re-matches from scratch),
                # so no page bookkeeping outlives this frame.
                self._alloc.decref(shared)
                return self._issue_fetch(req, ids, digests, covered)
            if host_blocks:
                return self._issue_restore(req, ids, digests, shared,
                                           host_blocks)
            if plen:
                return self._start_chunked(req, ids, prefix_len=plen,
                                           prefix_pages=shared,
                                           digests=digests)
        elif self._prefix is not None and self.dispatcher is None:
            plen = min(self._prefix.match(ids),
                       (len(ids) - 1) // self._chunk * self._chunk)
            self._prefix.record_query(len(ids), plen)
            self.metrics.prefix_cache_query_tokens_total.inc(len(ids))
            self.metrics.prefix_cache_hit_tokens_total.inc(plen, tier="host")
            self.metrics.prefix_cache_hit_rate.set(self._prefix.hit_rate)
            if plen:
                return self._start_chunked(req, ids, prefix_len=plen)

        if padded is None or self._mixed:
            # Mixed scheduling: EVERY prompt rides the chunked path — its
            # tokens reach the model through mixed dispatches, so the
            # bucketed one-shot admit programs never compile (the variant
            # family collapses to one budget-shaped program).
            if self._pool_budget and not self._pool_fits(req, ids):
                return self._pool_wait(req, ids)
            return self._start_chunked(req, ids)

        return (req, ids, padded)

    def _issue_admit_batch(self, items: list, want_lp: bool):
        """Issue ONE fused dispatch admitting ``len(items)`` one-shot
        prompts (same bucket).  Returns the pending record for
        _resolve_admit_batch."""
        # Guides compile on SERVER threads: a request added after this
        # step's top-of-loop table refresh would otherwise run its admit
        # with the pre-compile tables (everything masked -> instant eos).
        self._ensure_guides_uploaded()
        m = len(items)
        page = self._page_size() if self._paged else 0
        tokens = np.concatenate([padded for _, _, padded in items], axis=0)
        lengths = np.asarray([len(ids) for _, ids, _ in items], np.int32)
        slots_l, seeds, keys = [], [], []
        pages_rows = np.zeros((m, self._max_pages or 1), np.int32)
        n_pages = np.zeros((m,), np.int32)
        params_cols = {f: np.zeros((m,), np.float32)
                       for f in ("temperature", "top_p", "presence", "frequency")}
        top_ks = np.zeros((m,), np.int32)
        bias_ids = np.full((m, sampler_mod.LOGIT_BIAS_MAX), -1, np.int32)
        bias_vals = np.zeros((m, sampler_mod.LOGIT_BIAS_MAX), np.float32)
        sup_ids = np.full((m, sampler_mod.SUPPRESS_MAX), -1, np.int32)
        min_first = np.zeros((m,), np.int32)
        min_until = np.zeros((m,), np.int32)
        guide_col = np.full((m,), -1, np.int32)
        guide_row_col = np.zeros((m,), np.int32)
        try:
            self._faults.fire("admit")
            for i, (req, ids, _) in enumerate(items):
                p = req.params
                seed = self._resolve_seed(req)
                seeds.append(seed)
                keys.append(sampler_mod.np_prng_key(seed))
                slot = self._free.pop()
                slots_l.append(slot)
                # Park the slot at the write-drop sentinel until its
                # registration: with deferred resolution, decode dispatches
                # can land between this admit program (which inserts the
                # prompt KV) and _register_slot — a stale length here would
                # let those dispatches overwrite the inserted rows.
                self._lengths[slot] = self._park_sentinel()
                if self._paged:
                    n_alloc = -(-len(ids) // page)
                    pages_rows[i] = self._assign_slot_pages(slot, n_alloc)
                    n_pages[i] = n_alloc
                params_cols["temperature"][i] = p.temperature
                params_cols["top_p"][i] = p.top_p
                params_cols["presence"][i] = p.presence_penalty
                params_cols["frequency"][i] = p.frequency_penalty
                top_ks[i] = p.top_k
                if p.logit_bias or p.min_tokens:
                    (bias_ids[i], bias_vals[i], sup_ids[i], min_first[i],
                     min_until[i]) = self._shape_cols(p, len(ids))
                guide_col[i], guide_row_col[i] = self._guide_cols(p)
            slots = np.asarray(slots_l, np.int32)
            self._emit("admit_batch_lp" if want_lp else "admit_batch",
                       tokens=tokens, lengths=lengths, slots=slots,
                       pages=pages_rows if self._paged else None,
                       n_pages=n_pages if self._paged else None,
                       seeds=list(seeds),
                       temperature=params_cols["temperature"],
                       top_p=params_cols["top_p"], top_k=top_ks,
                       presence=params_cols["presence"],
                       frequency=params_cols["frequency"],
                       bias_ids=bias_ids, bias_vals=bias_vals,
                       sup_ids=sup_ids, min_first=min_first,
                       min_until=min_until, guide=guide_col,
                       guide_row=guide_row_col)
            args = (self.params, self._cache, self._sampling,
                    jnp.asarray(tokens), jnp.asarray(lengths),
                    jnp.asarray(slots),
                    jnp.asarray(pages_rows) if self._paged else None,
                    jnp.asarray(n_pages) if self._paged else None,
                    jnp.asarray(params_cols["temperature"]),
                    jnp.asarray(params_cols["top_p"]),
                    jnp.asarray(top_ks),
                    jnp.asarray(np.stack(keys)),
                    jnp.asarray(params_cols["presence"]),
                    jnp.asarray(params_cols["frequency"]),
                    jnp.asarray(bias_ids), jnp.asarray(bias_vals),
                    jnp.asarray(sup_ids), jnp.asarray(min_first),
                    jnp.asarray(min_until), jnp.asarray(guide_col),
                    jnp.asarray(guide_row_col), self._guide_dev)
            if want_lp:
                (first_ids, clps, valss, lidss, self._cache, self._sampling,
                 ks, vs) = self._admit_lp_fn(*args)
                lp_out = (clps, valss, lidss)
            else:
                first_ids, self._cache, self._sampling, ks, vs = \
                    self._admit_fn(*args)
                lp_out = None
        except Exception as e:
            # None of the requests holds a REGISTERED slot yet, so _run's
            # recovery snapshot can't see them — carry them as survivors
            # on the StepFault (they re-queue with their pinned seeds) or
            # their clients block forever.  (Slot and page bookkeeping are
            # rebuilt by the recovery reset.)
            survivors = [_Survivor(request=req, seed=self._resolve_seed(req),
                                   num_prompt=len(ids))
                         for req, ids, _ in items]
            if isinstance(e, StepFault):
                e.survivors.extend(survivors)
                raise
            raise StepFault(
                "admit", faults_mod.classify(e),
                culprits=[req.request_id for req, _, _ in items],
                survivors=survivors) from e
        # Only the slot-layout single-prompt prefix harvest reads ks/vs at
        # resolve; everywhere else, keeping them in the record would pin
        # the batch's full prompt KV in HBM for the deferral window.
        if self._paged or self._prefix is None or m > 1:
            ks = vs = None
        for req, ids, _ in items:
            self.trace.evt(req.request_id, "queue", "E")
            self.trace.evt(req.request_id, "prefill", "B", len(ids))
        return (items, slots_l, first_ids, lp_out, ks, vs)

    def _resolve_admit_batch(self, rec) -> None:
        """Host-sync tail of a fused admission batch: fetch the first
        tokens, register the slots, emit, and harvest prefixes."""
        items, slots_l, first_ids, lp_out, ks, vs = rec
        try:
            self._faults.fire("admit_resolve")
            firsts = np.asarray(first_ids).tolist()  # device round-trip
            if lp_out is not None:
                clps = np.asarray(lp_out[0])
                valss = np.asarray(lp_out[1])
                lidss = np.asarray(lp_out[2])
        except Exception as e:
            # Dispatch failed asynchronously; the requests hold slots the
            # recovery snapshot will not see (not registered) — carry them
            # as survivors so they re-queue with their pinned seeds.
            for (req, ids, _), slot in zip(items, slots_l):
                if slot not in self._slots:
                    self._free.append(slot)
            raise StepFault(
                "admit_resolve", faults_mod.classify(e),
                culprits=[req.request_id for req, _, _ in items],
                survivors=[_Survivor(request=req,
                                     seed=self._resolve_seed(req),
                                     num_prompt=len(ids))
                           for req, ids, _ in items]) from e
        for i, ((req, ids, _), slot) in enumerate(zip(items, slots_l)):
            # Aborts raised between issue and this (deferred) resolve:
            # honor them here instead of registering a dead slot for one
            # more dispatch cycle.
            with self._abort_lock:
                was_aborted = req.request_id in self._aborted
                self._aborted.discard(req.request_id)
            if was_aborted:
                self._release_slot_pages(slot)
                self._free.append(slot)
                self._unpin_guide(req)
                # The admit program already wrote this slot's shaping rows.
                self._clear_shaping(slot, req.params)
                self._deliver(req, RequestOutput(
                    request_id=req.request_id, token_ids=[], finished=True,
                    finish_reason="abort", num_prompt_tokens=len(ids)))
                continue
            first_lp = None
            if lp_out is not None and req.params.logprobs is not None:
                first_lp = self._lp_entry(clps[i], valss[i], lidss[i],
                                          req.params.logprobs)
            self._register_slot(req, slot, firsts[i], len(ids),
                                first_lp=first_lp,
                                seed=self._resolve_seed(req))
            if self._paged and self._chunk:
                # Zero-cost harvest: the prompt's full pages are already in
                # the pool — register their digests so later prompts share
                # them on device.  (Only pages entirely covered by the
                # prompt: decode writes start at position len(ids).)
                self._register_prompt_pages(ids,
                                            self._slot_pages.get(slot, []))
            # Slot layout: harvest into the host prefix cache — but NOT
            # under admission pressure: the device->host KV copy (tens of
            # MB per prompt) would starve waiting admissions.  (ks is None
            # whenever the issue path decided no harvest could apply.)
            elif (self._prefix is not None and self.dispatcher is None
                    and ks is not None
                    and len(items) == 1 and self._queue.empty()):
                nfull = len(ids) // self._chunk * self._chunk
                if nfull and self._prefix.missing_blocks(ids, nfull):
                    self._prefix.put(ids, np.asarray(ks[:, :, :nfull]),
                                     np.asarray(vs[:, :, :nfull]), nfull)
                    self.metrics.prefix_cache_usage_bytes.set(
                        self._prefix.bytes_used, tier="host")

    # -- a full pool under num_slots x max_cache_len (kv_pool_pages) ----
    # Admission reserves a request's pages of the FULL pool for its whole
    # life before it takes a slot, so allocation on the step path still
    # cannot fail; the window layers' pool needs no count of its own (a
    # slot holds at most WindowPages.per_slot of its pages whatever the
    # context, so a free slot is that promise).  A request the pool cannot
    # hold yet waits at the HEAD of the queue and nothing overtakes it: a
    # long prompt is not starved by the short ones behind it.

    def _pool_need(self, req: Request, ids) -> int:
        """Full-pool pages a request can come to own: its prompt, its
        ``max_tokens``, and the rows the dispatches in flight write past
        them (pages_needed, as _start_chunked and _grow_slot_pages ask)."""
        from arks_tpu.engine.paged import pages_needed
        last = min(len(ids) + req.params.max_tokens, self.ecfg.max_cache_len)
        rows = self.ecfg.steps_per_dispatch * (self._pipe_depth_cfg + 1)
        return pages_needed(last, rows, self._page_size(), self._max_pages)

    def _pool_fits(self, req: Request, ids) -> bool:
        return (sum(self._pool_reserved.values()) + self._pool_need(req, ids)
                <= self._pool_budget)

    def _pool_wait(self, req: Request, ids) -> None:
        """Park ``req`` (popped, prepared) until the pool can hold it."""
        self._pool_waiting = (req, ids)
        self.metrics.num_requests_waiting.inc(1)
        self.metrics.admission_page_waits_total.inc(1)

    def _pool_admit_waiting(self) -> bool:
        """Admit the request that waits for pages, if they are there now
        (or drop it, if its client has gone).  False: it still waits."""
        req, ids = self._pool_waiting
        with self._abort_lock:
            gone = req.request_id in self._aborted
            self._aborted.discard(req.request_id)
        if not gone and not self._pool_fits(req, ids):
            return False
        self._pool_waiting = None
        self.metrics.num_requests_waiting.inc(-1)
        if gone:
            self._unpin_guide(req)
            self._deliver(req, RequestOutput(
                request_id=req.request_id, token_ids=[], finished=True,
                finish_reason="abort"))
        else:
            self._start_chunked(req, ids)
        return True

    def _abort_pool_waiting(self) -> None:
        """Fail the request that waits for pages (stop / blanket abort)."""
        if self._pool_waiting is None:
            return
        req, ids = self._pool_waiting
        self._pool_waiting = None
        self.metrics.num_requests_waiting.inc(-1)
        self._unpin_guide(req)
        self._deliver(req, RequestOutput(
            request_id=req.request_id, token_ids=[], finished=True,
            finish_reason="abort", num_prompt_tokens=len(ids)))

    def _assign_slot_pages(self, slot: int, total: int,
                           head_pages=()) -> np.ndarray:
        """Allocate a slot's pages (optionally headed by already-incref'd
        shared prefix pages), record them in _slot_pages, and write the
        zero-padded table row — THE one place the row/ownership invariant
        lives.  Returns the table row."""
        pages = list(head_pages) + self._alloc.alloc(total - len(head_pages))
        self._slot_pages[slot] = pages
        row = np.zeros((self._max_pages,), np.int32)
        row[: len(pages)] = pages
        self._tables[slot] = row
        # Evictions the alloc caused spill before the caller's dispatch
        # can write the recycled pages (stream order).
        self._spill_flush()
        return row

    def _win_cover(self, slot: int, start: int, rows: int) -> None:
        """Own the window-layer pages of ``rows`` rows of ``slot`` from
        position ``start`` and release those behind its window
        (WindowPages.cover), counting the released."""
        gone = self._win.cover(slot, start, rows)
        if gone:
            self.metrics.kv_window_pages_released_total.inc(gone)

    def _register_prompt_pages(self, ids, pages, digests=None) -> None:
        if self._win is not None or self._lin_slot_bytes:
            # No prefix is ever indexed, so none is ever matched: a hit
            # would start a prompt behind window pages that are gone
            # (_block_preflight says so at construction), or behind a
            # prefix whose recurrent state at its end nobody kept.
            return
        from arks_tpu.engine.paged import chain_digests
        page = self._page_size()
        nreg = min(len(ids) // page, len(pages))
        if nreg:
            if digests is None or len(digests) < nreg:
                digests = chain_digests(ids, page, nreg)
            self._alloc.register(digests[:nreg], pages[:nreg])
            self.metrics.prefix_cache_usage_bytes.set(
                self._alloc.retained_pages * self._page_bytes, tier="device")

    # ------------------------------------------------------------------
    # Prefix-digest sketch export (cache-aware routing)
    # ------------------------------------------------------------------

    def cache_sketch(self) -> dict:
        """The prefix-digest sketch payload for ``GET /v1/cache/sketch``.
        Server threads only.  Reads host-side membership snapshots (the
        allocator's locked mirror, the host tier's map under its own
        lock) and host counters — never device data — so an export can
        never add a blocking fetch to the dispatch stream; the build
        itself is cached inside the exporter until tier membership (or
        the epoch) actually changes."""
        sk = self._sketch
        alloc = self._alloc
        if sk is None:
            return {"enabled": False}
        # Scaled to zero: the allocator (and the device prefix tier with
        # it) is gone — advertise an empty tier 0 but keep host/disk
        # visible; peers may still pull warm blocks from this replica.
        device: list = []
        dver = -1
        akey = 0
        if alloc is not None:
            device, dver = alloc.index_snapshot()
            akey = id(alloc)
        host_list: list = []
        hver = -1
        host = self._host
        if host is not None:
            host_list, hver = host.snapshot()
        disk_list: list = []
        dkver = -1
        disk = self._disk
        if disk is not None:
            disk_list, dkver = disk.snapshot()
        # id(alloc) keys the build cache across resets/model switches,
        # where a FRESH allocator restarts its version counter.
        hits = self.metrics.prefix_cache_hit_tokens_total
        return sk.build(
            device, (akey, dver), host_list, hver,
            disk=disk_list, disk_key=dkver,
            hit_tokens={"device": hits.get(tier="device"),
                        "host": hits.get(tier="host"),
                        "disk": hits.get(tier="disk")},
            query_tokens=self.metrics.prefix_cache_query_tokens_total.total(),
            extra={"model": self.cfg.name})

    def note_prompt_text(self, body: dict, ids) -> None:
        """Record one request's text->token digest alignment in the
        sketch exporter's ledger (the text-domain side of tokenize-free
        router scoring).  Server threads; pure host hashing."""
        sk = self._sketch
        if sk is None:
            return
        from arks_tpu.prefix_sketch import canonical_prompt_text
        text = canonical_prompt_text(body)
        if text:
            sk.link(text, ids)

    # ------------------------------------------------------------------
    # Hierarchical prefix cache: host-RAM spill tier (tier 1)
    # ------------------------------------------------------------------

    def _host_tier_on(self) -> bool:
        """Tier 1 active: paged+chunk engine with an ARKS_PREFIX_HOST_MB
        budget on a SINGLE host.  Followers would need the spill/restore
        dispatches mirrored for no benefit — the blocks are host-side
        state only the leader consults — so a dispatcher turns it off
        (same restriction as the legacy slot-layout host cache)."""
        return self._host is not None and self.dispatcher is None

    def _note_evicted(self, digest: bytes, page: int) -> None:
        """PageAllocator.on_evict hook: queue the victim for an async D2H
        spill.  Runs mid-alloc on the engine thread — bookkeeping only;
        _spill_flush issues the gather before any dispatch can reuse the
        page."""
        self._spill_victims.append((digest, page))

    def _spill_flush(self) -> None:
        """Issue spill gathers for every page evicted since the last
        flush: gather the victim pages into a device staging block and
        start the D2H drain (copy_to_host_async) — the engine thread
        never waits; _resolve_spills harvests the bytes one lagged step
        later.  MUST run after the evicting alloc and before the next
        dispatch that could write the recycled pages: both order on the
        device stream, so the gather reads the pre-overwrite bytes."""
        if not self._spill_victims:
            return
        victims, self._spill_victims = self._spill_victims, []
        if not self._host_tier_on():
            return
        victims = [(d, p) for d, p in victims if not self._host.has(d)]
        self.trace.evt("", "spill", "I", len(victims))
        G = self._spill_group
        for i in range(0, len(victims), G):
            grp = victims[i: i + G]
            self._faults.fire("spill")
            # Short groups pad by repeating a real page (one compiled
            # shape); the host side drops the padded entries.
            pages = [p for _, p in grp] + [grp[0][1]] * (G - len(grp))
            out = self._spill_gather_fn(self._cache,
                                        np.array(pages, np.int32))
            for arr in out:
                if arr is not None:
                    arr.copy_to_host_async()
            self._spills.append(([d for d, _ in grp], out))

    def _resolve_spills(self, force: bool = False) -> bool:
        """Harvest completed spill gathers into the host tier (FIFO;
        non-blocking unless forced).  Spills are best-effort cache
        warmth: a failed gather is dropped via the fault API, never
        escalated — losing a spill costs one future re-prefill, while
        faulting the engine for it would cost every in-flight stream a
        recovery round."""
        did = False
        while self._spills:
            digests, out = self._spills[0]
            if not force and not out[0].is_ready():
                break
            self._spills.popleft()
            did = True
            try:
                k, v, ks, vs = [None if a is None else np.asarray(a)
                                for a in out]
            except Exception as e:
                faults_mod.swallowed("spill_resolve", e)
                continue
            stored = 0
            for j, d in enumerate(digests):
                # Contiguous copies: a view would pin the whole staging
                # block in host RAM for the lifetime of one page entry.
                blk = {"k": np.ascontiguousarray(k[:, j]),
                       "v": np.ascontiguousarray(v[:, j])}
                if ks is not None:
                    blk["k_scale"] = np.ascontiguousarray(ks[:, j])
                    blk["v_scale"] = np.ascontiguousarray(vs[:, j])
                if self._host.put(d, blk):
                    stored += 1
            if stored:
                self.metrics.prefix_spill_blocks_total.inc(stored)
            self.metrics.prefix_cache_usage_bytes.set(
                self._host.bytes_used, tier="host")
        return did

    def _issue_restore(self, req: Request, ids: list[int], digests: list,
                       shared: list[int], blocks: list) -> None:
        """Tier-1 hit at admission: allocate fresh pool pages for the
        host blocks and issue the H2D scatter-into-pool dispatch(es)
        ASYNCHRONOUSLY — just another dispatch on the stream, so decode
        pipelining keeps its full depth while the restore is in flight.
        The request parks in awaiting_restore (mirroring the guide_wait
        park); _resolve_restores unparks it into the ordinary
        chunked-tail path once the marker lands."""
        seed = self._resolve_seed(req)
        try:
            self._faults.fire("restore")
            pages = self._alloc.alloc(len(blocks))
            # The alloc may have evicted tier-0 pages; their spill
            # gathers must precede our scatter (which may write those
            # very pages).
            self._spill_flush()
            marker = None
            G = self._restore_group
            for i in range(0, len(blocks), G):
                marker = self._dispatch_restore_group(
                    blocks[i: i + G], pages[i: i + G], G)
        except Exception as e:
            # Page/alloc state is rebuilt wholesale by the recovery
            # reset; the survivor re-queues with its pinned seed and
            # retries admission (the host tier survives the reset, so
            # the retry hits tier 1 again).
            if isinstance(e, StepFault):
                raise
            raise StepFault(
                "restore", faults_mod.classify(e),
                culprits=[req.request_id],
                survivors=[_Survivor(request=req, seed=seed,
                                     num_prompt=len(ids))]) from e
        self._awaiting_restore.append(_RestoreState(
            request=req, ids=ids, digests=digests, shared=shared,
            pages=pages, marker=marker, seed=seed, t0=time.monotonic()))
        self.metrics.num_requests_waiting.inc(1)
        self.trace.evt(req.request_id, "park.restore", "B", len(blocks))

    def _dispatch_restore_group(self, blocks: list, pages: list[int],
                                G: int):
        """One scatter dispatch: stack up to G host blocks into the
        padded staging shape (ONE compiled program) and write them into
        ``pages``.  Returns the dispatch's readiness marker."""
        nb = len(blocks)

        def staged(field):
            first = blocks[0][field]
            out = np.zeros((first.shape[0], G) + first.shape[1:],
                           first.dtype)
            for j, b in enumerate(blocks):
                out[:, j] = b[field]
            return jnp.asarray(out)

        ksb = vsb = None
        if "k_scale" in blocks[0]:
            ksb, vsb = staged("k_scale"), staged("v_scale")
        pg = list(pages) + [pages[0]] * (G - nb)
        self._cache, marker = self._restore_fn(
            self._cache, staged("k"), staged("v"), ksb, vsb,
            jnp.asarray(pg, jnp.int32), jnp.asarray(nb, jnp.int32))
        return marker

    def _restore_ready_any(self) -> bool:
        return any(rec.marker.is_ready()
                   for rec in self._awaiting_restore
                   if not isinstance(rec, _ResumeState))

    def _resume_ready_any(self) -> bool:
        """A preempt-swap resume's scatter landed.  Unlike a prefix
        restore it needs NO free slot — the resumed request already holds
        one — so the pipelined fast path must drain for it even when
        _free is empty."""
        return any(rec.marker.is_ready()
                   for rec in self._awaiting_restore
                   if isinstance(rec, _ResumeState))

    def _swap_ready_any(self) -> bool:
        """The oldest in-flight preempt spill's D2H copies landed (FIFO —
        _resolve_preempt_swaps only ever harvests the head)."""
        if not self._swap_pending:
            return False
        sw = self._swap_pending[0]
        marker = sw.staged[-1][1][0] if sw.staged else sw.row[1]
        return marker.is_ready() and sw.row[1].is_ready()

    def _resolve_restores(self) -> bool:
        """Unpark restore-parked requests whose scatter landed (and a
        free slot exists): register the restored digests into the device
        index (tier-1 hits repopulate tier 0) and continue through the
        ordinary chunked-tail path.  Aborts raised while parked release
        the pages; a failed restore dispatch faults the restoring
        request ALONE (phase "restore")."""
        did = False
        pending = self._awaiting_restore
        i = 0
        while i < len(pending):
            rec = pending[i]
            rid = rec.request.request_id
            with self._abort_lock:
                was_aborted = rid in self._aborted
                if was_aborted:
                    self._aborted.discard(rid)
            if isinstance(rec, _ResumeState):
                # Preempt-swap resume: the request holds its slot already;
                # only the scatter marker gates it (no free-slot wait).
                if was_aborted:
                    pending.pop(i)
                    did = True
                    self.metrics.num_requests_waiting.inc(-1)
                    self._alloc.decref(rec.pages)
                    self._free.append(rec.slot)
                    self._unpin_guide(rec.request)
                    self._deliver(rec.request, RequestOutput(
                        request_id=rid, token_ids=[], finished=True,
                        finish_reason="abort",
                        num_prompt_tokens=rec.rec.num_prompt,
                        num_generated_tokens=len(rec.rec.generated)))
                    self._update_parked()
                    continue
                if not rec.marker.is_ready():
                    i += 1
                    continue
                pending.pop(i)
                did = True
                self.metrics.num_requests_waiting.inc(-1)
                try:
                    self._faults.fire("preempt")
                    np.asarray(rec.marker)  # surfaces dispatch failures
                except Exception as e:
                    self._free.append(rec.slot)
                    if isinstance(e, StepFault):
                        raise
                    raise StepFault(
                        "preempt", faults_mod.classify(e), culprits=[rid],
                        survivors=[self._swap_survivor(rec.rec)]) from e
                self._finish_resume(rec)
                self._update_parked()
                continue
            if was_aborted:
                pending.pop(i)
                did = True
                self.metrics.num_requests_waiting.inc(-1)
                # The scatter may still be in flight toward these pages;
                # freeing them is safe — any re-allocation's write
                # dispatch queues behind our scatter on the stream.
                self._alloc.decref(rec.shared)
                self._alloc.decref(rec.pages)
                self._unpin_guide(rec.request)
                self._deliver(rec.request, RequestOutput(
                    request_id=rid, token_ids=[], finished=True,
                    finish_reason="abort", num_prompt_tokens=len(rec.ids)))
                continue
            if not self._free or not rec.marker.is_ready():
                i += 1
                continue
            pending.pop(i)  # before any fault path, so recovery cannot
            did = True      # double-count the record as a survivor
            self.metrics.num_requests_waiting.inc(-1)
            try:
                self._faults.fire("restore")
                np.asarray(rec.marker)  # surfaces async dispatch failures
            except Exception as e:
                raise StepFault(
                    "restore", faults_mod.classify(e),
                    culprits=[rid],
                    survivors=[_Survivor(request=rec.request, seed=rec.seed,
                                         num_prompt=len(rec.ids))]) from e
            page = self._page_size()
            start = len(rec.shared)
            # Register BEFORE _start_chunked: if the tail alloc faults,
            # its cleanup decrefs only our caller refs and the restored
            # pages survive as index-retained.
            self._alloc.register(
                rec.digests[start: start + len(rec.pages)], rec.pages)
            if self._host is not None:
                self._host.restored_blocks += len(rec.pages)
            self.metrics.prefix_restore_blocks_total.inc(len(rec.pages))
            self.metrics.prefix_restore_seconds.observe(
                time.monotonic() - rec.t0)
            self.metrics.prefix_cache_usage_bytes.set(
                self._alloc.retained_pages * self._page_bytes,
                tier="device")
            self.trace.evt(rid, "park.restore", "E")
            self._start_chunked(
                rec.request, rec.ids,
                prefix_len=(start + len(rec.pages)) * page,
                prefix_pages=rec.shared + rec.pages,
                digests=rec.digests)
        return did

    def _abort_awaiting_restores(self) -> None:
        """Fail every restore-parked request (engine exit / blanket
        abort): no scheduler remains to unpark them.  Page bookkeeping is
        moot — both callers precede a device reset or process exit."""
        for rec in self._awaiting_restore:
            self.metrics.num_requests_waiting.inc(-1)
            self._unpin_guide(rec.request)
            self._deliver(rec.request, RequestOutput(
                request_id=rec.request.request_id, token_ids=[],
                finished=True, finish_reason="abort",
                num_prompt_tokens=len(rec.ids)))
        self._awaiting_restore = []

    # ------------------------------------------------------------------
    # Tier-2 disk block store + fleet peer fetch
    # ------------------------------------------------------------------

    def _kv_layout_epoch(self) -> str:
        """Pool layout signature digest.  Chain digests are content-only
        (token ids) — NOT keyed by model or pool geometry — so every
        tier-2 block file and every peer-fetched wire block carries this
        stamp, and a reader on any other layout rejects the bytes
        instead of reinterpreting them."""
        import hashlib
        sig = "|".join(str(x) for x in (
            self.cfg.name, self._page_size(), self.cfg.num_layers,
            # (layers that keep a sequence's every page; window layers
            # keep a window of their own)
            *((self.cfg.num_full_layers, self.cfg.sliding_window)
              if self.cfg.windowed else ()),
            *((self.cfg.num_full_layers, "linear")
              if self.cfg.linear else ()),
            *((self.cfg.num_full_layers, "ssm") if self.cfg.ssm else ()),
            self.cfg.num_kv_heads, self._page_bytes,
            self.ecfg.kv_quantized, self.ecfg.kv_bits,
            self.ecfg.resolve_kv_cache_dtype()))
        return hashlib.sha1(sig.encode()).hexdigest()[:16]

    @property
    def kv_epoch(self) -> str:
        """The layout epoch peers validate fetched blocks against (the
        server's block-export path packs with this)."""
        return self._kv_epoch

    def _note_host_evicted(self, digest: bytes, block: dict) -> None:
        """HostPrefixTier.on_evict hook: queue a tier-1 evictee for the
        async disk spill.  Called outside the tier lock, from whichever
        thread triggered the eviction (engine spill harvest, disagg
        publish) — bookkeeping only; the step loop drains the queue and
        a writer thread does the file IO.  Bounded: a spill storm drops
        blocks (cache warmth is best-effort) rather than growing an
        unbounded backlog of host RAM the LRU just decided to free."""
        if self._disk is None or len(self._disk_spill_pending) >= 1024:
            return
        self._disk_spill_pending.append((digest, block))

    def _drain_disk_spills(self) -> bool:
        """Hand queued tier-1 evictees to the disk writer thread (engine
        thread; no file IO here).  Phase "disk_spill" raises with NO
        culprits — a spill serves no request, so a fault replays every
        in-flight stream and burns nobody's retry budget."""
        if self._disk is None or not self._disk_spill_pending:
            return False
        try:
            self._faults.fire("disk_spill")
        except Exception as e:
            if isinstance(e, StepFault):
                raise
            raise StepFault("disk_spill", faults_mod.classify(e)) from e
        n = 0
        while self._disk_spill_pending:
            digest, blk = self._disk_spill_pending.popleft()
            if self._disk.has(digest):
                continue
            try:
                self._disk_write_queue.put_nowait((digest, blk))
            except queue.Full:
                # Best-effort: losing a spill costs one future
                # re-prefill; blocking the step loop would cost every
                # in-flight stream.
                self._disk_spill_pending.clear()
                break
            n += 1
        if n:
            self.trace.evt("", "disk_spill", "I", n)
        return n > 0

    def _disk_write_loop(self) -> None:
        """Writer thread: persist queued blocks (tmp+rename inside the
        tier) and mirror the tier's gauges.  Failures are swallowed —
        the disk tier is warmth, never correctness."""
        q = self._disk_write_queue
        while True:
            item = q.get()
            if item is None:
                return
            digest, blk = item
            try:
                self._disk.put(digest, blk)
            except Exception as e:
                faults_mod.swallowed("disk_spill.write", e)
            self._mirror_disk_metrics()

    def _mirror_disk_metrics(self) -> None:
        """Mirror the disk tier's internal counters into EngineMetrics
        (called from the writer/fetch threads after tier mutations)."""
        d = self._disk
        if d is None:
            return
        m = self.metrics
        m.prefix_cache_usage_bytes.set(d.bytes_used, tier="disk")
        with self._disk_stats_lock:
            ev, co = d.evicted_blocks, d.corrupt_blocks
            if ev > self._disk_evict_seen:
                m.prefix_disk_evictions_total.inc(ev - self._disk_evict_seen)
                self._disk_evict_seen = ev
            if co > self._disk_corrupt_seen:
                m.prefix_disk_corrupt_total.inc(co - self._disk_corrupt_seen)
                self._disk_corrupt_seen = co

    def _flush_warm_to_disk(self) -> None:
        """Graceful-stop persistence (stop(), engine thread already
        joined): gather every prefix block still resident in the device
        index with the spill path's own grouped gather, and copy every
        tier-1 block, into the disk store — synchronously; blocking D2H
        is fine once the step loop is gone.  Best-effort throughout: a
        failed gather or write costs restart warmth, never the
        shutdown."""
        disk, host, alloc = self._disk, self._host, self._alloc
        gather = getattr(self, "_spill_gather_fn", None)
        if alloc is not None and gather is not None and \
                self._cache is not None:
            with alloc._mirror_lock:
                resident = list(alloc._index.items())  # digest -> page
            victims = [(d, p) for d, p in resident if not disk.has(d)]
            G = self._spill_group
            for i in range(0, len(victims), G):
                grp = victims[i: i + G]
                pages = [p for _, p in grp] + [grp[0][1]] * (G - len(grp))
                try:
                    out = gather(self._cache,
                                 jnp.asarray(pages, jnp.int32))
                    k, v, ks, vs = [None if a is None else np.asarray(a)
                                    for a in out]
                except Exception as e:
                    faults_mod.swallowed("disk_tier.flush", e)
                    continue
                for j, (d, _) in enumerate(grp):
                    blk = {"k": np.ascontiguousarray(k[:, j]),
                           "v": np.ascontiguousarray(v[:, j])}
                    if ks is not None:
                        blk["k_scale"] = np.ascontiguousarray(ks[:, j])
                        blk["v_scale"] = np.ascontiguousarray(vs[:, j])
                    disk.put(d, blk)
        if host is not None:
            digests, _ver = host.snapshot()
            for d in digests:
                if disk.has(d):
                    continue
                blk = host.peek(d)
                if blk is not None:
                    disk.put(d, blk)
        self._mirror_disk_metrics()

    def _fetch_candidate(self, req: Request, digests: list,
                         covered: int) -> bool:
        """Can tier 2 or a peer extend this admission's coverage?  Pure
        host probes (the disk check is an in-memory index hit): True
        parks the request in _awaiting_fetch instead of re-prefilling
        the uncovered span."""
        if self._fetch_queue is None or not self._host_tier_on():
            return False
        if self._disk is not None and \
                self._disk.match_digests(digests, covered):
            return True
        return self._peer_fetch and bool(req.peer_hint or self._peer_addrs)

    def _issue_fetch(self, req: Request, ids: list[int], digests: list,
                     start: int) -> None:
        """Park an admission miss whose uncovered digests the disk tier
        (or a hinted peer) can supply.  No device pages are held across
        the park — the resolve re-runs the match from scratch — so abort
        and recovery need no page bookkeeping for this state."""
        seed = self._resolve_seed(req)
        st = _FetchState(request=req, ids=ids, digests=digests,
                         start=start, peer=(req.peer_hint or None),
                         seed=seed, t0=time.monotonic())
        self._awaiting_fetch.append(st)
        self._fetch_queue.put(st)
        self.metrics.num_requests_waiting.inc(1)
        self.trace.evt(req.request_id, "park.fetch", "B",
                       len(digests) - start)

    def _fetch_loop(self) -> None:
        """Fetch worker thread: stage parked requests' missing blocks
        into the host tier.  Every failure mode degrades to `done` with
        whatever run was staged — the resolve then restores the partial
        run and chunk-prefills the rest (mid-fetch peer death costs
        latency, never correctness)."""
        q = self._fetch_queue
        while True:
            st = q.get()
            if st is None:
                return
            try:
                self._fetch_one(st)
            except Exception as e:
                faults_mod.swallowed("prefix_fetch", e)
            st.done = True

    def _fetch_one(self, st: _FetchState) -> None:
        """Stage st's uncovered digest run: local disk first (cheaper),
        then the hinted peer, then the static ARKS_PEER_ADDRS list.
        Consecutive-only — a gap stops the run, because a restore needs
        a contiguous prefix."""
        peers = [a for a in ([st.peer] if st.peer else [])
                 + self._peer_addrs if a]
        for d in st.digests[st.start:]:
            if self._host.has(d):
                continue
            blk = self._disk.get(d) if self._disk is not None else None
            src = "disk"
            if blk is None and peers:
                blk = self._fetch_from_peers(peers, d)
                src = "peer"
            if blk is None:
                break
            if not self._host.put(d, blk) and not self._host.has(d):
                break   # host budget cannot hold the staged run
            if src == "disk":
                st.fetched_disk += 1
            else:
                st.fetched_peer += 1
        self._mirror_disk_metrics()

    def _fetch_from_peers(self, peers: list[str], digest: bytes):
        """One block from the first peer that has it, validated against
        the local layout epoch (a peer on another pool layout 404s or is
        rejected — never reinterpreted)."""
        from arks_tpu.engine import kv_transfer
        for addr in peers:
            buf = self._peer_block_get(addr, digest)
            if buf is None:
                continue
            try:
                blk = kv_transfer.unpack_block(buf, digest, self._kv_epoch)
            except ValueError as e:
                faults_mod.swallowed("peer_fetch.unpack", e)
                continue
            return {k: np.ascontiguousarray(v) for k, v in blk.items()}
        return None

    def _peer_block_get(self, addr: str, digest: bytes) -> bytes | None:
        """GET /v1/cache/blocks/{digest} from one peer; None on any
        failure (timeout, refused, 404, mid-body death) — the caller
        falls back to the next peer or to re-prefill."""
        import http.client
        addr = addr.split("//", 1)[-1].rstrip("/")
        host, _, port = addr.rpartition(":")
        try:
            conn = http.client.HTTPConnection(
                host or addr, int(port) if port else 80,
                timeout=self._peer_timeout)
            try:
                conn.request("GET", f"/v1/cache/blocks/{digest.hex()}")
                resp = conn.getresponse()
                if resp.status != 200:
                    return None
                return resp.read()
            finally:
                conn.close()
        except Exception as e:
            faults_mod.swallowed("peer_fetch.http", e)
            return None

    def _fetch_ready_any(self) -> bool:
        return bool(self._free) and any(st.done
                                        for st in self._awaiting_fetch)

    def _resolve_fetches(self) -> bool:
        """Unpark fetch-parked requests whose worker finished: re-run
        the admission match (the staged blocks now sit in the host tier)
        and continue through the ordinary tier-1 restore / chunked-tail
        path.  A resolve fault culprits the fetching request ALONE
        (phase "peer_fetch"); aborts raised while parked just fail the
        request — no pages were held across the park."""
        did = False
        pending = self._awaiting_fetch
        i = 0
        while i < len(pending):
            st = pending[i]
            rid = st.request.request_id
            with self._abort_lock:
                was_aborted = rid in self._aborted
                if was_aborted:
                    self._aborted.discard(rid)
            if was_aborted:
                pending.pop(i)
                did = True
                self.metrics.num_requests_waiting.inc(-1)
                self._unpin_guide(st.request)
                self._deliver(st.request, RequestOutput(
                    request_id=rid, token_ids=[], finished=True,
                    finish_reason="abort", num_prompt_tokens=len(st.ids)))
                continue
            if not st.done or not self._free:
                i += 1
                continue
            pending.pop(i)  # before the fault fire, so recovery cannot
            did = True      # double-count the record as a survivor
            self.metrics.num_requests_waiting.inc(-1)
            try:
                self._faults.fire("peer_fetch")
            except Exception as e:
                if isinstance(e, StepFault):
                    raise
                raise StepFault(
                    "peer_fetch", faults_mod.classify(e), culprits=[rid],
                    survivors=[_Survivor(request=st.request, seed=st.seed,
                                         num_prompt=len(st.ids))]) from e
            page = self._page_size()
            if st.fetched_disk:
                self.metrics.prefix_peer_fetch_blocks_total.inc(
                    st.fetched_disk, source="disk")
                self.metrics.prefix_cache_hit_tokens_total.inc(
                    st.fetched_disk * page, tier="disk")
            if st.fetched_peer:
                self.metrics.prefix_peer_fetch_blocks_total.inc(
                    st.fetched_peer, source="peer")
                self.metrics.prefix_cache_hit_tokens_total.inc(
                    st.fetched_peer * page, tier="peer")
            if st.fetched_disk or st.fetched_peer:
                self.metrics.prefix_peer_fetch_seconds.observe(
                    time.monotonic() - st.t0)
            self.trace.evt(rid, "park.fetch", "E",
                           st.fetched_disk + st.fetched_peer)
            self._admit_after_fetch(st)
        return did

    def _admit_after_fetch(self, st: _FetchState) -> None:
        """Route an unparked fetch through the standard admission match:
        device run (may have changed while parked), then host tier (now
        holding the staged blocks), then the chunked tail.  An empty
        fetch degrades to plain chunked prefill — the no-worse-than-
        re-prefill guarantee."""
        req, ids, digests = st.request, st.ids, st.digests
        page = self._page_size()
        shared = self._alloc.match(digests)
        plen = len(shared) * page
        host_blocks: list = []
        if self._host_tier_on() and len(shared) < len(digests):
            host_blocks = self._host.match_blocks(digests, len(shared))
        if host_blocks:
            return self._issue_restore(req, ids, digests, shared,
                                       host_blocks)
        if plen:
            return self._start_chunked(req, ids, prefix_len=plen,
                                       prefix_pages=shared,
                                       digests=digests)
        self._alloc.decref(shared)
        self._start_chunked(req, ids)

    def block_for_export(self, digest: bytes) -> dict | None:
        """One prefix block for a peer's GET /v1/cache/blocks/{digest}.
        Server threads.  Host tier first (peek — a remote reader must
        not distort this replica's own recency order), then disk; None
        maps to 404 at the HTTP layer."""
        host = self._host
        if host is not None:
            blk = host.peek(digest)
            if blk is not None:
                return blk
        disk = self._disk
        if disk is not None:
            return disk.get(digest)
        return None

    def _abort_awaiting_fetches(self) -> None:
        """Fail every fetch-parked request (engine exit / blanket
        abort): no scheduler remains to unpark them."""
        for st in self._awaiting_fetch:
            self.metrics.num_requests_waiting.inc(-1)
            self._unpin_guide(st.request)
            self._deliver(st.request, RequestOutput(
                request_id=st.request.request_id, token_ids=[],
                finished=True, finish_reason="abort",
                num_prompt_tokens=len(st.ids)))
        self._awaiting_fetch = []

    # ------------------------------------------------------------------
    # SLO-tiered preemptive KV swap (ARKS_PREEMPT)
    # ------------------------------------------------------------------
    # Priority stops being mere queue ordering: when a queued request's
    # (aged) priority strictly outranks the lowest running tier and no
    # slot is free, the scheduler seizes a victim slot.  Two modes:
    #
    # - SWAP (paged + chunked + host tier, single-host, non-spec): the
    #   victim's FULL decode state leaves the device — KV pages through
    #   the same gather/stage path the prefix spill uses, plus the
    #   sampler row (PRNG key, penalty counts, DFA row) — and parks in
    #   the SwapStore.  Resume scatters it all back into a fresh slot and
    #   the stream continues byte-identically: the key snapshot re-enters
    #   the per-slot split chain exactly where sample() left it, the
    #   counts row reproduces the penalty state, and pool pages are
    #   byte-exact round trips (the PR 5 bit-exactness argument).
    # - REPLAY (everything else): the victim re-queues behind a
    #   _ReplayGate and deterministically re-executes — the PR 4 recovery
    #   discipline, which also backstops swap mode when the host budget
    #   is full.  docs/application-usage.md carries the fallback matrix.
    #
    # Freeing the victim's slot in the SAME step as the gathers is safe
    # for the same reason _spill_flush is: every device op enqueues in
    # order on one stream, so the gathers read pre-reuse bytes no matter
    # when the next admission's dispatch lands.

    def _preempt_swap_capable(self) -> bool:
        """Swap-mode eligibility (engine-wide, decided at init): needs
        the paged+chunk engine with the host tier on (the SwapStore
        shares its budget) and no draft model — a spec victim's draft
        cache mirror has no cheap snapshot, so spec engines preempt in
        replay mode."""
        return (self._host_tier_on() and self._swap is not None
                and self._draft_cfg is None)

    def _preempt_capable(self) -> bool:
        """Preemption on at all: ARKS_PREEMPT=1 and single-host (the
        follower dispatch protocol has no preempt op)."""
        return self._preempt_on and self.dispatcher is None

    @staticmethod
    def _swap_survivor(rec: _SwapRecord) -> _Survivor:
        """A swapped victim's replayable snapshot — any fault on the swap
        path downgrades it to ordinary token-replay recovery."""
        return _Survivor(request=rec.request, seed=rec.seed,
                         num_prompt=rec.num_prompt,
                         generated=list(rec.generated),
                         num_emitted=rec.num_emitted,
                         logprobs=list(rec.logprobs),
                         first_token_time=rec.first_token_time)

    def _queue_head_prio(self):
        """Effective priority of the admission-queue head (None when
        empty) — delegated to the FairQueue, which knows its own lanes
        (urgent heap first, then the best non-empty tier)."""
        return self._queue.head_prio()

    def _shed_due(self, req: Request) -> bool:
        """Should this just-popped request be deadline-shed?  True only
        when shedding is on, the request's tier declares a ttft_ms
        target, the wait already exceeds factor x that budget, and the
        request is not exempt (replay / swap-resume / disagg-prefilled)."""
        if not self._shed_deadline_factor or not self._slo:
            return False
        if (isinstance(req.outputs, _ReplayGate)
                or req.request_id in self._resuming
                or req.prefilled is not None):
            return False
        tier = self._slo.get(self._slo.tier_of(req.params.priority))
        if tier is None or not tier.ttft_ms:
            return False
        budget_s = tier.ttft_ms / 1000.0 * self._shed_deadline_factor
        return (time.monotonic() - req.arrival_time) > budget_s

    def saturation(self) -> dict:
        """Admission-queue overload signal (depth, caps, waiting tenants,
        drain rate, 0-1 saturation fraction) — exported via /readiness
        and the x-arks-saturation header on shed responses."""
        return self._queue.saturation()

    def queue_retry_after(self) -> int:
        """Drain-rate-derived backoff (seconds) for shed responses."""
        return self._queue.retry_after()

    def _slo_burn_record(self, priority: int, ttft_s: float) -> None:
        """One first-token sample for the rolling burn tracker (engine
        thread only; tiers without a ttft_ms target record nothing)."""
        if not self._slo:
            return
        name = self._slo.tier_of(priority)
        tier = self._slo.get(name)
        if tier is None or not tier.ttft_ms:
            return
        ev = self._slo_events.setdefault(name, [])
        ev.append((time.monotonic(), ttft_s * 1000.0 > tier.ttft_ms))
        if len(ev) > 1024:
            del ev[:len(ev) - 512]

    def slo_burn(self) -> dict:
        """Per-tier SLO burn rate over ARKS_SLO_BURN_WINDOW_S: the
        fraction of first tokens that missed the tier's ttft_ms target,
        divided by ARKS_SLO_ERROR_BUDGET (1.0 = burning exactly at
        budget).  Exported via /readiness; the signals-mode autoscaler
        scales up when any tier crosses ARKS_ELASTIC_BURN_HI.  Any
        thread — appends happen engine-side, the slice copies."""
        now = time.monotonic()
        cutoff = now - self._slo_burn_window_s
        out: dict[str, float] = {}
        for name, ev in list(self._slo_events.items()):
            recent = [v for (t, v) in ev[-1024:] if t >= cutoff]
            if recent:
                frac = sum(recent) / len(recent)
                out[name] = round(frac / self._slo_error_budget, 4)
        return out

    def _queue_age_tick(self) -> None:
        """Priority-queue aging (ARKS_QUEUE_AGING_S): re-derive queued
        entries' effective tier as ``base - elapsed/aging_s`` (floored
        at 0) so a starved batch request climbs one tier per window and
        eventually admits under sustained latency-tier load.  The aging
        itself is per-(tier, tenant) inside the FairQueue (promotions
        keep each tenant's FIFO order); replay re-queues (priority -
        2**20) ride the urgent lane and never age.  Throttled to a
        fraction of the window so the rebucketing cost stays off the
        per-step path."""
        if not self._queue_aging_s:
            return
        now = time.monotonic()
        if now - self._queue_age_last < min(1.0, self._queue_aging_s / 4):
            return
        self._queue_age_last = now
        self._queue.age_tick(now, self._queue_aging_s)

    def _preempt_inflight(self) -> int:
        """Victims preempted and not yet back in a slot, across both
        modes — the ARKS_PREEMPT_MAX_INFLIGHT budget's denominator."""
        if self._resuming:
            # Replay-mode victims leave _resuming at re-registration;
            # ones that died queued (abort/quarantine) must not pin the
            # budget forever.
            live = self._live_rids()
            with self._abort_lock:
                live |= self._queued_rids
            self._resuming &= live
        return (len(self._swap_pending) + len(self._swapped)
                + sum(1 for r in self._awaiting_restore
                      if isinstance(r, _ResumeState))
                + len(self._resuming))

    def _preempt_victims(self) -> list[int]:
        """Victim slots, best-first: strictly lower tier than the queue
        head (aged), lowest tier first, least progress within a tier
        (cheapest swap, most re-usable work preserved), most recent
        arrival on ties.  Never a replaying/resumed slot (their streams
        are mid-verification), never one inside the anti-thrash cooldown
        window."""
        head = self._queue_head_prio()
        if head is None:
            return []
        now = time.monotonic()
        cands = []
        for slot, st in self._slots.items():
            prio = st.request.params.priority
            if prio <= head:
                continue
            rid = st.request.request_id
            if rid in self._replaying or rid in self._resuming:
                continue
            if self._residency is not None and slot in self._residency.slots:
                # An engaged slot's KV is split across host store +
                # staging + tail — the swap harvest has no single page
                # list to gather.  Windowed slots finish in place.
                continue
            if now - self._preempt_last.get(rid, -1e9) < self._preempt_cooldown_s:
                continue
            cands.append((-prio, len(st.generated),
                          -st.request.arrival_time, slot))
        cands.sort()
        return [c[-1] for c in cands]

    def _preempt_wanted(self) -> bool:
        """Cheap host-only check, safe on the pipelined fast path: a
        queued request outranks a running victim, no free slot, budget
        available.  The queue-empty test short-circuits the common case
        to one attribute read."""
        if self._queue.empty() or self._free or not self._slots:
            return False
        if not self._preempt_capable() or self._state != "serving":
            return False
        if self._preempt_inflight() >= self._preempt_max:
            return False
        return bool(self._preempt_victims())

    def _maybe_preempt(self) -> bool:
        """Seize slots for outranking queued requests (one victim per
        queued seizer, capped by the in-flight budget).  Runs between
        resolves and the issue block, so every freed slot admits in the
        SAME scheduler step."""
        if not self._preempt_wanted():
            return False
        budget = self._preempt_max - self._preempt_inflight()
        n = min(budget, self._queue.qsize())
        did = False
        for slot in self._preempt_victims()[:n]:
            if self._preempt_swap_capable() and self._slot_pages.get(slot):
                self._issue_preempt_swap(slot)
            else:
                self._preempt_replay(slot)
            did = True
        if did:
            self._update_parked()
        return did

    def _issue_preempt_swap(self, slot: int) -> None:
        """Swap-mode preemption, issue side: gather the victim's valid KV
        pages and its sampler row into device staging blocks, start the
        D2H drain (copy_to_host_async — never a host wait), then free the
        slot immediately (stream order keeps the gathers pre-reuse).
        _resolve_preempt_swaps harvests the bytes into the SwapStore a
        lagged step later."""
        st = self._slots[slot]
        rid = st.request.request_id
        p = st.request.params
        page = self._page_size()
        length = int(self._lengths[slot])
        pages_all = self._slot_pages.get(slot, [])
        n_pages = min(-(-length // page), len(pages_all))
        rec = _SwapRecord(
            request=st.request, num_prompt=st.num_prompt,
            generated=list(st.generated), num_emitted=st.num_emitted,
            logprobs=list(st.logprobs),
            first_token_time=st.first_token_time, seed=st.seed,
            length=length, last_token=int(self._last_token[slot]),
            stop_col=st.stop_col, dead_len=st.dead_len, n_pages=n_pages,
            priority=p.priority, t0=time.monotonic())
        try:
            self._faults.fire("preempt")
            staged = []
            G = self._spill_group
            victim_pages = pages_all[:n_pages]
            for i in range(0, n_pages, G):
                grp = victim_pages[i: i + G]
                pg = grp + [grp[0]] * (G - len(grp))
                out = self._spill_gather_fn(self._cache,
                                            jnp.asarray(pg, jnp.int32))
                for arr in out:
                    if arr is not None:
                        arr.copy_to_host_async()
                staged.append((len(grp), out))
            row = self._sampler_row_fn(self._sampling,
                                       jnp.asarray(slot, jnp.int32))
            for arr in row:
                arr.copy_to_host_async()
        except Exception as e:
            # Victim still registered: recovery snapshots it from _slots
            # and token-replay preserves its stream.
            if isinstance(e, StepFault):
                raise
            raise StepFault("preempt", faults_mod.classify(e),
                            culprits=[rid]) from e
        # Gathers are on the stream — the slot can be reused now.  The
        # guide pin is deliberately KEPT: the snapshotted DFA row must
        # stay valid until resume.
        self._slots.pop(slot)
        self._release_slot_pages(slot)
        self._free.append(slot)
        self._clear_shaping(slot, p)
        self._swap_pending.append(_SwapState(rec=rec, staged=staged, row=row))
        self._preempt_last[rid] = time.monotonic()
        self.trace.evt(rid, "park.preempt", "B", n_pages)
        self.metrics.requests_preempted_total.inc(
            1, tier=self._slo.tier_of(p.priority))
        self.metrics.num_requests_running.set(len(self._slots))
        self.metrics.num_requests_waiting.inc(1)
        log.info("preempted %s (tier=%s, %d pages) for a higher tier",
                 rid, self._slo.tier_of(p.priority), n_pages)

    def _preempt_replay(self, slot: int) -> None:
        """Replay-mode preemption (the fallback matrix rows): free the
        victim's slot and re-queue it behind a _ReplayGate for
        deterministic re-execution — no host KV needed; the cost is
        re-prefilling and re-decoding the generated prefix on resume."""
        st = self._slots[slot]
        rid = st.request.request_id
        p = st.request.params
        try:
            self._faults.fire("preempt")
        except Exception as e:
            # Victim untouched: recovery snapshots it from _slots.
            raise StepFault("preempt", faults_mod.classify(e),
                            culprits=[rid]) from e
        rec = _SwapRecord(
            request=st.request, num_prompt=st.num_prompt,
            generated=list(st.generated), num_emitted=st.num_emitted,
            logprobs=list(st.logprobs),
            first_token_time=st.first_token_time, seed=st.seed,
            length=int(self._lengths[slot]) if self._paged else 0,
            last_token=int(self._last_token[slot]),
            stop_col=st.stop_col, dead_len=st.dead_len, n_pages=0,
            priority=p.priority, t0=time.monotonic())
        self._slots.pop(slot)
        self._release_slot_pages(slot)
        self._free.append(slot)
        self._unpin_guide(st.request)
        self._clear_shaping(slot, p)
        self._preempt_last[rid] = time.monotonic()
        self.trace.evt(rid, "park.preempt", "B", "replay")
        self.metrics.requests_preempted_total.inc(
            1, tier=self._slo.tier_of(p.priority))
        self.metrics.num_requests_running.set(len(self._slots))
        self._preempt_requeue_replay(rec)
        log.info("preempted %s (tier=%s) in replay mode",
                 rid, self._slo.tier_of(p.priority))

    def _preempt_requeue_replay(self, rec: _SwapRecord) -> None:
        """Re-queue a preempted victim for deterministic re-execution at
        its OWN priority (unlike fault replayers it is not urgent — it
        was just outranked).  The gate suppresses the already-delivered
        prefix and verifies byte-identity of the re-run."""
        req = rec.request
        rid = req.request_id
        # What the last resolve held back counts as emitted (num_emitted):
        # it reaches the client's queue before the gate takes its place.
        self._flush_deferred()
        gate = req.outputs if isinstance(req.outputs, _ReplayGate) else None
        if gate is None:
            req.outputs = _ReplayGate(req.outputs, self, rid,
                                      rec.generated, rec.num_emitted)
        else:
            gate.restart(rec.generated)
        self._resuming.add(rid)
        with self._abort_lock:
            self._queued_rids.add(rid)
            self._queue_seq += 1
            seq = self._queue_seq
        self.metrics.num_requests_waiting.inc(1)
        self._queue.put((req.params.priority, seq, req))

    def _resolve_preempt_swaps(self, force: bool = False) -> bool:
        """Harvest completed preempt spills into the SwapStore (FIFO,
        non-blocking unless forced).  Unlike prefix spills these are NOT
        best-effort — the victim's only KV copy is in these staging
        blocks — so a harvest failure faults the victim alone and
        token-replay rebuilds its stream; a SwapStore refusal (budget
        full) downgrades to replay mode without a fault."""
        did = False
        while self._swap_pending:
            sw = self._swap_pending[0]
            marker = sw.staged[-1][1][0] if sw.staged else sw.row[1]
            if not force and not (marker.is_ready()
                                  and sw.row[1].is_ready()):
                break
            self._swap_pending.pop(0)
            did = True
            rec = sw.rec
            rid = rec.request.request_id
            with self._abort_lock:
                was_aborted = rid in self._aborted
                if was_aborted:
                    self._aborted.discard(rid)
            if was_aborted:
                self._finish_swapped_abort(rec)
                self._update_parked()
                continue
            try:
                self._faults.fire("preempt")
                blocks = []
                for n, out in sw.staged:
                    k, v, ks, vs = [None if a is None else np.asarray(a)
                                    for a in out]
                    for j in range(n):
                        blk = {"k": np.ascontiguousarray(k[:, j]),
                               "v": np.ascontiguousarray(v[:, j])}
                        if ks is not None:
                            blk["k_scale"] = np.ascontiguousarray(ks[:, j])
                            blk["v_scale"] = np.ascontiguousarray(vs[:, j])
                        blocks.append(blk)
                entry = {"blocks": blocks,
                         "key": np.asarray(sw.row[0]),
                         "counts": np.asarray(sw.row[1]),
                         "guide_row": int(np.asarray(sw.row[2]))}
            except Exception as e:
                self.metrics.num_requests_waiting.inc(-1)
                if isinstance(e, StepFault):
                    raise
                raise StepFault("preempt", faults_mod.classify(e),
                                culprits=[rid],
                                survivors=[self._swap_survivor(rec)]) from e
            if self._swap is not None and self._swap.put(rid, entry):
                self._swapped[rid] = rec
                self.metrics.preempt_swap_seconds.observe(
                    time.monotonic() - rec.t0)
                self.metrics.prefix_cache_usage_bytes.set(
                    self._swap.bytes_used, tier="swap")
            else:
                # Host budget cannot hold the snapshot — fall back to
                # replay-mode resume (drop the bytes, re-execute later).
                log.warning("swap store refused %s (%d blocks); falling "
                            "back to replay-mode preemption", rid,
                            len(entry["blocks"]))
                self.metrics.num_requests_waiting.inc(-1)
                self._unpin_guide(rec.request)
                self._preempt_requeue_replay(rec)
            self._update_parked()
        return did

    def _service_swapped(self) -> bool:
        """Swapped-out victims: serve aborts (host bytes come straight
        back) and schedule resumes — best victim first (highest tier,
        earliest preempt), but only while the queue head does not
        STRICTLY outrank it (admission wins ties are not allowed to
        starve a victim of the same tier that already burned a prefill)."""
        did = False
        if not self._swapped:
            return False
        with self._abort_lock:
            hit = [rid for rid in self._swapped if rid in self._aborted]
            for rid in hit:
                self._aborted.discard(rid)
        for rid in hit:
            rec = self._swapped.pop(rid)
            if self._swap is not None:
                self._swap.discard(rid)
                self.metrics.prefix_cache_usage_bytes.set(
                    self._swap.bytes_used, tier="swap")
            self._finish_swapped_abort(rec)
            did = True
        while self._swapped and self._free:
            rid, rec = min(self._swapped.items(),
                           key=lambda kv: (kv[1].priority, kv[1].t0))
            head = self._queue_head_prio()
            if head is not None and head < rec.priority:
                break
            if (self._alloc.free_pages + self._alloc.retained_pages
                    < rec.n_pages):
                break  # pool pressure: wait for pages, don't fault
            self._resume_swapped(rid)
            did = True
        if did:
            self._update_parked()
        return did

    def _resume_swapped(self, rid: str) -> None:
        """Swap-mode resume, issue side: take a free slot, scatter the
        victim's page blocks back (async, padded restore groups — the
        same program as prefix restores) and rebuild its sampler row
        (snapshot key + DFA row through set_slot, counts through the
        donated restore jit).  The request parks as a _ResumeState in
        awaiting_restore; _finish_resume re-registers the slot once the
        marker lands."""
        rec = self._swapped[rid]
        entry = self._swap.pop(rid) if self._swap is not None else None
        self.metrics.prefix_cache_usage_bytes.set(
            self._swap.bytes_used if self._swap is not None else 0,
            tier="swap")
        if entry is None:
            # Entry vanished (blanket-abort clear raced a re-queue):
            # replay mode still resumes the stream correctly.
            del self._swapped[rid]
            self.metrics.num_requests_waiting.inc(-1)
            self._unpin_guide(rec.request)
            self._preempt_requeue_replay(rec)
            return
        slot = self._free.pop()
        try:
            self._faults.fire("preempt")
            pages = self._alloc.alloc(rec.n_pages)
            # The alloc may have evicted tier-0 pages; their spill
            # gathers must precede our scatter.
            self._spill_flush()
            marker = None
            G = self._restore_group
            blocks = entry["blocks"]
            for i in range(0, len(blocks), G):
                marker = self._dispatch_restore_group(
                    blocks[i: i + G], pages[i: i + G], G)
            gid = -1
            if rec.request.params.guide is not None:
                gid, _ = self._guide_cols(rec.request.params)
            self._apply_set_slot(slot, rec.request.params, entry["key"],
                                 False, num_prompt=rec.num_prompt,
                                 guide=gid,
                                 guide_row=int(entry["guide_row"]))
            self._sampling = self._restore_counts_fn(
                self._sampling, np.int32(slot), entry["counts"])
        except Exception as e:
            self._free.append(slot)
            del self._swapped[rid]
            self.metrics.num_requests_waiting.inc(-1)
            self._unpin_guide(rec.request)
            if isinstance(e, StepFault):
                raise
            raise StepFault("preempt", faults_mod.classify(e),
                            culprits=[rid],
                            survivors=[self._swap_survivor(rec)]) from e
        del self._swapped[rid]
        self._awaiting_restore.append(_ResumeState(
            rec=rec, slot=slot, pages=pages, marker=marker,
            t0=time.monotonic()))

    def _finish_resume(self, res: _ResumeState) -> None:
        """Swap-mode resume, landing side: the scatter resolved — rebuild
        the victim's _Slot and host mirrors exactly as preempt recorded
        them.  No first-token output, no TTFT: the stream simply
        continues at the next decode dispatch (the restored key/counts/
        DFA row make that continuation byte-identical to the
        never-preempted run)."""
        rec = res.rec
        slot = res.slot
        # One invariant owner for the table row: alloc(0) extra pages,
        # head_pages = everything we restored.
        self._assign_slot_pages(slot, len(res.pages),
                                head_pages=res.pages)
        st = _Slot(request=rec.request, num_prompt=rec.num_prompt,
                   generated=list(rec.generated),
                   num_emitted=rec.num_emitted,
                   first_token_time=rec.first_token_time,
                   draft_synced=False, spec_ok=False,
                   logprobs=list(rec.logprobs), stop_col=rec.stop_col,
                   dead_len=rec.dead_len, seed=rec.seed)
        self._slot_gen[slot] += 1
        self._slots[slot] = st
        self._lengths[slot] = rec.length
        self._last_token[slot] = rec.last_token
        self.metrics.num_requests_waiting.inc(-1)
        self.metrics.num_requests_running.set(len(self._slots))
        self.metrics.preempt_swap_seconds.observe(
            time.monotonic() - res.t0)
        self.trace.evt(rec.request.request_id, "park.preempt", "E")
        log.info("resumed %s after preempt swap (slot %d, %d pages)",
                 rec.request.request_id, slot, len(res.pages))

    def _finish_swapped_abort(self, rec: _SwapRecord) -> None:
        """Terminal abort for a preempted victim (client went away while
        its state was off-device)."""
        self.metrics.num_requests_waiting.inc(-1)
        self._unpin_guide(rec.request)
        self._deliver(rec.request, RequestOutput(
            request_id=rec.request.request_id, token_ids=[],
            finished=True, finish_reason="abort",
            num_prompt_tokens=rec.num_prompt,
            num_generated_tokens=len(rec.generated)))

    def _abort_swapped(self) -> None:
        """Fail every preempted-but-unresumed victim (engine exit /
        blanket abort) and release their host bytes."""
        for sw in self._swap_pending:
            self._finish_swapped_abort(sw.rec)
        self._swap_pending = []
        for rid, rec in list(self._swapped.items()):
            if self._swap is not None:
                self._swap.discard(rid)
            self._finish_swapped_abort(rec)
        self._swapped.clear()
        if self._swap is not None:
            self._swap.clear()
            self.metrics.prefix_cache_usage_bytes.set(0, tier="swap")

    # ------------------------------------------------------------------
    # Multi-model serving (engine.model_pool)
    # ------------------------------------------------------------------

    def served_models(self) -> list[str]:
        """Model names this engine can serve: the primary plus every pool
        registration (the openai server routes the request's ``model``
        field against this)."""
        names = [self._primary_model]
        if self.pool is not None:
            names += [n for n in self.pool.names() if n not in names]
        return names

    def register_model(self, model, model_path: str | None = None,
                       pinned: bool = False) -> None:
        """Register a secondary model with the shared pool.  ``model`` is
        a config name (models.get_config) or a ModelConfig.  The default
        loader streams real weights from ``model_path`` when present
        (weights.load_params_streaming — async per-leaf H2D puts, safe
        under a live engine) and otherwise falls back to the SAME
        deterministic random init a single-model engine of this config
        would boot with (PRNGKey(ecfg.seed), same quantize/shard steps) —
        which is what makes pooled token streams byte-identical to
        single-model baselines.  Secondary models share the engine's
        tokenizer; register models with a foreign tokenizer on their own
        engine instead."""
        if self.pool is None:
            raise RuntimeError("engine has no model pool")
        if self.dispatcher is not None:
            raise RuntimeError("multi-model serving is single-host only")
        if self._pp > 1:
            raise RuntimeError(
                "multi-model serving is unsupported under pipeline_parallel")
        from arks_tpu.models import get_config
        cfg2 = get_config(model) if isinstance(model, str) else model
        ecfg = self._primary_ecfg

        def loader(cfg2=cfg2, model_path=model_path):
            from arks_tpu.models import weights as wmod
            dtype = jnp.dtype(ecfg.dtype or cfg2.dtype)
            if wmod.weights_kind(model_path) is not None:
                return wmod.load_params_streaming(
                    cfg2, model_path, mesh=self.mesh, dtype=dtype,
                    weight_dtype=ecfg.weight_dtype)
            from arks_tpu.models.quant import weight_bits
            wbits = weight_bits(ecfg.weight_dtype)
            if wbits:
                from arks_tpu.models import quant
                shards = (self.mesh.shape.get(tf.AXIS_MODEL, 1)
                          if self.mesh is not None else 1)
                params = quant.init_params_quantized(
                    cfg2, jax.random.PRNGKey(ecfg.seed), dtype,
                    bits=wbits, shards=shards)
            else:
                params = tf.init_params(
                    cfg2, jax.random.PRNGKey(ecfg.seed), dtype)
            if self.mesh is not None:
                params = tf.shard_params(params, cfg2, self.mesh)
            return params

        self.pool.register(cfg2.name, cfg2, model_path=model_path,
                           loader=loader, pinned=pinned)

    def _update_parked(self) -> None:
        """Refresh the requests_parked{reason} gauges from the park lists
        themselves — one authoritative setter instead of inc/dec pairs
        scattered across every park/unpark/abort path."""
        m = self.metrics.requests_parked
        m.set(len(self._awaiting_guide), reason="guide")
        m.set(len([r for r in self._awaiting_restore
                   if not isinstance(r, _ResumeState)]), reason="restore")
        m.set(len(self._awaiting_model), reason="model")
        # Preempted victims: spill in flight, parked in host RAM, or
        # restoring back into a slot.  Set-from-len keeps the gauge
        # non-negative across any abort interleaving (the regression in
        # tests/test_preempt.py).
        m.set(len(self._swap_pending) + len(self._swapped)
              + len([r for r in self._awaiting_restore
                     if isinstance(r, _ResumeState)]), reason="preempt")

    def _park_awaiting_model(self, req: Request, want: str) -> None:
        """Park a request until its model is active (mirrors the guide /
        restore parks: waiting gauge held up, abortable, failed on engine
        exit).  Requests for unknown models — or on engines that cannot
        switch (no pool, multi-host gang) — fail immediately instead."""
        if (self.pool is None or self.dispatcher is not None
                or not (want == self._primary_model or self.pool.has(want))):
            error = ("model_not_found" if self.pool is not None
                     and self.dispatcher is None else "multi_model_unsupported")
            self._deliver(req, RequestOutput(
                request_id=req.request_id, token_ids=[], finished=True,
                finish_reason="error", error=error,
                num_prompt_tokens=len(req.prompt_ids)))
            log.info("rejected %s: %s (model=%r)", req.request_id, error, want)
            return
        self._awaiting_model.append((req, want, time.monotonic()))
        self.metrics.num_requests_waiting.inc(1)
        self.trace.evt(req.request_id, "park.model", "B", want)
        self._switch_t0.setdefault(want, time.monotonic())
        self._update_parked()

    def _abort_awaiting_model(self) -> None:
        """Fail every model-parked request (engine exit / blanket abort):
        no scheduler remains to switch models for them."""
        for req, _want, _t in self._awaiting_model:
            self.metrics.num_requests_waiting.inc(-1)
            self._deliver(req, RequestOutput(
                request_id=req.request_id, token_ids=[], finished=True,
                finish_reason="abort", num_prompt_tokens=len(req.prompt_ids)))
        self._awaiting_model = []
        self._update_parked()

    def _fail_parked_for(self, want: str, error: str) -> None:
        """Fail the parked requests waiting on ``want`` (load failure or
        pool exhaustion); other models' parked requests stay."""
        keep = []
        for req, w, t in self._awaiting_model:
            if w != want:
                keep.append((req, w, t))
                continue
            self.metrics.num_requests_waiting.inc(-1)
            self._fault_counts.pop(req.request_id, None)
            self._deliver(req, RequestOutput(
                request_id=req.request_id, token_ids=[], finished=True,
                finish_reason="error", error=error,
                num_prompt_tokens=len(req.prompt_ids)))
            self.metrics.request_success_total.inc(reason="error")
            log.info("rejected %s: %s", req.request_id, error)
        self._awaiting_model = keep
        self._switch_t0.pop(want, None)
        self._model_loads.pop(want, None)
        if self._switch_target == want:
            self._switch_target = None
        self._update_parked()

    def _switch_due_policy(self, target: str) -> bool:
        """May a switch to ``target`` be COMMITTED now?  drain: as soon as
        the target is ready (in-flight work still runs to completion —
        slots are never preempted).  timeslice: once the active model has
        had its quantum, or has no runnable work left."""
        if self._switch_policy == "drain":
            return True
        return (time.monotonic() - self._slice_t0 >= self._switch_quantum
                or (not self._slots and not self._prefilling
                    and not self._pending_admits and self._queue.empty()))

    def _drained_for_switch(self) -> bool:
        """A switch swaps the per-model context wholesale, which is only
        legal when every mutable scheduling member is at its empty state:
        no slots, prefills, deferred admits, pipelined dispatches,
        in-flight spills/restores, or queued admissions (a committed
        target parks the queue through _preadmit first).  Guide-parked
        requests are re-parked by _switch_to itself."""
        return (not self._slots and not self._prefilling
                and not self._pending_admits and not self._pipe_inflight
                and not self._awaiting_restore and not self._spills
                and self._queue.empty()
                # The pipe-warmup thread writes per-model attrs through
                # ``self``; switching mid-compile would graft this model's
                # executables into the next model's context.
                and self._pipe_warm_state != "compiling")

    def _issue_model_load(self) -> bool:
        """Service the awaiting_model park: consume aborts, kick/poll the
        head-of-line model's background load (pool.ensure — NON-blocking;
        the weights stream on the pool's loader thread as async H2D
        puts), commit a switch target per policy, drain the admission
        queue into parks once committed, and execute the switch at the
        drained boundary.  Never blocks the engine thread."""
        worked = False
        with self._abort_lock:
            dead = {req.request_id for req, _, _ in self._awaiting_model
                    if req.request_id in self._aborted}
            self._aborted -= dead
        if dead:
            keep = []
            for req, want, t in self._awaiting_model:
                if req.request_id not in dead:
                    keep.append((req, want, t))
                    continue
                self.metrics.num_requests_waiting.inc(-1)
                self._deliver(req, RequestOutput(
                    request_id=req.request_id, token_ids=[], finished=True,
                    finish_reason="abort",
                    num_prompt_tokens=len(req.prompt_ids)))
            self._awaiting_model = keep
            self._update_parked()
            worked = True
        # Cold-start prefetch hints from add_request: kick the load while
        # the demanding request is still QUEUED behind busy slots.  Errors
        # are deliberately dropped here — they surface with full reporting
        # when the request parks and the head-of-line path re-ensures.
        while self._model_prefetch:
            name = self._model_prefetch.pop()
            if name == self.cfg.name or not self.pool.has(name):
                continue
            try:
                got = self.pool.ensure(name)
            except (KeyError, PoolFullError):
                continue
            if isinstance(got, LoadTicket) and name not in self._model_loads:
                self._model_loads[name] = got
                self._switch_t0.setdefault(name, got.t0)
                self._switch_stats = {"dispatches": 0, "max_depth": 0}
                worked = True
        if not self._awaiting_model:
            self._switch_target = None
            for name, t in list(self._model_loads.items()):
                if t.event.is_set():
                    self._model_loads.pop(name, None)
                    self._switch_t0.pop(name, None)
            return worked
        target = self._switch_target or self._awaiting_model[0][1]
        if target == self.cfg.name:
            # The target became active (or a stale commit cleared) while
            # these requests were parked: release them back to the queue.
            self._switch_target = None
            self._unpark_for(target)
            return True
        try:
            got = self.pool.ensure(target)
        except KeyError as e:
            self._fail_parked_for(target, f"model_not_found: {e}")
            return True
        except PoolFullError as e:
            self._fail_parked_for(target, f"model_pool_exhausted: {e}")
            return True
        resident = not isinstance(got, LoadTicket)
        if not resident:
            if target not in self._model_loads:
                # Fresh load kicked: reset the overlap accounting (full
                # depth during the load window).
                self._model_loads[target] = got
                self._switch_t0.setdefault(target, got.t0)
                self._switch_stats = {"dispatches": 0, "max_depth": 0}
                worked = True
            if got.event.is_set():
                self._model_loads.pop(target, None)
                if got.error:
                    code = ("model_pool_exhausted"
                            if "model_pool_exhausted" in got.error
                            else "model_load_failed")
                    self._fail_parked_for(target, f"{code}: {got.error}")
                    return True
                resident = True
        else:
            self._model_loads.pop(target, None)
        if not resident:
            return worked
        if (self._switch_target is None and not self._resize_active
                and self._switch_due_policy(target)):
            self._switch_target = target
            worked = True
        if self._switch_target != target:
            return worked
        # Drain the admission queue through _preadmit: with a committed
        # target every popped request parks (for its own model), so the
        # queue empties instead of deadlocking the drained check below.
        while True:
            try:
                _, _, req = self._queue.get_nowait()
            except queue.Empty:
                break
            pre = self._preadmit(req)
            if pre is not None:
                self._resolve_admit_batch(self._issue_admit_batch(
                    [pre], pre[0].params.logprobs is not None))
            worked = True
        if self._drained_for_switch() and not self._resize_active:
            self._flush_deferred()   # the switch takes seconds
            self._switch_to(target)
            worked = True
        return worked

    def _unpark_for(self, name: str) -> None:
        """Re-queue every parked request waiting on ``name`` (the waiting
        gauge stays up — it was raised at park and _preadmit lowers it,
        matching the guide-unpark discipline)."""
        keep = []
        for req, want, t in self._awaiting_model:
            if want != name:
                keep.append((req, want, t))
                continue
            with self._abort_lock:
                self._queued_rids.add(req.request_id)
                self._queue_seq += 1
                seq = self._queue_seq
            self._queue.put((req.params.priority, seq, req))
            self.trace.evt(req.request_id, "park.model", "E")
        self._awaiting_model = keep
        self._switch_t0.pop(name, None)
        self._update_parked()

    def _switch_fault(self, name: str, e: Exception) -> StepFault:
        """Build the StepFault for a failed switch — callers raise it so
        the routing is visible at the fault site (test_fault_guard).  The
        requests parked for the target are BOTH the culprits (their retry
        budget burns — over budget they quarantine alone) and the
        survivors (nothing was emitted, so recovery plain-requeues them
        and the switch retries on re-park)."""
        self._switch_target = None
        self._switch_t0.pop(name, None)
        survivors, keep = [], []
        for req, want, t in self._awaiting_model:
            if want != name:
                keep.append((req, want, t))
                continue
            self.metrics.num_requests_waiting.inc(-1)
            survivors.append(_Survivor(
                request=req, seed=self._resolve_seed(req),
                num_prompt=len(req.prompt_ids)))
        self._awaiting_model = keep
        self._update_parked()
        return StepFault("model_switch", faults_mod.classify(e),
                         culprits=[sv.request.request_id for sv in survivors],
                         survivors=survivors)

    def _switch_to(self, name: str) -> None:
        """Activate pool model ``name`` at a fully drained boundary: save
        the active model's context (every _model_attr_names attribute,
        wholesale — caches, mirrors, guide registry, compiled programs),
        then restore ``name``'s saved context or build a fresh one from
        the pool's (already device-resident) weights.  A warm switch
        compiles NOTHING — program shapes are per-context and cached
        executables ride the context swap; that is what keeps the compile
        budget flat when the second model comes online."""
        t0 = time.monotonic()
        old = self.cfg.name
        try:
            self._faults.fire("model_switch")
            entry = self.pool.acquire(name)
        except Exception as e:
            raise self._switch_fault(name, e) from e
        # Guide-parked requests belong to the OLD model's compiler: re-park
        # them on the model itself so they re-admit (and re-ensure their
        # guide) after a switch back, instead of stranding inside a saved
        # context nothing ever services.  Waiting gauge: both parks hold
        # +1, so the move is gauge-neutral.
        for req, _ticket in self._awaiting_guide:
            self._awaiting_model.append((req, old, time.monotonic()))
        self._awaiting_guide = []
        ctx = {a: getattr(self, a) for a in self._model_attr_names}
        try:
            saved = self._model_ctxs.pop(name, None)
            if saved is not None:
                for a, v in saved.items():
                    setattr(self, a, v)
                # The pool may have reloaded the weights since an eviction
                # dropped this context: trust the pool's params.
                self.params = entry.params
            elif name == self._primary_model:
                ecfg2 = self._primary_ecfg
                dname = ecfg2.draft_model
                dcfg = self.pool.entry(dname).cfg if dname else None
                dparams = self.pool.params_of(dname) if dname else None
                self._init_model_state(entry.cfg, ecfg2, params=entry.params,
                                       draft_params=dparams, draft_cfg=dcfg)
            else:
                # Secondary models run without their own draft (the spec
                # draft rides the primary's context).
                ecfg2 = dataclasses.replace(self._primary_ecfg, model=name,
                                            draft_model=None)
                self._init_model_state(entry.cfg, ecfg2, params=entry.params)
        except Exception as e:
            # Restore the old context before faulting so recovery rebuilds
            # a coherent (old-model) device state.
            for a, v in ctx.items():
                setattr(self, a, v)
            self.pool.release(name)
            raise self._switch_fault(name, e) from e
        self._model_ctxs[old] = ctx
        self.pool.release(old)
        self._switch_target = None
        self._slice_t0 = time.monotonic()
        dt = time.monotonic() - self._switch_t0.pop(name, t0)
        self.metrics.model_switch_seconds.observe(dt)
        self.last_switch_stats = {
            "model": name, "from": old, "seconds": dt,
            "overlap_dispatches": self._switch_stats["dispatches"],
            "overlap_max_depth": self._switch_stats["max_depth"],
        }
        self.metrics.engine_config_info.set(1, **self.resolved_config)
        self._emit("model_switch", model=name)
        log.info("model switch %s -> %s in %.3fs (overlap: %d dispatches, "
                 "max pipeline depth %d)", old, name, dt,
                 self._switch_stats["dispatches"],
                 self._switch_stats["max_depth"])
        self._unpark_for(name)

    # ------------------------------------------------------------------
    # Elastic parallelism: live topology resize + scale-from-zero
    # ------------------------------------------------------------------
    # A serving engine changes shape without dropping a byte of any
    # stream.  The resize state machine rides the step loop:
    #
    #   drain    — every decoding slot is preempted to the host with the
    #              PR-11 swap machinery (full KV pages + sampler row) or
    #              re-queued for deterministic replay (the PR-4/PR-7
    #              fallback-matrix rows: guided, spec, residency-engaged);
    #              new admissions and swap resumes are gated while
    #              in-flight spills/restores/admits run dry.
    #   reshard  — a per-leaf device_put plan (weights.reshard_plan)
    #              moves the CURRENT params onto the new mesh; no
    #              checkpoint reload, no weight re-init.
    #   resume   — _init_model_state rebuilds the per-model context at
    #              the new shape while keep_tiers carries the host/disk
    #              prefix tiers, the SwapStore, and the swapped victims
    #              across verbatim (their blocks are full logical host
    #              arrays keyed by a layout epoch that excludes the mesh
    #              shape), the sketch epoch bumps so routers drop the
    #              pre-resize membership exactly once, and a warm-up
    #              request compiles the new shape's programs before the
    #              first real token rides them.
    #
    # Each seam is a "resize" chaos phase fire site: a fault at drain or
    # reshard recovers at the OLD shape (the context swap has not
    # committed), one at resume recovers at the NEW shape — in both
    # cases the preempted streams were already host-side in
    # layout-independent form, so recovery replays them with nobody
    # quarantined (_phase_culprits returns () for "resize").
    #
    # Scale-to-zero disarms a fully idle engine: weights and device KV
    # drop (the pool remembers nbytes, so re-arm makes room before
    # streaming), host/disk prefix tiers stay warm, and the first queue
    # arrival — or a posted resize — re-arms via _step_disarmed.

    def request_resize(self, tensor_parallel: int | None = None,
                       data_parallel: int | None = None) -> "_ResizeRequest":
        """Post a live topology resize (any thread).  Returns the request
        holder; ``holder.wait(timeout)`` blocks until the step loop
        finishes it and ``holder.outcome`` is "ok" / "rejected" /
        "error".  Validation beyond cheap shape checks happens on the
        engine thread (_resize_reject_reason) where the scheduler state
        is coherent."""
        tp = self._mesh_tp() if tensor_parallel is None else tensor_parallel
        dp = self._mesh_dp() if data_parallel is None else data_parallel
        if tp < 1 or dp < 1:
            raise ValueError(f"resize to tp={tp} dp={dp}: shapes must be >= 1")
        if self._resize_req is not None:
            raise RuntimeError("a resize is already in flight")
        req = _ResizeRequest(tensor_parallel=tp, data_parallel=dp)
        self._resize_req = req
        self._rearm_wake.set()   # a disarmed engine's backoff wait ends now
        return req

    def set_rearm_loader(self, fn) -> None:
        """Install the scale-from-zero weight source: ``fn(cfg, mesh) ->
        params`` (typically a closure over weights.load_orbax_streaming,
        so re-arm streams the checkpoint host->device without a full
        host-tree materialization).  Without one, re-arm re-initializes
        from the engine seed — deterministic, which is what the tests
        ride, but not the served checkpoint."""
        self._rearm_loader = fn

    @property
    def armed(self) -> bool:
        """False while scaled to zero (no device state exists)."""
        return self._armed

    def elastic_status(self) -> dict:
        """Operator/readiness snapshot of the elastic state (any
        thread; plain attribute reads)."""
        req = self._resize_req
        return {
            "armed": self._armed,
            "shape": self._mesh_shape_str(),
            "resize_inflight": req is not None,
            "last_resize": self.last_resize_stats,
            "last_rearm": self.last_rearm_stats,
        }

    def _mesh_tp(self) -> int:
        return self.mesh.shape.get(tf.AXIS_MODEL, 1) if self.mesh is not None else 1

    def _mesh_dp(self) -> int:
        return self.mesh.shape.get("data", 1) if self.mesh is not None else 1

    def _mesh_shape_str(self) -> str:
        return f"tp{self._mesh_tp()}xdp{self._mesh_dp()}"

    def _service_elastic(self) -> bool:
        """Step-loop elastic hook: progress a posted resize, else check
        the idle scale-to-zero window.  Engine thread only."""
        if self._resize_req is not None:
            return self._service_resize()
        return self._maybe_scale_to_zero()

    def _resize_reject_reason(self, req: "_ResizeRequest") -> str | None:
        """Why this engine cannot live-resize to the requested shape
        (docs/application-usage.md carries the fallback matrix), or None
        when it can."""
        tp, dp = req.tensor_parallel, req.data_parallel
        if self._pp > 1:
            return "pipeline_parallel engines cannot live-resize"
        if self._cp > 1:
            return "context_parallel engines cannot live-resize"
        if self.mesh is not None and self.mesh.shape.get("slice", 1) > 1:
            return "multi-slice engines cannot live-resize"
        if self.dispatcher is not None:
            return "multi-host gang engines cannot live-resize"
        if self._draft_cfg is not None and dp > 1:
            return "speculative engines require data_parallel == 1"
        ndev = len(jax.devices())
        if tp * dp > ndev:
            return f"tp*dp={tp * dp} exceeds {ndev} visible devices"
        return None

    def _service_resize(self) -> bool:
        """One step of the resize state machine: validate/activate, then
        drain (evict every classic decode slot to the host), then
        execute at the drained boundary.  Never blocks — partial drains
        return and the next step continues."""
        req = self._resize_req
        if not req.active:
            if (req.tensor_parallel == self._mesh_tp()
                    and req.data_parallel == self._mesh_dp()):
                # Already at the requested shape: trivially complete.
                self._finish_resize(req, "ok")
                return True
            err = self._resize_reject_reason(req)
            if err is not None:
                self.metrics.engine_resizes_total.inc(
                    1, mode="resize", outcome="rejected")
                log.warning("resize to tp=%d dp=%d rejected: %s",
                            req.tensor_parallel, req.data_parallel, err)
                req.error = err
                self._finish_resize(req, "rejected")
                return True
            if self._switch_target is not None or self._awaiting_model:
                # A model switch is in flight: let it land first (the
                # resize would otherwise race its drained boundary).
                return False
            req.active = True
            req.drain_t0 = time.monotonic()
            self._resize_active = True
            log.info("resize %s -> tp%dxdp%d: draining %d slots",
                     self._mesh_shape_str(), req.tensor_parallel,
                     req.data_parallel, len(self._slots))
        worked = False
        if self._pipe_inflight or self._pipe_state is not None:
            self._pipe_drain()
            worked = True
        worked = self._resize_evict_slots() or worked
        if not self._drained_for_resize():
            return worked
        self._flush_deferred()   # the reshard takes seconds
        self._execute_resize(req)
        return True

    def _resize_evict_slots(self) -> bool:
        """Evict every classic decode slot for the drain: swap-capable
        victims take the full-KV swap path (resume is byte-identical by
        the PR-5 round-trip argument), the fallback-matrix rows (guided
        — their saved DFA row indexes the OLD compiler's registry, which
        the rebuild discards —, spec engines, replaying/resuming
        streams, swap-incapable engines) re-queue for deterministic
        replay.  Residency-engaged slots finish in place: their KV is
        split across host store + staging + tail with no single page
        list to gather."""
        did = False
        for slot in list(self._slots):
            st = self._slots.get(slot)
            if st is None:
                continue
            if self._residency is not None and slot in self._residency.slots:
                continue
            rid = st.request.request_id
            use_swap = (self._preempt_swap_capable()
                        and st.request.params.guide is None
                        and bool(self._slot_pages.get(slot))
                        and rid not in self._replaying
                        and rid not in self._resuming)
            if use_swap:
                self._issue_preempt_swap(slot)
            else:
                self._preempt_replay(slot)
            did = True
        if did:
            self._update_parked()
        return did

    def _drained_for_resize(self) -> bool:
        """The resize boundary: like _drained_for_switch but the
        admission queue MAY be non-empty (queued requests simply admit
        at the new shape) and the host-side swap machinery must also be
        quiet — in-flight D2H swap harvests and restore scatters
        reference the old cache's device buffers."""
        return (not self._slots and not self._prefilling
                and not self._pending_admits and not self._pipe_inflight
                and self._pipe_state is None
                and not self._awaiting_restore and not self._spills
                and not self._swap_pending and not self._awaiting_fetch
                and self._pipe_warm_state != "compiling")

    def _requeue_awaiting_guide(self) -> None:
        """Re-queue guide-parked requests before a context rebuild:
        their CompileTickets belong to the compiler the rebuild
        discards; on re-admission they re-ensure (and re-pin) against
        the fresh one.  Gauge-neutral: the park holds waiting +1 and
        _preadmit lowers it, same as _unpark_for."""
        for req, _ticket in self._awaiting_guide:
            with self._abort_lock:
                self._queued_rids.add(req.request_id)
                self._queue_seq += 1
                seq = self._queue_seq
            self._queue.put((req.params.priority, seq, req))
            self.trace.evt(req.request_id, "park.guide", "E")
        self._awaiting_guide = []
        self._update_parked()

    def _snapshot_tiers(self) -> dict:
        """The keep_tiers dict for an elastic _init_model_state rebuild:
        the host/disk prefix tiers, their worker threads + queues, and
        the swap store with its parked victims — everything whose state
        is mesh-shape-independent host data that must survive the new
        topology verbatim."""
        return {
            "host": self._host,
            "disk": self._disk,
            "disk_write_queue": self._disk_write_queue,
            "disk_writer": self._disk_writer,
            "fetch_queue": self._fetch_queue,
            "disk_stats_lock": self._disk_stats_lock,
            "disk_evict_seen": self._disk_evict_seen,
            "disk_corrupt_seen": self._disk_corrupt_seen,
            "swap": self._swap,
            "swapped": self._swapped,
        }

    def _new_mesh_for(self, tp: int, dp: int):
        """The resize target mesh over an explicit device prefix —
        resolve_plan requires the plan to cover its device list exactly,
        so scaling BELOW the full host passes jax.devices()[:tp*dp].
        tp*dp == 1 -> no mesh (the single-chip path)."""
        if tp * dp == 1:
            return None
        from arks_tpu.parallel.mesh import make_mesh
        return make_mesh(tensor_parallel=tp, data_parallel=dp,
                         devices=jax.devices()[: tp * dp])

    def _execute_resize(self, req: "_ResizeRequest") -> None:
        """The drained-boundary commit: reshard params onto the new
        mesh, rebuild the per-model context at the new shape with the
        prefix/swap tiers carried across, bump the sketch epoch, and
        issue the warm-up request.  Fault seams: drain (before the
        reshard), reshard (after the device_put plan ran), resume (after
        the commit) — the first two roll back to the old shape before
        raising, the last recovers at the new one."""
        t0 = time.monotonic()
        tp, dp = req.tensor_parallel, req.data_parallel
        cfg = self.cfg
        draft_cfg = self._draft_cfg
        old_mesh = self.mesh
        old_shape = self._mesh_shape_str()
        n_swapped = len(self._swapped)
        try:
            self._faults.fire("resize")                      # drain seam
            new_mesh = self._new_mesh_for(tp, dp)
            from arks_tpu.models import weights as weights_mod
            new_params = weights_mod.reshard_params_to_mesh(
                cfg, self.params, new_mesh)
            new_draft = None
            if draft_cfg is not None and self._draft_params is not None:
                new_draft = weights_mod.reshard_params_to_mesh(
                    draft_cfg, self._draft_params, new_mesh)
            self._faults.fire("resize")                      # reshard seam
        except Exception as e:
            self._finish_resize(req, "error", e)
            if isinstance(e, StepFault):
                raise
            raise StepFault("resize", faults_mod.classify(e)) from e
        self._requeue_awaiting_guide()
        keep = self._snapshot_tiers()
        ctx = {a: getattr(self, a) for a in self._model_attr_names}
        ecfg2 = dataclasses.replace(self.ecfg, tensor_parallel=tp,
                                    data_parallel=dp)
        self.mesh = new_mesh
        try:
            self._init_model_state(cfg, ecfg2, params=new_params,
                                   draft_params=new_draft,
                                   draft_cfg=draft_cfg, keep_tiers=keep)
        except Exception as e:
            # Roll back to a coherent old-shape context before faulting
            # so recovery rebuilds the device state we still have.
            for a, v in ctx.items():
                setattr(self, a, v)
            self.mesh = old_mesh
            self._finish_resize(req, "error", e)
            raise StepFault("resize", faults_mod.classify(e)) from e
        # Committed: saved per-model contexts reference the OLD mesh's
        # buffers — drop them (a later switch re-inits from the pool).
        self._model_ctxs.clear()
        if self.pool is not None:
            self.pool.adopt(cfg.name, cfg, self.params, pinned=True)
            if draft_cfg is not None and self._draft_params is not None:
                self.pool.adopt(draft_cfg.name, draft_cfg,
                                self._draft_params, pinned=True)
        self._primary_ecfg = dataclasses.replace(
            self._primary_ecfg, tensor_parallel=tp, data_parallel=dp)
        if self._sketch is not None:
            # Routers drop the pre-resize membership exactly once on
            # their next poll (the tier-0 index restarted empty).
            self._sketch.bump_epoch("resize")
        try:
            self._faults.fire("resize")                      # resume seam
        except Exception as e:
            self._finish_resize(req, "error", e)
            raise StepFault("resize", faults_mod.classify(e)) from e
        dt = time.monotonic() - t0
        drain_s = t0 - (req.drain_t0 or t0)
        self.metrics.resize_seconds.observe(dt + drain_s)
        self.metrics.engine_resizes_total.inc(1, mode="resize", outcome="ok")
        self.metrics.engine_config_info.set(1, **self.resolved_config)
        self.last_resize_stats = {
            "from": old_shape, "to": self._mesh_shape_str(),
            "drain_seconds": drain_s, "reshard_seconds": dt,
            "seconds": drain_s + dt, "swapped": n_swapped,
        }
        self._issue_warmup_request()
        self._finish_resize(req, "ok")
        log.info("resized %s -> %s in %.3fs (drain %.3fs, %d streams "
                 "swapped to host)", old_shape, self._mesh_shape_str(),
                 drain_s + dt, drain_s, n_swapped)

    def _finish_resize(self, req: "_ResizeRequest", outcome: str,
                       error: Exception | None = None) -> None:
        """Close out a resize request (every terminal path): record the
        outcome, clear the admission gate, and wake waiters."""
        req.outcome = outcome
        if error is not None:
            req.error = f"{type(error).__name__}: {error}"
        req.seconds = time.monotonic() - req.t0
        self._resize_req = None
        self._resize_active = False
        req.event.set()

    # ---- scale-to-zero / re-arm --------------------------------------

    def _maybe_scale_to_zero(self) -> bool:
        """Track the idle window (ARKS_ELASTIC_IDLE_ZERO_S) and disarm
        once the engine has been COMPLETELY quiet — no parked work, no
        in-flight spills, no background model loads — for the full
        window."""
        quiet = (self.idle and not self._pipe_inflight
                 and self._pipe_state is None and not self._spills
                 and not self._disk_spill_pending and not self._model_loads
                 and self._pipe_warm_state != "compiling"
                 and self._state == "serving")
        if not quiet:
            self._idle_since = None
            return False
        now = time.monotonic()
        if self._idle_since is None:
            self._idle_since = now
            return False
        if now - self._idle_since < self._idle_zero_s:
            return False
        self._scale_to_zero()
        return True

    def _scale_to_zero(self) -> None:
        """Disarm an idle engine: flush warm device prefixes to the disk
        tier (best-effort), drop weights + device KV + sampler state,
        and release the pool residency.  Host/disk prefix tiers stay
        warm; every per-model attribute stays PRESENT (the context
        contract) — re-arm rebuilds the device side via
        _init_model_state(keep_tiers=...)."""
        if self._disk is not None:
            try:
                self._resolve_zero_flush()
            except Exception as e:
                faults_mod.swallowed("scale_to_zero.flush", e)
        self.params = None
        self._cache = None
        self._sampling = None
        self._draft_params = None
        self._draft_cache = None
        # The device prefix index died with the cache: drop the allocator
        # so cache_sketch stops advertising tier-0 membership this
        # replica can no longer serve (host/disk stay advertised — peers
        # may still pull warm blocks from a scaled-to-zero replica).
        self._alloc = None
        self._tables = None
        self._model_ctxs.clear()
        if self.pool is not None:
            try:
                self.pool.scale_to_zero(self.cfg.name)
                if self._draft_cfg is not None:
                    self.pool.scale_to_zero(self._draft_cfg.name)
            except RuntimeError as e:
                faults_mod.swallowed("scale_to_zero.pool", e)
        if self._sketch is not None:
            self._sketch.bump_epoch("scale_to_zero")
        self._armed = False
        self._zero_t0 = time.monotonic()
        self._idle_since = None
        self.metrics.engine_resizes_total.inc(
            1, mode="scale_to_zero", outcome="ok")
        log.info("idle %.0fs: scaled to zero (weights + device KV dropped; "
                 "host/disk prefix tiers stay warm)", self._idle_zero_s)

    def _resolve_zero_flush(self) -> None:
        """Host-sync tail of scale-to-zero: D2H-read the warm device
        blocks into the disk tier before the cache drops.  Runs at a
        fully drained boundary (idle engine, no in-flight streams) —
        the sanctioned _resolve_* sync-tail contract, same as the
        spill/restore resolves."""
        self._flush_warm_to_disk()

    def _step_disarmed(self, block_s: float) -> bool:
        """The step loop while scaled to zero: wait for demand (a queue
        arrival) or a posted resize, then re-arm.  A failed re-arm backs
        off one second and retries on the next demand signal — the
        engine stays disarmed rather than crash-looping the step
        thread."""
        if self._resize_req is not None:
            req = self._resize_req
            err = self._resize_reject_reason(req)
            if err is not None:
                self.metrics.engine_resizes_total.inc(
                    1, mode="resize", outcome="rejected")
                req.error = err
                self._finish_resize(req, "rejected")
                return True
            ok = self._rearm(shape=(req.tensor_parallel, req.data_parallel),
                             resize_req=req)
            return True if ok else False
        try:
            prio, seq, demand = self._queue.get(timeout=block_s)
        except queue.Empty:
            return False
        if time.monotonic() - self._rearm_fail_t < 1.0:
            # Recent re-arm failure: put the demand back and pace the
            # retry on the wake event instead of hot-spinning — a
            # posted resize (request_resize sets the event) interrupts
            # the backoff immediately.
            self._queue.put((prio, seq, demand))
            self._rearm_wake.wait(min(block_s, 0.1))
            self._rearm_wake.clear()
            return False
        self._rearm()
        # Re-queue the demand that woke us at its own priority — whether
        # or not the re-arm succeeded (on failure it simply waits for
        # the next attempt's window).
        with self._abort_lock:
            self._queued_rids.add(demand.request_id)
            self._queue_seq += 1
            seq2 = self._queue_seq
        self._queue.put((prio, seq2, demand))
        return True

    def _rearm(self, shape: tuple[int, int] | None = None,
               resize_req: "_ResizeRequest | None" = None) -> bool:
        """Scale from zero: stream the weights back (the installed
        re-arm loader, typically Orbax streaming — or a deterministic
        seed re-init without one) and rebuild the device context at the
        current (or requested) shape, with the warm host/disk tiers and
        any swapped victims carried across.  Rolls the context back and
        stays disarmed on failure."""
        t0 = time.monotonic()
        cfg = self.cfg
        draft_cfg = self._draft_cfg
        keep = self._snapshot_tiers()
        ctx = {a: getattr(self, a) for a in self._model_attr_names}
        old_mesh = self.mesh
        ecfg2 = self.ecfg
        try:
            if shape is not None:
                tp, dp = shape
                ecfg2 = dataclasses.replace(self.ecfg, tensor_parallel=tp,
                                            data_parallel=dp)
                self.mesh = self._new_mesh_for(tp, dp)
            params = None
            if self._rearm_loader is not None:
                params = self._rearm_loader(cfg, self.mesh)
            self._init_model_state(cfg, ecfg2, params=params,
                                   draft_cfg=draft_cfg, keep_tiers=keep)
        except Exception as e:
            for a, v in ctx.items():
                setattr(self, a, v)
            self.mesh = old_mesh
            self._rearm_fail_t = time.monotonic()
            self.metrics.engine_resizes_total.inc(
                1, mode="rearm", outcome="error")
            if resize_req is not None:
                self._finish_resize(resize_req, "error", e)
            log.error("scale-from-zero re-arm failed: %s: %s",
                      type(e).__name__, e)
            # Intentional swallow: the engine stays DISARMED and retries
            # on the next demand signal — a re-arm failure must not take
            # down the step thread of a replica that is serving nothing.
            faults_mod.swallowed("elastic.rearm", e)
            return False
        self._armed = True
        self._idle_since = None
        if self.pool is not None:
            self.pool.adopt(cfg.name, cfg, self.params, pinned=True)
            if draft_cfg is not None and self._draft_params is not None:
                self.pool.adopt(draft_cfg.name, draft_cfg,
                                self._draft_params, pinned=True)
        if shape is not None:
            self._primary_ecfg = dataclasses.replace(
                self._primary_ecfg, tensor_parallel=shape[0],
                data_parallel=shape[1])
        if self._sketch is not None:
            self._sketch.bump_epoch("rearm")
        dt = time.monotonic() - t0
        self.metrics.scale_from_zero_seconds.observe(dt)
        self.metrics.engine_resizes_total.inc(1, mode="rearm", outcome="ok")
        self.metrics.engine_config_info.set(1, **self.resolved_config)
        self.last_rearm_stats = {
            "seconds": dt, "shape": self._mesh_shape_str(),
            "idle_seconds": t0 - self._zero_t0,
            "streamed": self._rearm_loader is not None,
        }
        self._issue_warmup_request()
        if resize_req is not None:
            self._finish_resize(resize_req, "ok")
        log.info("re-armed from zero at %s in %.3fs (%s weights)",
                 self._mesh_shape_str(), dt,
                 "streamed" if self._rearm_loader is not None else "re-init")
        return True

    def _issue_warmup_request(self) -> bool:
        """Queue one tiny greedy self-request after a resize/re-arm so
        the new shape's programs compile BEFORE the first real token
        rides them (its output sinks into _WarmupSink — no client).
        Replicates add_request's queue-put bookkeeping only: the full
        add_request path is host-heavy and off the step-reachable
        hot-path budget."""
        if not self._elastic_warmup:
            return False
        self._warmup_seq += 1
        req = Request(
            request_id=f"__warmup__{self._warmup_seq}",
            prompt_ids=[min(3, self.cfg.vocab_size - 1)] * 4,
            params=SamplingParams(max_tokens=2, top_k=1),
            outputs=_WarmupSink())
        self.metrics.num_requests_waiting.inc(1)
        with self._abort_lock:
            self._queued_rids.add(req.request_id)
            self._queue_seq += 1
            seq = self._queue_seq
        self._queue.put((req.params.priority, seq, req))
        return True

    def _admit_prefilled(self, req: Request) -> None:
        """Admit a request whose prefill ran on another engine (disaggregated
        decode side): insert the transferred KV, reconstruct the sampling key
        stream, and continue decoding from the first token."""
        pf = req.prefilled
        if req.params.logprobs is not None and pf.first_lp is None:
            # A logprob request whose transferred state carries no
            # first-token logprob data (pre-upgrade prefill peer): serving
            # a partial stream would be silently wrong — reject cleanly.
            self._unpin_guide(req)
            self._deliver(req, RequestOutput(
                request_id=req.request_id, token_ids=[], finished=True,
                finish_reason="error", error="logprobs_unavailable",
                num_prompt_tokens=pf.num_prompt))
            return
        usable = self.ecfg.max_cache_len - self.ecfg.steps_per_dispatch - 1
        k, v = jnp.asarray(pf.k), jnp.asarray(pf.v)
        if pf.num_prompt > usable:
            self._unpin_guide(req)
            self._deliver(req, RequestOutput(
                request_id=req.request_id, token_ids=[], finished=True,
                finish_reason="abort", num_prompt_tokens=pf.num_prompt))
            return
        if k.shape[2] > self.ecfg.max_cache_len:
            k = k[:, :, : self.ecfg.max_cache_len]
            v = v[:, :, : self.ecfg.max_cache_len]
        p = req.params
        key = sampler_mod.np_prng_key(pf.seed)
        try:
            slot = self._free.pop()
            if self._paged:
                page = self._page_size()
                n_alloc = -(-pf.num_prompt // page)
                row = self._assign_slot_pages(slot, n_alloc)
                # Pad T to a page multiple so the page-insert loop reads
                # whole pages (the tail rows are masked by length).
                pad_t = n_alloc * page - k.shape[2]
                if pad_t > 0:
                    width = [(0, 0)] * 5
                    width[2] = (0, pad_t)
                    k = jnp.pad(k, width)
                    v = jnp.pad(v, width)
                self._emit("insert_pages", k=np.asarray(k), v=np.asarray(v),
                           pages=row.copy(), n_pages=n_alloc)
                self._cache = self._insert_pages_fn(
                    self._cache, k, v, jnp.asarray(row),
                    jnp.asarray(n_alloc, jnp.int32))
            else:
                self._emit("insert_kv", slot=slot, k=np.asarray(k),
                           v=np.asarray(v))
                self._cache = self._insert_fn(self._cache, k, v,
                                              jnp.asarray(slot))
            gid, start = self._guide_cols(p)
            # Refresh the device tables like every other admission path: a
            # guide published (or evicted+repacked) after this step's
            # top-of-loop refresh would otherwise decode against stale
            # device rows (all -1 -> everything masked -> instant eos).
            self._ensure_guides_uploaded()
            # pf.guide_row is RELATIVE to the guide's start state; rebase
            # onto THIS engine's table (compile orders may differ).
            grow = start + pf.guide_row if gid >= 0 else 0
            self._emit("set_slot", slot=slot, temperature=p.temperature,
                       top_p=p.top_p, top_k=p.top_k, seed=pf.seed,
                       presence=p.presence_penalty,
                       frequency=p.frequency_penalty,
                       logit_bias=list(p.logit_bias),
                       min_tokens=p.min_tokens,
                       stop_ids=list(p.stop_token_ids),
                       ignore_eos=p.ignore_eos,
                       num_prompt=pf.num_prompt, guide=gid, guide_row=grow)
            self._apply_set_slot(slot, p, key, True,
                                 num_prompt=pf.num_prompt, guide=gid,
                                 guide_row=grow)
        except Exception as e:
            # The transferred KV lives on the REQUEST (host arrays): the
            # survivor simply re-queues and re-inserts after the reset.
            raise StepFault(
                "admit", faults_mod.classify(e),
                culprits=[req.request_id],
                survivors=[_Survivor(request=req, seed=pf.seed,
                                     num_prompt=pf.num_prompt)]) from e
        self._register_slot(req, slot, pf.first_token, pf.num_prompt,
                            first_lp=pf.first_lp
                            if req.params.logprobs is not None else None,
                            seed=pf.seed)
        if self._paged and self._chunk and pf.prompt_ids:
            # Disaggregated publish: the transferred prefill's pages are
            # now in the pool — register their digests (tier 0, zero
            # cost) and spill them into the host tier, so a decode-side
            # restart (or later eviction) keeps the prefill peer's warm
            # prefixes without another wire transfer.  The spill path
            # reads the pages the insert dispatch just wrote, so the
            # stored bytes are the pool-canonical form (quantization
            # included) — no host-side conversion to drift.
            ids_full = [int(t) for t in pf.prompt_ids]
            pages_row = list(self._slot_pages.get(slot, []))
            self._register_prompt_pages(ids_full, pages_row)
            if self._host_tier_on():
                from arks_tpu.engine.paged import chain_digests
                page = self._page_size()
                nreg = min(len(ids_full) // page, len(pages_row))
                digs = chain_digests(ids_full, page, nreg)
                for d, pg in zip(digs, pages_row[:nreg]):
                    if not self._host.has(d):
                        self._spill_victims.append((d, pg))
                self._spill_flush()

    @staticmethod
    def _lp_entry(clp, vals, lids, n: int):
        """(chosen_logprob, [(token_id, logprob) x min(n, MAX)]) from the
        device outputs of a top_logprobs call."""
        n = min(n, sampler_mod.TOP_LOGPROBS_MAX)
        vals = np.asarray(vals)
        lids = np.asarray(lids)
        return (float(clp),
                [(int(lids[i]), float(vals[i])) for i in range(n)])

    def _shape_cols(self, p, num_prompt: int):
        """Host-side logit_bias / min_tokens columns for one request:
        (bias_ids [NB], bias_vals [NB], suppress [NS], min_first,
        min_until).  min_until is the ABSOLUTE sequence length below which
        suppression holds in the fused loop (the new token at carry length
        L is generated-token number L - num_prompt + 2); min_first is the
        transient first-token flag (sample's lengths=None reading)."""
        bias_ids, bias_vals = sampler_mod.np_bias_cols(p, self.cfg.vocab_size)
        sup = sampler_mod.np_suppress_col(self.min_tokens_suppress_ids(p))
        min_first = 1 if p.min_tokens >= 1 else 0
        min_until = num_prompt + p.min_tokens - 1 if p.min_tokens > 0 else 0
        return bias_ids, bias_vals, sup, min_first, min_until

    def _gate_guide(self, req: Request) -> str | None:
        """Resolve a guided request's guide at admission: None = published
        and PINNED (proceed), "park" = parked on the in-flight compile
        (caller returns), any other string = compile failure message.
        Never blocks on compilation."""
        from arks_tpu.engine.guides import Guide
        if req.request_id in self._guide_pins:
            return None
        for _ in range(3):
            got = self.guides.ensure(*req.params.guide)
            if isinstance(got, Guide):
                try:
                    self._pin_guide(req)
                    return None
                except GuideError:
                    # Evicted between publish and pin (another worker's
                    # publish ran in the gap): re-kick and retry.
                    continue
            if got.event.is_set() and got.error is not None:
                return got.error
            self._awaiting_guide.append((req, got))
            self.metrics.num_requests_waiting.inc(1)
            self.trace.evt(req.request_id, "park.guide", "B")
            return "park"
        return "guide evicted repeatedly during admission"

    def _service_awaiting_guides(self) -> bool:
        """Advance the parked-on-compile requests: aborted ones fail,
        failed compiles produce per-request error outputs, published
        guides send their requests back to the admission queue (this
        step's _admit pops them).  Returns True when anything moved."""
        did = False
        still: list = []
        for req, ticket in self._awaiting_guide:
            with self._abort_lock:
                was_aborted = req.request_id in self._aborted
                self._aborted.discard(req.request_id)
            if was_aborted:
                self.metrics.num_requests_waiting.inc(-1)
                self._deliver(req, RequestOutput(
                    request_id=req.request_id, token_ids=[], finished=True,
                    finish_reason="abort",
                    num_prompt_tokens=len(req.prompt_ids)))
                did = True
                continue
            if not ticket.event.is_set():
                still.append((req, ticket))
                continue
            self.trace.evt(req.request_id, "park.guide", "E")
            if ticket.error is not None:
                self.metrics.num_requests_waiting.inc(-1)
                self._deliver(req, RequestOutput(
                    request_id=req.request_id, token_ids=[], finished=True,
                    finish_reason="error",
                    error=f"guide_compile_failed: {ticket.error}",
                    num_prompt_tokens=len(req.prompt_ids)))
                log.info("rejected %s: guide compile failed: %s",
                         req.request_id, ticket.error)
                did = True
                continue
            # Published: back to the admission queue (the waiting gauge
            # stays up — _preadmit decrements it again on the re-pop).
            with self._abort_lock:
                self._queued_rids.add(req.request_id)
                self._queue_seq += 1
                seq = self._queue_seq
            self._queue.put((req.params.priority, seq, req))
            did = True
        self._awaiting_guide = still
        return did

    def _abort_awaiting_guide(self) -> None:
        """Fail every request parked on a guide compile (engine exit):
        no scheduler remains to unpark them."""
        for req, _ in self._awaiting_guide:
            self.metrics.num_requests_waiting.inc(-1)
            self._deliver(req, RequestOutput(
                request_id=req.request_id, token_ids=[], finished=True,
                finish_reason="abort",
                num_prompt_tokens=len(req.prompt_ids)))
        self._awaiting_guide = []

    def _pin_guide(self, req: Request) -> None:
        """Refcount the request's guide (idempotent per request): pinned
        guides are never evicted, so the absolute rows its slot carries on
        device stay valid from admission through _finish."""
        if req.params.guide is None or req.request_id in self._guide_pins:
            return
        self.guides.acquire(*req.params.guide)
        self._guide_pins[req.request_id] = req.params.guide

    def _unpin_guide(self, req: Request) -> None:
        """Release the request's guide pin (idempotent, no-op when
        unguided) — called on EVERY request end-of-life path."""
        key = self._guide_pins.pop(req.request_id, None)
        if key is not None:
            self.guides.release(*key)

    def _guide_cols(self, p) -> tuple[int, int]:
        """(guide_id, start_row) for a request's guide spec, (-1, 0) when
        unguided.  Admission paths reach here only after _gate_guide
        pinned the published guide, so this is a registry hit; a miss
        means the pin discipline broke — GuideError routes to the
        admission fault path, failing just this request."""
        if p.guide is None:
            return -1, 0
        g = self.guides.lookup(*p.guide)
        if g is None:
            raise GuideError(
                f"guide {p.guide[0]}:{p.guide[1]!r} is not registered "
                "(evicted without a pin?)")
        return g.guide_id, g.start_row

    def _apply_set_slot(self, slot: int, p, key: np.ndarray, fold: bool,
                        num_prompt: int = 0, guide: int = -1,
                        guide_row: int = 0, site: str = "admit") -> None:
        """One slot through ``_apply_set_slots``."""
        self._apply_set_slots(
            [(slot, p, key, fold, num_prompt, guide, guide_row)], site)

    def _apply_set_slots(self, rows: list, site: str,
                         size: int | None = None) -> None:
        """Write the sampling rows of ``rows`` (each ``(slot, params, key,
        fold, num_prompt, guide, guide_row)``) through ONE call of the
        donated promotion program, whatever their number.  Its operand is
        one host buffer (_OperandPack) with explicit dtypes, padded up to
        a size of ``_promote_packs`` (so the program compiles once a size,
        at ``_warm_promote``, and a python float cannot retrace it per
        value); ``key`` is a HOST key, the request's base key where
        ``fold`` (folded inside the program) or a snapshot written as it
        is.  ``guide_row`` is the POST-first-token DFA row (resolved by
        the caller — followers receive it by value, so they never need the
        leader's guide registry)."""
        m = size or next(s for s in self._promote_packs if s >= len(rows))
        operands, a = self._promote_packs[m].host()
        for i, (slot, p, key, fold, num_prompt, guide, guide_row) \
                in enumerate(rows):
            (a["bias_ids"][i], a["bias_vals"][i], a["suppress_ids"][i], _mf,
             min_until) = self._shape_cols(p, num_prompt)
            a["slots"][i] = slot
            a["scalars_f"][i] = (p.temperature, p.top_p, p.presence_penalty,
                                 p.frequency_penalty)
            a["scalars_i"][i] = (p.top_k, min_until, guide, guide_row)
            a["keys"][i] = key
            a["fold"][i] = fold
        self.metrics.step_device_calls_total.inc(1, site=site)
        self._sampling = self._promote_fn(self._sampling, operands)

    def _warm_promote(self) -> None:
        """Compile the promotion program for every size it takes, before
        the first sequential step's dispatch: a call with no rows writes
        nothing.  Followers mirror each call, so a gang compiles (and
        runs) the same programs in the same order."""
        for m in self._promote_packs:
            self._emit("set_slots", rows=[], size=m)
            self._apply_set_slots([], "warm", size=m)
        self._promote_warm = True

    def _register_slot(self, req: Request, slot: int, first: int,
                       num_prompt: int, first_lp=None,
                       seed: int = 0) -> None:
        # Draft-cache prompt prefill (speculative decoding).  Skipped when
        # the prompt tokens aren't available (disagg-transferred KV) or the
        # prompt exceeds the one-shot buckets (a monolithic draft prefill
        # would reintroduce the head-of-line stall chunking exists to
        # prevent): the slot then rides the fused loop — still CORRECT, the
        # verifier is exact; only the draft speedup is forfeited.
        draft_synced = False
        if (self._draft_cfg is not None and req.prompt_ids
                and len(req.prompt_ids) <= self._buckets[-1]):
            ids = list(req.prompt_ids)
            padded = self._pad_to_bucket(ids)
            try:
                self._emit("draft_prefill", tokens=padded, length=len(ids),
                           slot=slot)
                self.metrics.step_device_calls_total.inc(1, site="draft")
                self._draft_cache = self._draft_prefill_fn(
                    self._draft_params, self._draft_cache, padded,
                    np.array([len(ids)], np.int32), np.int32(slot))
            except Exception as e:
                # Not registered yet, nothing emitted yet: the survivor
                # re-queues and re-admits with its pinned seed (same
                # contract as the pre-registration dispatches).
                self._free.append(slot)
                raise StepFault(
                    "admit", faults_mod.classify(e),
                    culprits=[req.request_id],
                    survivors=[_Survivor(request=req,
                                         seed=self._resolve_seed(req),
                                         num_prompt=num_prompt)]) from e
            draft_synced = True
        now = time.monotonic()
        p_ = req.params
        # Spec eligibility, frozen for the slot's lifetime (see _Slot):
        # per-lane and params-pure, which keeps the key-advance structure
        # schedule-independent — the property token-replay recovery needs.
        spec_ok = (draft_synced
                   and p_.presence_penalty == 0
                   and p_.frequency_penalty == 0
                   and p_.logprobs is None
                   and not p_.logit_bias
                   and p_.min_tokens == 0)
        st = _Slot(request=req, num_prompt=num_prompt,
                   draft_synced=draft_synced, spec_ok=spec_ok, seed=seed)
        self._fault_counts.pop(req.request_id, None)
        replaying = req.request_id in self._replaying
        if replaying:
            # Token-replay re-execution reached a decoding slot again:
            # the stream is live (the gate streams the continuation once
            # the re-run passes the delivered prefix).
            self._replaying.discard(req.request_id)
            self.metrics.requests_recovered_total.inc(1)
        resumed = req.request_id in self._resuming
        if resumed:
            # Replay-mode preempt resume reached a slot again: same
            # suppression as a fault replay (the gate drops the delivered
            # prefix), but it is not a recovery — don't count it as one.
            self._resuming.discard(req.request_id)
            self.trace.evt(req.request_id, "park.preempt", "E")
        st.generated.append(first)
        if first_lp is not None:
            st.logprobs.append(first_lp)
        st.first_token_time = now
        # Pipelined-decode liveness data (device mirrors of _is_stop and
        # the retire conditions), frozen for the slot's lifetime.
        st.stop_col = sampler_mod.np_stop_col(
            self._stop_ids_for(req.params))
        st.dead_len = min(num_prompt + req.params.max_tokens - 1,
                          self.ecfg.max_cache_len - self._pipe_rows)
        self._slot_gen[slot] += 1
        self._slots[slot] = st
        self._lengths[slot] = num_prompt
        self._last_token[slot] = first

        self.metrics.prompt_tokens_total.inc(num_prompt)
        self.metrics.num_requests_running.set(len(self._slots))
        ttft = now - req.arrival_time
        if not replaying and not resumed:
            # A replay re-registration is not a first token — the client
            # got theirs long ago; observing it would poison the TTFT
            # histogram with fault-to-now spans.
            self.metrics.time_to_first_token_seconds.observe(ttft)
            self.metrics.ttft_seconds.observe(
                ttft, tier=self._slo.tier_of(p_.priority))
            self._slo_burn_record(p_.priority, ttft)
        if self.trace.enabled:
            self.trace.evt(req.request_id, "prefill", "E")
            if not replaying and not resumed:
                self.trace.evt(req.request_id, "first_token", "I", ttft)
                tier = (self._slo.get(self._slo.tier_of(p_.priority))
                        if self._slo else None)
                if (tier is not None and tier.ttft_ms is not None
                        and ttft * 1000.0 > tier.ttft_ms):
                    self.trace.evt(req.request_id, "slo_violation", "I",
                                   (ttft * 1000.0, tier.ttft_ms))

        if self._check_finished(slot):
            return
        st.num_emitted = 1
        self._deliver(req, RequestOutput(
            request_id=req.request_id, token_ids=[first],
            num_prompt_tokens=num_prompt, ttft_s=ttft,
            logprobs=list(st.logprobs) if st.logprobs else None))

    # ------------------------------------------------------------------
    # Detached prefill (disaggregated prefill side)
    # ------------------------------------------------------------------

    @property
    def max_prompt_len(self) -> int:
        """Largest admissible prompt (one-dispatch decode reserve kept).
        Servers use this for the pre-queue 400 check."""
        usable = self.ecfg.max_cache_len - self.ecfg.steps_per_dispatch - 1
        if self._chunk:
            return usable
        return min(self._buckets[-1], usable)

    def _one_shot_limit(self) -> int:
        return min(self._buckets[-1],
                   self.ecfg.max_cache_len - self.ecfg.steps_per_dispatch - 1)

    def _insert_pad_len(self, plen: int) -> int:
        """Bucketed insert length for a cached prefix: the next prefill
        bucket, or beyond the largest bucket the next multiple of it —
        bounding distinct compiled insert shapes to
        O(len(buckets) + max_cache_len / last_bucket)."""
        for b in self._buckets:
            if plen <= b:
                return b
        last = self._buckets[-1]
        return min(-(-plen // last) * last, self.ecfg.max_cache_len)

    def _pad_to_bucket(self, ids: list[int]) -> np.ndarray:
        """[1, bucket] zero-padded prompt at the smallest covering bucket —
        the ONE padding implementation (one-shot prefill, draft prefill);
        shape agreement between them rides on this."""
        bucket = next(b for b in self._buckets if b >= len(ids))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(ids)] = ids
        return padded

    def _prepare_prompt(self, prompt_ids: list[int]) -> tuple[list[int], np.ndarray | None]:
        """Pad the prompt to the smallest prefill bucket.  Shared by the
        unified and disaggregated paths — the bit-identity guarantee between
        them depends on this being one implementation.

        Returns (ids, padded) for the one-shot path, (ids, None) when the
        prompt needs chunked prefill, and raises ContextLengthExceededError
        when it cannot be served at all — silent truncation would corrupt
        long-context results and billing."""
        ids = list(prompt_ids)
        if len(ids) > self.max_prompt_len:
            raise ContextLengthExceededError(
                f"prompt has {len(ids)} tokens but the maximum context "
                f"length is {self.max_prompt_len}")
        if self._residency_window:
            # Windowed residency engages on DECODE growth only: the
            # prompt itself must fit the resident budget (prefill chunks
            # attend through gather_pages, which needs every causal page
            # on device).  A window that cannot hold the prompt would
            # fail deep inside the allocator instead.
            limit = self._residency_window * self._page_size()
            if len(ids) > limit:
                raise ContextLengthExceededError(
                    f"prompt has {len(ids)} tokens but "
                    f"ARKS_RESIDENCY_WINDOW_PAGES={self._residency_window} "
                    f"bounds resident prompts to {limit} tokens (windowed "
                    "residency streams DECODE-grown context; prompts must "
                    "fit the window)")
        if len(ids) > self._one_shot_limit():
            return ids, None  # chunked path
        return ids, self._pad_to_bucket(ids)

    # ------------------------------------------------------------------
    # Chunked prefill
    # ------------------------------------------------------------------

    def _start_chunked(self, req: Request, ids: list[int],
                       prefix_len: int = 0, prefix_pages=None,
                       digests=None) -> None:
        p = req.params
        seed = self._resolve_seed(req)
        slot = self._free.pop()
        if self._paged:
            # Pages must cover positions [0, len+K-1]: while this slot
            # chunk-prefills, every interleaved decode dispatch's K-step
            # scan writes garbage rows at len..len+K-1 (device lengths
            # advance per step for ALL batch rows) — they must land in
            # owned pages, never a stale/zero table entry that another
            # sequence's page sits behind.  Shared prefix pages (already
            # incref'd by match) head the table; only the tail is newly
            # allocated.
            from arks_tpu.engine.paged import pages_needed
            page = self._page_size()
            k_steps = self.ecfg.steps_per_dispatch
            # Clamped at the table width: a replayed near-cap stream's
            # ids + K window can overshoot max_cache_len — the device's
            # dead_len mask retires the slot before a write lands there.
            total = pages_needed(len(ids), k_steps, page, self._max_pages)
            shared = list(prefix_pages or [])
            try:
                self._faults.fire("pages")
                self._assign_slot_pages(slot, total, head_pages=shared)
                if self._pool_budget:
                    self._pool_reserved[slot] = self._pool_need(req, ids)
                if self._lin_slot_bytes:
                    self.metrics.linear_state_starts_total.inc(1)
            except Exception as e:
                self._alloc.decref(shared)
                self._free.append(slot)
                raise StepFault(
                    "pages", faults_mod.classify(e),
                    culprits=[req.request_id],
                    survivors=[_Survivor(request=req, seed=seed,
                                         num_prompt=len(ids))]) from e
        elif prefix_len:
            # Cached prefix blocks land in the slot first; chunked prefill
            # then continues from prefix_len (a chunk boundary by
            # construction).  The insert is padded to a BUCKETED length so
            # the jitted program compiles O(buckets) shapes, not one per
            # distinct prefix length (the padding rows are garbage the tail
            # chunks overwrite / the per-slot length masks — same invariant
            # as one-shot bucket padding).
            k, v = self._prefix.get(ids, prefix_len)
            pad = self._insert_pad_len(prefix_len)
            if pad > prefix_len:
                width = [(0, 0)] * 5
                width[2] = (0, pad - prefix_len)
                k = np.pad(k, width)
                v = np.pad(v, width)
            try:
                self._cache = self._insert_fn(
                    self._cache, jnp.asarray(k), jnp.asarray(v),
                    jnp.asarray(slot))
            except Exception as e:
                self._free.append(slot)
                raise StepFault(
                    "chunk", faults_mod.classify(e),
                    culprits=[req.request_id],
                    survivors=[_Survivor(request=req, seed=seed,
                                         num_prompt=len(ids))]) from e
        self._prefilling[slot] = _ChunkState(request=req, ids=ids,
                                             pos=prefix_len, seed=seed,
                                             key=sampler_mod.np_prng_key(
                                                 seed),
                                             digests=digests)
        self.trace.evt(req.request_id, "queue", "E")
        self.trace.evt(req.request_id, "prefill", "B", len(ids))
        # Interleaved decode dispatches write garbage KV rows for every slot
        # at its length index; pointing this slot's length at the FINAL
        # prompt position keeps those writes beyond every masked read until
        # real decode overwrites them.
        self._lengths[slot] = len(ids)
        self._last_token[slot] = 0

    def _process_chunk(self) -> None:
        slot, st = next(iter(self._prefilling.items()))
        rid = st.request.request_id
        with self._abort_lock:
            if rid in self._aborted:
                self._aborted.discard(rid)
                del self._prefilling[slot]
                self._release_slot_pages(slot)
                self._free.append(slot)
                self._unpin_guide(st.request)
                self._deliver(st.request, RequestOutput(
                    request_id=rid, token_ids=[], finished=True,
                    finish_reason="abort", num_prompt_tokens=len(st.ids)))
                return
        c = self._chunk
        chunk = st.ids[st.pos: st.pos + c]
        valid = len(chunk)
        self.trace.evt(rid, "chunk", "I", st.pos)
        padded = np.zeros((c,), np.int32)
        padded[:valid] = chunk
        try:
            self._faults.fire("chunk")
            if self._paged:
                self._emit("chunk_paged", slot=slot, tokens=padded,
                           start=st.pos, valid=valid,
                           tables_row=self._tables[slot].copy())
                logits, self._cache = self._chunk_fn(
                    self.params, self._cache, jnp.asarray(self._tables[slot]),
                    jnp.asarray(padded), jnp.asarray(st.pos, jnp.int32),
                    jnp.asarray(valid, jnp.int32))
            else:
                self._emit("chunk", slot=slot, tokens=padded, start=st.pos,
                           valid=valid)
                logits, self._cache = self._chunk_fn(
                    self.params, self._cache, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(padded), jnp.asarray(st.pos, jnp.int32),
                    jnp.asarray(valid, jnp.int32))
        except Exception as e:
            # Attribute the fault to THIS request (the chunk dispatch does
            # work for exactly one sequence) and carry its replayable
            # state with the StepFault — _run's recovery quarantines it
            # within the retry budget while every other request survives.
            del self._prefilling[slot]
            raise StepFault(
                "chunk", faults_mod.classify(e),
                culprits=[st.request.request_id],
                survivors=[_Survivor(request=st.request, seed=st.seed,
                                     num_prompt=len(st.ids))]) from e
        st.pos += valid
        if st.pos < len(st.ids):
            return
        # Final chunk: sample the first token (same key semantics as the
        # one-shot prefill_and_sample) and promote the slot to decoding.
        p = st.request.params
        bias_ids, bias_vals, sup, min_first, _mu = self._shape_cols(p, 0)
        gid, grow0 = self._guide_cols(p)
        self._ensure_guides_uploaded()  # see _issue_admit_batch
        args = (logits, jnp.float32(p.temperature), jnp.float32(p.top_p),
                jnp.int32(p.top_k), st.key,
                jnp.asarray(bias_ids), jnp.asarray(bias_vals),
                jnp.asarray(sup), jnp.asarray(min_first, jnp.int32),
                jnp.asarray(gid, jnp.int32), jnp.asarray(grow0, jnp.int32),
                self._guide_dev)
        first_lp = None
        if p.logprobs is not None:
            self._emit("sample_one_lp", temperature=p.temperature,
                       top_p=p.top_p, top_k=p.top_k, seed=st.seed,
                       bias_ids=bias_ids, bias_vals=bias_vals,
                       sup_ids=sup, min_first=min_first,
                       guide=gid, guide_row=grow0)
            fid, clp, vals, lids = self._sample_one_lp_fn(*args)
            first = int(fid)
            first_lp = self._lp_entry(clp, vals, lids, p.logprobs)
        else:
            self._emit("sample_one", temperature=p.temperature, top_p=p.top_p,
                       top_k=p.top_k, seed=st.seed,
                       bias_ids=bias_ids, bias_vals=bias_vals,
                       sup_ids=sup, min_first=min_first,
                       guide=gid, guide_row=grow0)
            first = int(self._sample_one_fn(*args))
        del self._prefilling[slot]
        grow1 = self.guides.next_row(grow0, first) if gid >= 0 else 0
        self._emit("set_slot", slot=slot, temperature=p.temperature,
                   top_p=p.top_p, top_k=p.top_k, seed=st.seed,
                   presence=p.presence_penalty, frequency=p.frequency_penalty,
                   logit_bias=list(p.logit_bias), min_tokens=p.min_tokens,
                   stop_ids=list(p.stop_token_ids), ignore_eos=p.ignore_eos,
                   num_prompt=len(st.ids), guide=gid, guide_row=grow1)
        self._apply_set_slot(slot, p, st.key, True, num_prompt=len(st.ids),
                             guide=gid, guide_row=grow1)
        self._register_slot(st.request, slot, first, len(st.ids),
                            first_lp=first_lp, seed=st.seed)
        if self._paged and self._chunk:
            # Zero-cost harvest: every full prompt page is now written —
            # register the digest chain so later prompts share on device
            # (st.digests carries the chain computed at match time).
            self._register_prompt_pages(st.ids,
                                        self._slot_pages.get(slot, []),
                                        st.digests)
        # Slot layout: harvest the chunk-prefilled prompt (its KV exists
        # only inside the slotted cache — read it back out before decode
        # grows past it).  Same pressure gate as the one-shot path: the
        # device->host copy must not starve waiting admissions.
        elif (self._prefix is not None and self.dispatcher is None
                and self._queue.empty()):
            nfull = len(st.ids) // self._chunk * self._chunk
            if nfull and self._prefix.missing_blocks(st.ids, nfull):
                k, v = self._extract_fn(self._cache, jnp.asarray(slot, jnp.int32))
                # Slice on device: the host copy is nfull rows, not the whole
                # max_cache_len slot.
                self._prefix.put(st.ids, np.asarray(k[:, :, :nfull]),
                                 np.asarray(v[:, :, :nfull]), nfull)
                self.metrics.prefix_cache_usage_bytes.set(
                    self._prefix.bytes_used, tier="host")

    def prefill_detached(self, prompt_ids: list[int],
                         params) -> PrefilledState:
        """Run prefill + first-token sampling and return the transferable
        state instead of inserting into this engine's cache.  Thread-safe;
        called from server threads on a prefill-only engine (no decode
        loop).  On a multi-host gang the dispatch is mirrored to followers
        like any other op — the prefill lock serializes the emit+dispatch
        pair, and a prefill-only engine runs no scheduler thread to
        interleave with, so followers see the leader's exact order.

        One-shot only: the transferred KV is a single [T] block, so prompts
        beyond the largest bucket are rejected (HTTP 400 at the server)."""
        if self.cfg.latent:
            raise ValueError(
                f"model {self.cfg.name!r} (latent attention): a detached "
                "prefill hands K and V to another pod (kv_transfer); a "
                "latent page is not carried")
        if len(prompt_ids) > self._one_shot_limit():
            raise ContextLengthExceededError(
                f"prompt has {len(prompt_ids)} tokens but the disaggregated "
                f"prefill limit is {self._one_shot_limit()}")
        ids, padded = self._prepare_prompt(prompt_ids)

        want_lp = getattr(params, "logprobs", None) is not None
        first_lp = None
        pinned = False
        if params.guide is not None:
            # BLOCKING compile on this server thread (deduped against
            # concurrent compiles of the same key), taken OUTSIDE the
            # prefill lock so a cold compile never serializes other
            # prefills; then pin for the dispatch window so an eviction
            # cannot repack the guide's rows under us.
            self.guides.compile(*params.guide)
            self.guides.acquire(*params.guide)
            pinned = True
        try:
            return self._prefill_detached_pinned(ids, padded, params,
                                                 want_lp, first_lp)
        finally:
            if pinned:
                self.guides.release(*params.guide)

    def _prefill_detached_pinned(self, ids, padded, params, want_lp,
                                 first_lp) -> PrefilledState:
        with self._prefill_lock:
            self._request_seed += 1
            seed = params.seed if params.seed is not None else self._request_seed
            key = jnp.asarray(sampler_mod.np_prng_key(seed))
            bias_ids, bias_vals, sup, min_first, _mu = \
                self._shape_cols(params, 0)
            gid, grow0 = self._guide_cols(params)
            self._ensure_guides_uploaded()
            args = (self.params, jnp.asarray(padded),
                    jnp.asarray([len(ids)], jnp.int32),
                    jnp.float32(params.temperature),
                    jnp.float32(params.top_p),
                    jnp.int32(params.top_k), key,
                    jnp.asarray(bias_ids), jnp.asarray(bias_vals),
                    jnp.asarray(sup), jnp.asarray(min_first, jnp.int32),
                    jnp.asarray(gid, jnp.int32),
                    jnp.asarray(grow0, jnp.int32), self._guide_dev)
            if want_lp:
                self._emit("prefill_detached_lp", tokens=padded,
                           length=len(ids), temperature=params.temperature,
                           top_p=params.top_p, top_k=params.top_k, seed=seed,
                           bias_ids=bias_ids, bias_vals=bias_vals,
                           sup_ids=sup, min_first=min_first,
                           guide=gid, guide_row=grow0)
                first_id, clp, vals, lids, ks, vs = \
                    self._prefill_detached_lp_fn(*args)
                first_lp = self._lp_entry(clp, vals, lids, params.logprobs)
            else:
                self._emit("prefill_detached", tokens=padded,
                           length=len(ids), temperature=params.temperature,
                           top_p=params.top_p, top_k=params.top_k, seed=seed,
                           bias_ids=bias_ids, bias_vals=bias_vals,
                           sup_ids=sup, min_first=min_first,
                           guide=gid, guide_row=grow0)
                first_id, ks, vs = self._prefill_detached_fn(*args)
            first = int(first_id)
        self.metrics.prompt_tokens_total.inc(len(ids))
        return PrefilledState(first_token=first, num_prompt=len(ids),
                              seed=seed, k=np.asarray(ks), v=np.asarray(vs),
                              first_lp=first_lp,
                              guide_row=(self.guides.next_row(grow0, first)
                                         - grow0 if gid >= 0 else 0),
                              prompt_ids=list(ids))

    # ------------------------------------------------------------------
    # Pipelined decode (ARKS_PIPELINE_DEPTH)
    # ------------------------------------------------------------------

    def _stop_ids_for(self, p) -> list[int]:
        """The token ids that end a stream for these params — the EXACT
        set _is_stop checks, mirrored onto the device as a stop column so
        pipelined dispatches can compute liveness without the host."""
        if p.ignore_eos:
            return list(p.stop_token_ids)
        return (list(self.cfg.eos_token_ids)
                + list(self.tokenizer.eos_token_ids)
                + list(p.stop_token_ids))

    def _pipe_ready(self) -> bool:
        """True when the next iteration can stay on the zero-host-sync
        pipelined path: live decoding slots, no host-side scheduler work
        pending (admission, chunked prefill, deferred admits), no abort
        aimed at a live slot, and every slot's stop set fits the device
        column.  Anything else drains the pipeline first — host mutations
        need authoritative mirrors.  Requests parked on an in-flight guide
        compile do NOT drain it: the park is pure host bookkeeping, and a
        slow compile must not degrade live decoding to the sequential
        path — step() re-queues the request the moment its guide
        publishes, which the admission check below then catches."""
        if not self._pipe_depth:
            return False
        if not self._slots:
            return False
        if self._residency_active():
            # Windowed-residency slots decode span-by-span on the host
            # loop — the pipe programs do not cover them.
            return False
        if self._prefilling or self._pending_admits:
            return False
        if self._awaiting_restore and self._free \
                and self._restore_ready_any():
            # A host-tier restore LANDED: drain so the unpark can take a
            # slot with authoritative mirrors.  Restores still in flight
            # keep pipelining at full depth — that is the point of
            # issuing them as ordinary stream dispatches.
            return False
        if self._fetch_ready_any():
            # A disk/peer fetch finished staging: drain so the unpark
            # re-enters admission with authoritative mirrors.  In-flight
            # fetches are worker-thread work — full depth continues.
            return False
        if self._free and not self._queue.empty():
            # Admission is possible RIGHT NOW; with no free slot the queue
            # can only wait anyway, so saturation keeps pipelining.
            return False
        if self._swap_ready_any() or self._resume_ready_any():
            # A preempt spill's D2H copies landed (its staging blocks
            # hold the victim's only KV copy — harvest them), or a swap
            # resume's scatter landed (its slot must re-register) — both
            # are host mutations.  In-flight ones keep full depth.
            return False
        if self._preempt_wanted():
            # A queued request outranks a running victim: drain so the
            # preempt swap runs on authoritative host mirrors.
            return False
        if any(st.stop_col is None for st in self._slots.values()):
            return False
        with self._abort_lock:
            if self._aborted:
                live = {st.request.request_id
                        for st in self._slots.values()}
                if self._aborted & live:
                    return False
        if self._pipe_warm_state != "ready":
            # Pipe programs still cold: keep serving on the warm
            # sequential path and compile them off-thread — an inline
            # compile here would freeze every live token stream for the
            # whole build (seconds on CPU, potentially tens on TPU).
            self._pipe_kick_warmup()
            return False
        return True

    # ------------------------------------------------------------------
    # Windowed residency (ARKS_RESIDENCY_WINDOW_PAGES)
    # ------------------------------------------------------------------

    def _residency_active(self) -> bool:
        """True when a slot decodes (or is about to decode) through the
        windowed-residency path.  The margin term drains the pipelined
        path a few tokens BEFORE a slot's page need crosses the window,
        so pipelined grow calls can never allocate past the resident
        budget while dispatches are still in flight."""
        r = self._residency
        if r is None:
            return False
        if r.slots:
            return True
        if not self._slots:
            return False
        from arks_tpu.engine.paged import pages_needed
        page = self._page_size()
        margin = 1 + max(self._pipe_depth, 1)
        return any(
            pages_needed(int(self._lengths[s]), margin, page,
                         self._max_pages) > r.window
            for s in self._slots)

    @_scoped("residency")
    def _residency_step(self) -> bool:
        """Advance every engaged slot one token: the manager runs the
        span-streaming forward (cold pages rotate through staging while
        resident spans attend), the engine runs the mixed program's
        sampler tail on the returned logits and fans the token out
        through the shared per-slot resolve path."""
        r = self._residency
        r.engage_pending()
        if not r.slots:
            return False
        self._faults.fire("residency")
        worked = False
        for slot in list(r.slots):
            st = self._slots.get(slot)
            if st is None:
                r.release(slot)
                continue
            t0 = time.monotonic()
            want_lp = st.request.params.logprobs is not None
            logits = r.forward(slot)
            feed_tokens = np.zeros((self.ecfg.num_slots,), np.int32)
            feed_active = np.zeros((self.ecfg.num_slots,), bool)
            feed_tokens[slot] = self._last_token[slot]
            feed_active[slot] = True
            args = (self._sampling, logits, jnp.asarray(feed_tokens),
                    jnp.asarray(feed_active),
                    jnp.asarray(np.array(self._lengths)), self._guide_dev)
            if want_lp:
                ids, clp, vals, lids, self._sampling = r.sample_lp_fn(*args)
                lp_rows = ([np.asarray(clp)[slot]], [np.asarray(vals)[slot]],
                           [np.asarray(lids)[slot]])
            else:
                ids, self._sampling = r.sample_fn(*args)
                lp_rows = None
            tok = int(np.asarray(ids)[slot])
            self._fanout_decode_tokens(slot, [tok], lp_rows,
                                       max(time.monotonic() - t0, 1e-6))
            worked = True
        return worked

    def _pipe_signature(self):
        """Specimen arguments for AOT-lowering the pipe programs: the
        exact avals+shardings a fresh `_pipe_issue` produces.  Built on
        the calling thread while the referenced arrays are alive (the
        engine thread may donate self._cache away at any later dispatch,
        so the background thread must never touch the arrays — only this
        frozen aval view)."""
        n = self.ecfg.num_slots
        state = (jnp.asarray(np.zeros((n,), np.int32)),
                 jnp.asarray(np.zeros((n,), np.int32)),
                 jnp.asarray(np.zeros((n,), bool)))
        cols = [jnp.asarray(np.full((n, sampler_mod.STOP_IDS_MAX), -1,
                                    np.int32)),
                jnp.asarray(np.zeros((n,), np.int32))]
        if self._draft_cfg is not None:
            cols.append(jnp.asarray(np.zeros((n,), bool)))
        tables = self._tables_arg() if self._paged else None
        if self._draft_cfg is not None:
            args = (self.params, self._draft_params, self._cache,
                    self._draft_cache, *state, *cols, self._sampling,
                    tables, self._guide_dev)
        else:
            args = (self.params, self._cache, *state, *cols, self._sampling,
                    tables, self._guide_dev)
        # Only committed arrays pin a sharding.  Host-built and freshly
        # initialised arrays are uncommitted; pinning their default-device
        # sharding makes the executable hand back COMMITTED cache and
        # sampler state, on which the sequential programs' jit cache then
        # misses: the first drop back from the pipelined path recompiles
        # the step program inline, under live streams.
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=x.sharding if x.committed else None), args)

    def _pipe_jit_fn(self, want_lp: bool):
        if self._draft_cfg is not None:
            return self._spec_pipe_lp_fn if want_lp else self._spec_pipe_fn
        if self._mixed:
            return self._mixed_pipe_lp_fn if want_lp else self._mixed_pipe_fn
        return self._decode_pipe_lp_fn if want_lp else self._decode_pipe_fn

    def _pipe_kick_warmup(self) -> None:
        """Start the one-shot background compile of both pipe-program
        variants (with/without logprobs).  Idempotent; engine-thread."""
        if self._pipe_warm_state is not None or not self._pipe_depth:
            return
        self._pipe_warm_state = "compiling"
        sig = self._pipe_signature()
        t = threading.Thread(target=self._pipe_warmup, args=(sig,),
                             name="pipe-warmup", daemon=True)
        self._pipe_warm_thread = t
        t.start()

    def _pipe_warmup(self, sig) -> None:
        try:
            t0 = time.monotonic()
            for lp in (False, True):
                self._pipe_exec[lp] = self._pipe_jit_fn(lp).lower(
                    *sig).compile()
            self._pipe_warm_state = "ready"
            log.info("pipelined decode programs warm in %.1fs "
                     "(depth=%d, %s)", time.monotonic() - t0,
                     self._pipe_depth,
                     "mixed_pipe" if self._mixed else "decode_pipe")
        except Exception as e:
            self._pipe_warm_state = "failed"
            faults_mod.swallowed("pipe_warmup", e)
            log.warning("pipelined decode warmup failed; engine stays on "
                        "the sequential path", exc_info=True)

    def _pipe_warm_wait(self, timeout: float | None = None) -> str | None:
        """Kick the warmup and block until it resolves — tests and
        preflight only; the serving path never blocks on it."""
        self._pipe_kick_warmup()
        t = self._pipe_warm_thread
        if t is not None:
            t.join(timeout)
        return self._pipe_warm_state

    def _pipe_call(self, want_lp: bool, *args):
        """Dispatch one pipe program: the warmed AOT executable when the
        inputs still match its signature, else the jit path (which then
        compiles the drifted variant inline ONCE — e.g. after the guide
        tables grew, or for threaded state whose sharding differs from
        the fresh-entry signature on a meshed engine)."""
        exe = self._pipe_exec.get(bool(want_lp))
        if exe is not None:
            try:
                return exe(*args)
            except (TypeError, ValueError):
                pass  # aval/sharding drift: inputs not consumed, retry jit
        return self._pipe_jit_fn(want_lp)(*args)

    @_scoped("decode")
    def _step_pipelined(self) -> None:
        """One steady-state iteration: issue ONE dispatch (if the pipeline
        has room), then resolve — blocking on the oldest only when the
        pipeline is full, else opportunistically draining whatever the
        device already finished."""
        if len(self._pipe_inflight) < self._pipe_depth:
            self._pipe_issue()
        # The resolve before this step (a sequential one, or the last of
        # a cold pipeline) left nothing in flight and its deferral open:
        # it leaves behind this step's issue.
        self._flush_deferred("phase.decode.deliver")
        if len(self._pipe_inflight) >= self._pipe_depth:
            self._pipe_resolve_one()
        else:
            while (self._pipe_inflight
                   and self._pipe_inflight[0][2].is_ready()):
                self._pipe_resolve_one()
        if self._spills:
            # Harvest landed spill gathers (steady-state evictions come
            # from _pipe_issue's page growth); ready-only, never blocks.
            self._resolve_spills()

    def _pipe_issue(self) -> None:
        """Issue one pipelined decode dispatch (inside a profiler window:
        the ``phase.decode.issue`` section, arg = dispatches in flight)."""
        if not self.profiler.sections:
            return self._pipe_issue_body()
        self.trace.evt("", "phase.decode.issue", "B")
        try:
            self._pipe_issue_body()
        finally:
            self.trace.evt("", "phase.decode.issue", "E",
                           len(self._pipe_inflight))

    def _pipe_issue_body(self) -> None:
        """Fresh (pipeline cold): device state is built from the host
        mirrors — the ONE host->device state upload per run.  Threaded:
        the previous dispatch's returned arrays feed this one untouched;
        only the block tables (host-owned page bookkeeping) travel per
        dispatch."""
        K = self._pipe_rows
        fresh = self._pipe_state is None
        if fresh:
            # Host-authoritative entry: retire slots whose next dispatch
            # would overflow the cache (same margin dead_len enforces on
            # device for every later dispatch of the run).
            for slot in list(self._slots):
                if int(self._lengths[slot]) >= self.ecfg.max_cache_len - K:
                    self._finish(slot, "length")
            if not self._slots:
                return
        spec = self._draft_cfg is not None
        if self._paged:
            self._grow_slot_pages(K, ahead=len(self._pipe_inflight))
        if self._lin_slot_bytes:
            # (A pipelined dispatch is the decoding slots' row each.)
            self._count_state_lanes(len(self._slots), 0)
        self._ensure_guides_uploaded()
        self._faults.fire("spec" if spec else "decode")
        if fresh:
            n = self.ecfg.num_slots
            alive = np.zeros((n,), bool)
            stop_ids = np.full((n, sampler_mod.STOP_IDS_MAX), -1, np.int32)
            dead_len = np.zeros((n,), np.int32)
            spec_col = np.zeros((n,), bool)
            for slot, st in self._slots.items():
                alive[slot] = True
                stop_ids[slot] = st.stop_col
                dead_len[slot] = st.dead_len
                spec_col[slot] = st.spec_ok
            state = (jnp.asarray(self._last_token),
                     jnp.asarray(self._lengths), jnp.asarray(alive))
            cols = [jnp.asarray(stop_ids), jnp.asarray(dead_len)]
            cols_np = [stop_ids, dead_len]
            if spec:
                # Spec eligibility is per-slot device data too: the
                # threaded spec_pipe dispatches gate acceptance on it
                # without any host value.
                cols.append(jnp.asarray(spec_col))
                cols_np.append(spec_col)
            self._pipe_cols = tuple(cols)
            self._pipe_cols_np = tuple(cols_np)
        else:
            state = self._pipe_state
        want_lp = any(st.request.params.logprobs is not None
                      for st in self._slots.values())
        tables_arg = self._tables_arg() if self._paged else None
        payload = dict(lp=want_lp, fresh=fresh,
                       tables=self._tables.copy() if self._paged else None,
                       occupancy=len(self._pipe_inflight) + 1)
        if fresh:
            payload.update(tokens=np.array(self._last_token),
                           lengths=np.array(self._lengths),
                           alive=alive.copy(),
                           stop_ids=self._pipe_cols_np[0].copy(),
                           dead_len=self._pipe_cols_np[1].copy())
            if spec:
                payload.update(spec_enable=self._pipe_cols_np[2].copy())
        self._emit("decode_pipe", **payload)
        t0 = time.monotonic()
        self._pipe_seq += 1
        self.trace.evt("", "pipe", "B", self._pipe_seq)
        if spec:
            out = self._pipe_call(want_lp, self.params, self._draft_params,
                                  self._cache, self._draft_cache, *state,
                                  *self._pipe_cols, self._sampling,
                                  tables_arg, self._guide_dev)
            if want_lp:
                (self._cache, self._draft_cache, self._sampling, toks,
                 counts, clps, lvals, lids, ntok, nlen, nalive) = out
                lp_devs = (clps, lvals, lids)
            else:
                (self._cache, self._draft_cache, self._sampling, toks,
                 counts, ntok, nlen, nalive) = out
                lp_devs = None
        else:
            counts = None
            out = self._pipe_call(want_lp, self.params, self._cache, *state,
                                  *self._pipe_cols, self._sampling,
                                  tables_arg, self._guide_dev)
            if want_lp:
                (self._cache, self._sampling, toks, clps, lvals, lids,
                 ntok, nlen, nalive) = out
                lp_devs = (clps, lvals, lids)
            else:
                self._cache, self._sampling, toks, ntok, nlen, nalive = out
                lp_devs = None
        t_ret = time.monotonic()
        self._pipe_state = (ntok, nlen, nalive)
        # Start the device->host copies NOW so the lagged resolve finds
        # them materialized instead of blocking the engine thread.
        for arr in (toks,) + (() if counts is None else (counts,)) \
                + (lp_devs or ()):
            arr.copy_to_host_async()
        snapshot = [(s, int(self._slot_gen[s])) for s in self._slots]
        self._pipe_inflight.append(
            (snapshot, want_lp, toks, lp_devs, K, t0, counts))
        self.metrics.pipeline_depth_occupancy.observe(
            len(self._pipe_inflight))
        self.step_clock.dispatched("spec_pipe" if spec else "pipe", t0,
                                   t_ret, len(snapshot) * K)
        if self._model_loads:
            # Dispatch accounting for the switch-overlap claim: decode
            # dispatches issued while another model's weights stream, and
            # the pipeline depth they sustained (plain host counters, no
            # device sync).
            self._switch_stats["dispatches"] += 1
            if len(self._pipe_inflight) > self._switch_stats["max_depth"]:
                self._switch_stats["max_depth"] = len(self._pipe_inflight)

    def _pipe_resolve_one(self) -> None:
        """Resolve the OLDEST in-flight dispatch (inside a profiler window:
        the ``phase.decode.resolve`` section, arg = dispatches still in
        flight after it)."""
        if not self.profiler.sections:
            return self._pipe_resolve_body()
        self.trace.evt("", "phase.decode.resolve", "B")
        try:
            self._pipe_resolve_body()
        finally:
            self.trace.evt("", "phase.decode.resolve", "E",
                           len(self._pipe_inflight))

    def _pipe_resolve_body(self) -> None:
        """On the lagged host view: fan the dispatch's tokens out, apply
        the host-only semantics (stop tokens, max_tokens truncation,
        logprob formatting), and retire finished slots — whose overshoot
        tokens in NEWER in-flight dispatches are discarded by the (slot,
        gen) snapshot guard."""
        (snapshot, want_lp, toks, lp_devs, K, t0,
         counts_dev) = self._pipe_inflight.popleft()
        if not self._pipe_inflight:
            # The drain's last resolve, or a cold pipeline's: the device
            # runs dry behind it.  A steady depth-2 resolve puts at once.
            self._defer_delivery()
        self._faults.fire("resolve")
        t_wait = time.monotonic()
        toks = np.asarray(toks)  # host sync point (async copy usually done)
        if self._held_stat:
            self._count_held(toks[0], self.ecfg.num_slots)
        counts = None if counts_dev is None else np.asarray(counts_dev)
        if lp_devs is not None:
            clps = np.asarray(lp_devs[0])    # [K, B]
            lvals = np.asarray(lp_devs[1])   # [K, B, L]
            lids = np.asarray(lp_devs[2])
        now = time.monotonic()
        self.step_clock.waited(t_wait, now, len(self._pipe_inflight))
        self.trace.evt("", "pipe", "E", len(snapshot))
        # TPOT from resolve interarrival: in steady state one resolve
        # lands per dispatch, so the gap IS the per-dispatch device time —
        # this dispatch's own issue->resolve span covers the whole
        # pipeline depth and would overstate TPOT by ~depth x.
        last = self._pipe_last_resolve
        self._pipe_last_resolve = now
        dt = max(now - (t0 if last is None else last), 1e-6)
        cols = toks.T.tolist()
        n_spec = accepted = 0
        for slot, gen in snapshot:
            st = self._slots.get(slot)
            if st is None or int(self._slot_gen[slot]) != gen:
                continue  # retired at an earlier resolve: overshoot dropped
            col = cols[slot]
            if counts is not None:
                # Spec dispatch: only the accepted prefix of the verify
                # block is real output; the rejected tail is garbage the
                # device also never threaded forward.
                c = max(1, min(int(counts[slot]), K))
                col = col[:c]
                if st.spec_ok:
                    n_spec += 1
                    accepted += c - 1
                    self.metrics.spec_decode_accepted_length.observe(c)
            lp_rows = None
            if want_lp and st.request.params.logprobs is not None:
                lp_rows = (clps[:, slot], lvals[:, slot], lids[:, slot])
            self._fanout_decode_tokens(slot, col, lp_rows, dt)
        if n_spec:
            DK = self.ecfg.draft_len
            self.metrics.spec_decode_proposed_tokens_total.inc(
                (DK - 1) * n_spec)
            self.metrics.spec_decode_accepted_tokens_total.inc(accepted)
            self._spec_proposed += (DK - 1) * n_spec
            self._spec_accepted += accepted
            self.metrics.spec_decode_acceptance_rate.set(
                self._spec_accepted / max(self._spec_proposed, 1))

    @_scoped("decode")
    def _pipe_drain(self) -> None:
        """Resolve every in-flight dispatch and hand authority back to the
        host mirrors (they are exact after the last resolve)."""
        try:
            while self._pipe_inflight:
                self._pipe_resolve_one()
        finally:
            self._pipe_state = None
            self._pipe_cols = None
            self._pipe_cols_np = None
            self._pipe_last_resolve = None

    def _pipe_reset(self) -> None:
        """Fault path: drop in-flight records without resolving (the
        dispatch error already aborted their requests; the device state is
        being rebuilt)."""
        self._pipe_inflight.clear()
        self._pipe_state = None
        self._pipe_cols = None
        self._pipe_cols_np = None
        self._pipe_last_resolve = None

    def _decode_dispatch(self) -> None:
        rec = self._issue_decode()
        if rec is not None:
            self._resolve_decode(rec)

    @_scoped("decode")
    def _issue_decode(self):
        """Decode bookkeeping + ASYNC dispatch.  Returns the pending record
        for _resolve_decode, or None when nothing dispatched (no live
        slots).

        The issue/resolve split lets step() overlap admission host work
        with the in-flight decode: aborted/retired slots free their pages
        BEFORE the dispatch snapshot (their rows carry the write-drop
        sentinel), so pages handed to admissions during the flight cannot
        be written by it, and admissions' device work queues after the
        decode on the stream."""
        K = self.ecfg.steps_per_dispatch
        with self._abort_lock:
            aborted = set(self._aborted)
        consumed = set()
        for slot in list(self._slots):
            rid = self._slots[slot].request.request_id
            if rid in aborted:
                self._finish(slot, "abort")
                consumed.add(rid)
        # Aborts for requests still waiting in the admission queue stay in
        # the set until _preadmit consumes them; deferred admits and
        # guide-parked requests count as live (purging their flags would
        # lose aborts raised between issue and registration).
        self._purge_stale_aborts(consumed)
        # Retire any slot that would overflow its cache this dispatch.
        for slot in list(self._slots):
            if int(self._lengths[slot]) + 1 + K > self.ecfg.max_cache_len:
                self._finish(slot, "length")
        if not self._slots:
            return None

        if self._paged:
            self._grow_slot_pages(K)

        self._faults.fire("decode")
        t0 = time.monotonic()
        # Logprob variant selected per dispatch: only dispatches containing
        # a logprob-bearing slot pay the full-vocab log-softmax.
        want_lp = any(st.request.params.logprobs is not None
                      for st in self._slots.values())
        tables_arg = jnp.asarray(self._tables) if self._paged else None
        self._emit("decode", tokens=np.array(self._last_token),
                   lengths=np.array(self._lengths), lp=want_lp,
                   tables=self._tables.copy() if self._paged else None)
        lp_devs = None
        if want_lp:
            self._cache, self._sampling, (toks, clps, lvals, lids) = \
                self._decode_lp_fn(
                    self.params, self._cache, jnp.asarray(self._last_token),
                    jnp.asarray(self._lengths), self._sampling, tables_arg,
                    self._guide_dev)
            lp_devs = (clps, lvals, lids)
        else:
            self._cache, self._sampling, toks = self._decode_fn(
                self.params, self._cache, jnp.asarray(self._last_token),
                jnp.asarray(self._lengths), self._sampling, tables_arg,
                self._guide_dev)
        self.step_clock.dispatched("decode", t0, time.monotonic(),
                                   len(self._slots) * K)
        # Snapshot the dispatch's slot set: slots admitted while this
        # dispatch is in flight are NOT part of it (their rows carried the
        # free-slot sentinel at issue).
        return (list(self._slots.keys()), want_lp, toks, lp_devs, K, t0)

    @_scoped("decode")
    def _resolve_decode(self, rec, exclude_s: float = 0.0) -> None:
        """Host-sync tail: fetch the dispatch's tokens and fan them out to
        the SNAPSHOT slots.  ``exclude_s`` subtracts the overlapped
        admit/chunk wall time from the TPOT observation — in overlap mode
        issue-to-resolve spans that host work, which is not decode time."""
        snapshot, want_lp, toks, lp_devs, K, t0 = rec
        self._faults.fire("resolve")
        t_wait = time.monotonic()
        toks = np.asarray(toks)  # [K, B] — host sync point
        # Pure device-stream wait, free of overlapped host work (the
        # phase-seconds breakdown attributes WALL time, which in overlap
        # mode can land waits in whichever phase fetches first).
        self.step_clock.waited(t_wait, time.monotonic(), 0)
        if lp_devs is not None:
            clps = np.asarray(lp_devs[0])    # [K, B]
            lvals = np.asarray(lp_devs[1])   # [K, B, L]
            lids = np.asarray(lp_devs[2])
        dt = max(time.monotonic() - t0 - exclude_s, 1e-6)
        # One bulk C conversion instead of B*K numpy scalar reads (~6k
        # PyObject boxing calls per dispatch at b192/K32 — measurable host
        # time the GIL shares with the serving threads).
        cols = toks.T.tolist()   # [B][K] python ints

        for slot in snapshot:
            st = self._slots[slot]
            lp_rows = None
            if want_lp and st.request.params.logprobs is not None:
                lp_rows = (clps[:, slot], lvals[:, slot], lids[:, slot])
            self._fanout_decode_tokens(slot, cols[slot], lp_rows, dt)

    def _fanout_decode_tokens(self, slot: int, col: list, lp_rows,
                              dt: float) -> None:
        """Per-slot tail shared by every resolve (the mixed one hands it
        a one-token column, the spec-mixed one the accepted block, the
        legacy and the pipelined one a dispatch's K rows): append the
        tokens (truncating at the first stop token or the max_tokens
        cutoff — everything past it is overshoot the device computed but
        the client never sees), advance the host mirrors, and finish or
        stream the delta.  This is the bookkeeping the next batch needs;
        the frame itself goes through _deliver."""
        st = self._slots[slot]
        K = len(col)
        n_lp = st.request.params.logprobs
        finished = False
        new_tokens = 0
        for k in range(K):
            tok = col[k]
            st.generated.append(tok)
            if lp_rows is not None:
                st.logprobs.append(self._lp_entry(
                    lp_rows[0][k], lp_rows[1][k], lp_rows[2][k], n_lp))
            new_tokens += 1
            if self._is_stop(st, tok) or len(st.generated) >= st.request.params.max_tokens:
                finished = True
                break
        self._lengths[slot] += K  # all K KVs were written on device
        self._last_token[slot] = col[K - 1]
        self.metrics.generation_tokens_total.inc(new_tokens)
        self.metrics.time_per_output_token_seconds.observe(dt / K)
        self.metrics.tpot_seconds.observe(
            dt / K, tier=self._slo.tier_of(st.request.params.priority))
        if finished:
            self._finish(slot, self._finish_reason(st))
        else:
            delta = st.generated[st.num_emitted:]
            lp_delta = (st.logprobs[st.num_emitted:]
                        if n_lp is not None else None)
            st.num_emitted = len(st.generated)
            self._deliver(st.request, RequestOutput(
                request_id=st.request.request_id, token_ids=delta,
                num_prompt_tokens=st.num_prompt,
                logprobs=lp_delta))

    # ------------------------------------------------------------------
    # Mixed prefill+decode dispatch (ARKS_MIXED_STEP)
    # ------------------------------------------------------------------

    def _mixed_abort_and_retire(self, rows: int = 1) -> None:
        """Mixed-mode scheduling boundary: honor aborts for decoding AND
        prefilling sequences, purge stale abort flags, and retire slots
        that would overflow the cache this dispatch (``rows`` decode rows
        per slot: 1 for the plain mixed step, draft_len for a spec-mixed
        verify block)."""
        with self._abort_lock:
            aborted = set(self._aborted)
        consumed = set()
        for slot in list(self._slots):
            rid = self._slots[slot].request.request_id
            if rid in aborted:
                self._finish(slot, "abort")
                consumed.add(rid)
        for slot, st in list(self._prefilling.items()):
            rid = st.request.request_id
            if rid in aborted:
                del self._prefilling[slot]
                self._release_slot_pages(slot)
                self._free.append(slot)
                self._unpin_guide(st.request)
                self._deliver(st.request, RequestOutput(
                    request_id=rid, token_ids=[], finished=True,
                    finish_reason="abort", num_prompt_tokens=len(st.ids)))
                consumed.add(rid)
        self._purge_stale_aborts(consumed)
        for slot in list(self._slots):
            if int(self._lengths[slot]) + 1 + rows > self.ecfg.max_cache_len:
                self._finish(slot, "length")

    def _mixed_fields(self, t_budget: int, spec: bool = False) -> list:
        """The host operands of one mixed (``spec``: spec-mixed) batch as
        fields of an _OperandPack, with the values an empty batch holds:
        the block tables and lengths, the flat token view, the per-lane
        sampler view, and the completion-override columns — ONE
        definition, so the plain and spec builders (and a follower's
        replay) cannot drift on padding conventions."""
        b = self.ecfg.num_slots
        nb, ns = sampler_mod.LOGIT_BIAS_MAX, sampler_mod.SUPPRESS_MAX
        i32, f32 = np.int32, np.float32
        return [
            ("tables", i32, (b, self._max_pages), 0),
            *([("win_tables", i32, (b, self._max_pages), 0)]
              if self._win is not None else []),
            ("lengths", i32, (b,), 0),
            ("tokens", i32, (t_budget,), 0),
            ("token_slot", i32, (t_budget,), -1),
            ("token_pos", i32, (t_budget,), self._park_sentinel()),
            ("sample_src", i32, (b,), 0),
            ("feed_tokens", i32, (b,), 0),
            ("feed_active", bool, (b,), False),
            ("seq_q_start", i32, (b,), 0),
            ("seq_q_len", i32, (b,), 0),
            ("seq_pos_start", i32, (b,), 0),
            *([("spec_enable", bool, (b,), False)] if spec else []),
            ("ov_mask", bool, (b,), False),
            ("ov_temp", f32, (b,), 0.0),
            ("ov_top_p", f32, (b,), 1.0),
            ("ov_top_k", i32, (b,), 0),
            ("ov_key", np.uint32, (b, 2), 0),
            ("ov_bias_ids", i32, (b, nb), -1),
            ("ov_bias_vals", f32, (b, nb), 0.0),
            ("ov_sup", i32, (b, ns), -1),
            ("ov_min_until", i32, (b,), 0),
            ("ov_guide", i32, (b,), -1),
            ("ov_guide_row", i32, (b,), 0)]

    def _fill_chunk_lanes(self, a: dict, t: int, budget: int = 0):
        """Round-robin prefill-chunk fill starting at flat index ``t``: an
        even quota per prefilling sequence first, FIFO greedy for the
        leftover — a burst of long prompts shares the budget instead of
        serializing.  Sequences whose prompt completes inside this batch
        get transient first-token sampling columns packed into their lane
        (same key and shaping semantics as the legacy sample_one).
        Returns (completing, chunk_take, t)."""
        completing: list = []
        chunk_take: list[tuple[int, int]] = []
        pre = list(self._prefilling.items())
        budget = budget or self._mixed_budget
        if not pre or not budget:
            return completing, chunk_take, t
        quota = max(budget // len(pre), 1)
        takes: dict[int, int] = {}
        for slot, st in pre:
            if budget <= 0:
                break
            take = min(len(st.ids) - st.pos, quota, budget)
            if take > 0:
                takes[slot] = take
                budget -= take
        for slot, st in pre:
            if budget <= 0:
                break
            extra = min(len(st.ids) - st.pos - takes.get(slot, 0),
                        budget)
            if extra > 0:
                takes[slot] = takes.get(slot, 0) + extra
                budget -= extra
        for slot, st in pre:
            take = takes.get(slot, 0)
            if not take:
                continue
            a["tokens"][t: t + take] = st.ids[st.pos: st.pos + take]
            a["token_slot"][t: t + take] = slot
            a["token_pos"][t: t + take] = np.arange(st.pos, st.pos + take)
            a["seq_q_start"][slot] = t
            a["seq_q_len"][slot] = take
            a["seq_pos_start"][slot] = st.pos
            chunk_take.append((slot, take))
            if st.pos + take == len(st.ids):
                a["sample_src"][slot] = t + take - 1
                p = st.request.params
                gid, grow0 = self._guide_cols(p)
                bias_ids, bias_vals, sup, min_first, _mu = \
                    self._shape_cols(p, 0)
                a["ov_mask"][slot] = True
                a["ov_temp"][slot] = p.temperature
                a["ov_top_p"][slot] = p.top_p
                a["ov_top_k"][slot] = p.top_k
                a["ov_key"][slot] = st.key
                a["ov_bias_ids"][slot] = bias_ids
                a["ov_bias_vals"][slot] = bias_vals
                a["ov_sup"][slot] = sup
                # lengths[slot] carries len(ids) while prefilling; +1
                # makes ``lengths < min_until`` read as min_first.
                a["ov_min_until"][slot] = \
                    len(st.ids) + 1 if min_first else 0
                a["ov_guide"][slot] = gid
                a["ov_guide_row"][slot] = grow0
                completing.append((slot, st, gid, grow0))
            t += take
        return completing, chunk_take, t

    def _mixed_grid_counters(self, pos_start, q_len, qmax: int) -> None:
        """Account one mixed dispatch's plan counters:
        mixed_grid_steps_total (the page-compute steps the work list
        runs), mixed_q_layout_rows_total (the query rows the plan lays out
        for the kernel, whatever the batch holds), mixed_kv_write_blocks_total
        (the blocks the row write moves: a lane's rows are one run of
        positions) and the mixed_kv_bytes pair.
        The counters describe the grid PLAN — they are meaningful under
        either attention impl, which is what lets the sparse-batch
        test run on the XLA oracle.  Inputs are the host-side numpy batch
        arrays — no device fetches here (hot-path guard covers this)."""
        plan = self._grid_plans.get(qmax)
        if plan is None:
            from arks_tpu.ops.paged_attention import (mixed_grid_plan,
                                                      update_block_tokens)
            kvd = self.ecfg.resolve_kv_cache_dtype()
            kv = kvd if kvd in ("int8", "int4") else str(self._cache.k.dtype)
            self._kv_write_block = update_block_tokens(kv)
            plan = mixed_grid_plan(
                qmax, hkv=self.cfg.num_kv_heads,
                g=self.cfg.num_heads // self.cfg.num_kv_heads,
                d=tf.cache_head_dim(self.cfg, self._pad_head()),
                page=self._page_size(), kv=kv, lanes=pos_start.shape[0])
            self._grid_plans[qmax] = plan
        from arks_tpu.engine.paged import mixed_grid_steps, mixed_kv_bytes
        self.metrics.mixed_grid_steps_total.inc(mixed_grid_steps(
            pos_start, q_len, page=self._page_size(),
            block_q=plan["block_q"], num_qb=plan["num_qb"],
            max_pages=self._max_pages))
        self.metrics.mixed_q_layout_rows_total.inc(plan["q_rows"])
        blk = self._kv_write_block
        self.metrics.mixed_kv_write_blocks_total.inc(int((
            ((pos_start + q_len - 1) // blk - pos_start // blk + 1)
            * (q_len > 0)).sum()))
        b_actual, b_ideal = mixed_kv_bytes(
            pos_start, q_len, page=self._page_size(),
            block_q=plan["block_q"], num_qb=plan["num_qb"],
            max_pages=self._max_pages, hkv=self.cfg.num_kv_heads,
            page_head_bytes=self._page_head_bytes())
        self.metrics.mixed_kv_bytes_total.inc(b_actual, kind="full")
        self.metrics.mixed_kv_bytes_ideal_total.inc(b_ideal)
        if self._win is None:
            return
        # A window layer's launch: its own group size, so a plan of its
        # own; the same mirror told the window.
        wplan = self._grid_plans.get(("window", qmax))
        if wplan is None:
            from arks_tpu.ops.paged_attention import mixed_grid_plan
            cfg = self.cfg
            wplan = mixed_grid_plan(
                qmax, hkv=cfg.kv_heads_of(True),
                g=cfg.window_num_heads // cfg.kv_heads_of(True),
                d=tf.cache_head_dim(cfg, self._pad_head()),
                page=self._page_size(),
                kv=self.ecfg.resolve_kv_cache_dtype(),
                lanes=pos_start.shape[0])
            self._grid_plans[("window", qmax)] = wplan
        w_actual, _ = mixed_kv_bytes(
            pos_start, q_len, page=self._page_size(),
            block_q=wplan["block_q"], num_qb=wplan["num_qb"],
            max_pages=self._max_pages, hkv=self.cfg.kv_heads_of(True),
            page_head_bytes=self._page_head_bytes(self._cache.win),
            window=self.cfg.sliding_window)
        self.metrics.mixed_kv_bytes_total.inc(w_actual, kind="window")
        win = self._win
        self.metrics.kv_window_page_steps_total.inc(win.pages_in_use,
                                                    state="held")
        self.metrics.kv_window_page_steps_total.inc(win.unreleased_pages,
                                                    state="unreleased")
        self.metrics.kv_pages_in_use.set(win.pages_in_use, kind="window")
        self.metrics.kv_pages_in_use.set(
            self._alloc.num_pages - self._alloc.free_pages, kind="full")
        self.metrics.kv_pages_reserved.set(sum(self._pool_reserved.values()))

    def _count_held(self, ids: np.ndarray, n_rows: int) -> None:
        """A latent routed model's step hands back four counts behind its
        token ids (the last four entries: (token, expert) pairs that landed
        on experts held here, the overflow tiles its layers' batched
        dispatch needed, those of them beyond the spare ones, and the rows
        that carried a token): the counters of docs/monitoring.md, from
        values already on the host.  ``n_rows``: the rows of the step's
        program, by which a share's dispatch sizes its batches.  Where the
        routers score identity experts a fifth count, the pairs that
        landed on one, stands ahead of the rows."""
        cfg = self.cfg
        if cfg.zero_experts:
            held, needed, extra, zero, rows = (int(v) for v in ids[-5:])
            self.metrics.moe_zero_pairs_total.inc(zero)
        else:
            held, needed, extra, rows = (int(v) for v in ids[-4:])
        self.metrics.mixed_latent_rows_total.inc(
            rows * (cfg.num_attn_sublayers if cfg.latent
                    else cfg.num_layers))
        self.metrics.moe_routed_pairs_total.inc(
            rows * cfg.num_experts_per_tok * cfg.num_routed_layers)
        self.metrics.moe_held_pairs_total.inc(held)
        if cfg.expert_parallel_size > 1:
            self.metrics.moe_overflow_tiles_total.inc(needed, kind="needed")
            self.metrics.moe_overflow_tiles_total.inc(extra, kind="extra")
            fixed, a_tile = moe_mod.share_rows(n_rows, cfg)
            self.metrics.moe_batch_rows_total.inc(
                fixed * cfg.num_routed_layers + extra * a_tile)

    def _block_preflight(self, cfg: ModelConfig, ecfg: "EngineConfig",
                         draft_cfg) -> None:
        """What ``cfg``'s block cannot be served with, refused here, at
        construction, each by name (as ops.attention.kernel_blockers does
        for a kernel); nothing falls back quietly.  A block whose slot
        holds K and V pages of every layer in one pool (``_BLOCKS`` has no
        row for it) is refused nothing.  Every other block is served by the
        mixed scheduler on one device over pages, and everything else that
        moves KV speaks K and V blocks of "every layer's page" of one
        pool: a mesh, a draft, no chunked prefill, and every mover of KV
        blocks that was ASKED for (the host tier is on by default, so its
        default is off for such a model, _init_model_state, and only a
        tier asked for is refused).  A model that is latent AND linear
        says what it is once, in one sentence.  Token replay after a fault
        stays for every block: it re-prefills from position 0, which
        rebuilds a recurrent state too."""
        block = _BLOCKS.get(_kv_page(cfg))
        if block is None:
            return
        block = dataclasses.replace(block, **{
            f: getattr(block, f).format(recurrent=cfg.recurrent_kind)
            for f in ("what", "mesh_why")})
        if block.latent_page and ecfg.kv_cache_dtype == "auto":
            ecfg.kv_cache_dtype = "bf16"
        if ecfg.kv_layout == "auto":
            ecfg.kv_layout = "paged"
        why = []
        if block.latent_page and ecfg.kv_cache_dtype != "bf16":
            why.append(f"kv_cache_dtype={ecfg.kv_cache_dtype} (a latent "
                       "page is bf16 only: an int8 / int4 latent row is "
                       "not built)")
        if ecfg.kv_layout != "paged":
            why.append(f"kv_layout={ecfg.kv_layout} (the slot layout "
                       f"{block.slot_keeps})")
        if self.mesh is not None and self.mesh.size > 1:
            why.append(f"a device mesh {dict(self.mesh.shape)} (tensor / "
                       "data / context / pipeline parallelism: "
                       f"{block.mesh_why})")
        if ecfg.draft_model or draft_cfg is not None:
            why.append("speculative decoding (the draft and the verify "
                       f"rows move {block.moves})")
        if not ecfg.prefill_chunk:
            why.append("prefill_chunk off (the mixed scheduler needs "
                       "chunked prefill)")
        for knob, what in (
                ("ARKS_PREFIX_HOST_MB", "the host spill tier"),
                ("ARKS_PREFIX_DISK_MB", "the disk spill tier"),
                ("ARKS_RESIDENCY_WINDOW_PAGES", "windowed residency")):
            if knobs.is_set(knob) and knobs.get_int(knob) > 0:
                why.append(f"{knob} ({what} moves {block.moves})")
        if knobs.get_bool("ARKS_PREEMPT"):
            why.append(f"ARKS_PREEMPT (the KV swap moves {block.moves})")
        if [a for a in knobs.get_list("ARKS_PEER_ADDRS") if a.strip()]:
            why.append("ARKS_PEER_ADDRS (peer fetch carries "
                       f"{block.moves} in the AKV1 format)")
        if knobs.get_str("ARKS_MIXED_STEP") == "0":
            why.append("ARKS_MIXED_STEP=0 (the legacy scheduler)")
        if why:
            raise ValueError(f"model {cfg.name!r} ({block.what}) cannot be "
                             "served with: " + "; ".join(why))
        if block.no_index and ecfg.prefix_cache_mb:
            log.info("model %s: the device prefix index is off (a matched "
                     "prefix's %s)", cfg.name, block.no_index)

    def _page_head_bytes(self, pool=None) -> int:
        """Bytes one (page, KV head) block of ``pool`` (the full layers'
        where not given) moves over the mixed kernel's page stream: K + V
        rows, each at its stored width (int4 pools store packed nibble
        rows, so the row count already reflects the halving) plus the f32
        scale rows for quantized pools."""
        pool = self._cache if pool is None else pool
        per = sum(x.shape[3] * x.shape[4] * x.dtype.itemsize
                  for x in (pool.k, pool.v) if x is not None)
        if pool.k_scale is not None:
            per += 2 * pool.k_scale.shape[3] * 4
        return per

    # Step-section spans (``phase.<phase>.<section>``, docs/monitoring.md):
    # engine-scope B/E pairs through trace.evt, recorded only while a
    # profiler window is open (``self.profiler.sections``).  The sections
    # the plain and the spec-mixed dispatch share live in the three helpers
    # below; ``tag`` is ``"phase.mixed."`` or ``"phase.spec."``.

    def _mixed_begin(self, rows: int, tag: str) -> bool:
        """The ``retire`` section: honor aborts, retire slots that would
        overflow (``rows`` decode rows per slot), upload guides and grow
        the slots' pages.  False when no sequence needs the model.  Span
        arg: slots retired."""
        sec = self.profiler.sections
        n0 = len(self._slots)
        if sec:
            self.trace.evt("", tag + "retire", "B")
        try:
            self._mixed_abort_and_retire(rows)
            if not self._slots and not self._prefilling:
                return False
            self._ensure_guides_uploaded()
            self._grow_slot_pages(rows)
            if not self._promote_warm:
                self._warm_promote()
            if not self._mixed_tail_warm:
                self._warm_mixed_tail()
            if not self._spill_warm:
                self._warm_spill()
            return True
        finally:
            if sec:
                self.trace.evt("", tag + "retire", "E",
                               n0 - len(self._slots))

    def _mixed_shape(self):
        """(operand pack, prefill rows) of the sequential step about to be
        packed: the tail shape when every prompt row the prefilling
        sequences still have fits it (none at all included), else the
        whole budget's."""
        if self._mixed_tail_pack is not None and sum(
                len(st.ids) - st.pos
                for st in self._prefilling.values()) <= self._mixed_tail:
            return self._mixed_tail_pack, self._mixed_tail
        return self._mixed_pack, self._mixed_budget

    def _mixed_pack_of(self, rows: int) -> "_OperandPack":
        """The pack a mixed batch of ``rows`` flat tokens was built from
        (a follower's replay)."""
        tail = self._mixed_tail_pack
        if tail is not None and rows == self.ecfg.num_slots + self._mixed_tail:
            return tail
        return self._mixed_pack

    def _warm_mixed_tail(self) -> None:
        """Compile the tail shape's two programs before the first
        sequential step's dispatch (the full shape's compile with the
        first step that needs them; a tail step may first come inside a
        window that must not compile): a batch with no row writes
        nothing.  Followers mirror each call."""
        for lp in (False, True):
            operands, a = self._mixed_tail_pack.host()
            self._emit("mixed", lp=lp, **a)
            fn = self._mixed_lp_fn if lp else self._mixed_fn
            out = fn(self.params, self._cache, self._sampling, operands,
                     self._guide_dev)
            self._cache, self._sampling = out[-2], out[-1]
        self._mixed_tail_warm = True

    def _warm_spill(self) -> None:
        """Compile the host tier's spill gather before the first
        sequential step's dispatch (the pool's first eviction may fall
        inside a window that must not compile): one group of pages
        gathered into a staging block that is dropped.  Where the tier is
        off the program is never called; that includes every gang leader
        (_host_tier_on), so no follower has a call to mirror."""
        if self._host_tier_on():
            self._spill_gather_fn(
                self._cache, np.zeros((self._spill_group,), np.int32))
        self._spill_warm = True

    def _mixed_account(self, a: dict, rows: int, n_chunk: int, budget: int,
                       qmax: int, tag: str) -> None:
        """The ``count`` section: the dispatch's counters, all from the
        host-side batch arrays.  The budget counter rises, by the
        ``budget`` of the shape the step took (the tail's on a tail step),
        only while a prompt could have used it (one is prefilling or
        queued), so chunk_tokens / chunk_budget_tokens is the share of the
        prefill budget the steps took."""
        sec = self.profiler.sections
        if sec:
            self.trace.evt("", tag + "count", "B")
        self.metrics.mixed_batch_tokens.observe(rows)
        if n_chunk:
            self.metrics.mixed_chunk_tokens_total.inc(n_chunk)
        if budget and (self._prefilling or self._queue.qsize() > 0):
            self.metrics.mixed_chunk_budget_tokens_total.inc(budget)
        self._mixed_grid_counters(a["seq_pos_start"], a["seq_q_len"], qmax)
        if self._lin_slot_bytes:
            q_len = a["seq_q_len"]
            self._count_state_lanes(int((q_len == 1).sum()),
                                    int((q_len > 1).sum()),
                                    int(q_len[q_len > 1].sum()))
        if sec:
            self.trace.evt("", tag + "count", "E")

    def _mixed_finish_chunks(self, chunk_take, completing, ids, want_lp,
                             lp_host, tag: str) -> None:
        """Advance every prefilling sequence and, in the ``promote``
        section (arg: prompts completed), promote those whose prompt
        completed inside the batch."""
        for slot, take in chunk_take:
            st = self._prefilling.get(slot)
            if st is not None:
                st.pos += take
        sec = self.profiler.sections
        if sec:
            self.trace.evt("", tag + "promote", "B")
        try:
            self._promote_completing(completing, ids, want_lp, lp_host)
        finally:
            if sec:
                self.trace.evt("", tag + "promote", "E", len(completing))

    @_scoped("mixed")
    def _issue_mixed(self):
        """Build and issue ONE mixed dispatch: every decoding slot's next
        token plus up to ARKS_MIXED_CHUNK_TOKENS prefill tokens spread
        round-robin across ALL prefilling sequences (each makes progress
        every step — no head-of-line prefill serialization).  Sequences
        whose prompt completes inside this batch get transient first-token
        sampling columns packed into their lane; everything samples in the
        program's single sampler.sample call.  Returns the pending record
        for _resolve_mixed, or None when no sequence needs the model."""
        tag = "phase.mixed."
        if not self._mixed_begin(1, tag):
            return None
        self._faults.fire("decode")
        dec_slots = list(self._slots.keys())
        if self._residency is not None:
            # Engaged slots decode through _residency_step — their lanes
            # must never enter the classic dispatch (its attend expects
            # the whole causal prefix resident).
            dec_slots = [s for s in dec_slots
                         if s not in self._residency.slots]
            if not dec_slots and not self._prefilling:
                return None
        sec = self.profiler.sections
        evt = self.trace.evt
        if sec:
            evt("", tag + "pack", "B")
        pack, budget = self._mixed_shape()
        operands, a = pack.host()

        t = 0
        for slot in dec_slots:
            a["tokens"][t] = self._last_token[slot]
            a["token_slot"][t] = slot
            a["token_pos"][t] = self._lengths[slot]
            a["sample_src"][slot] = t
            a["feed_tokens"][slot] = self._last_token[slot]
            a["feed_active"][slot] = True
            a["seq_q_start"][slot] = t
            a["seq_q_len"][slot] = 1
            a["seq_pos_start"][slot] = self._lengths[slot]
            t += 1

        completing, chunk_take, t = self._fill_chunk_lanes(a, t, budget)

        want_lp = any(self._slots[s].request.params.logprobs is not None
                      for s in dec_slots)
        want_lp = want_lp or any(
            st.request.params.logprobs is not None
            for _, st, _, _ in completing)
        a["lengths"][...] = self._lengths
        a["tables"][...] = self._tables
        if self._win is not None:
            # A chunk's window pages, now that the step's takes are known
            # (the decoding slots' were covered with their full pages).
            for slot, take in chunk_take:
                self._win_cover(slot, self._prefilling[slot].pos, take)
            a["win_tables"][...] = self._win.tables
        n_chunk = sum(take for _, take in chunk_take)
        if sec:
            evt("", tag + "pack", "E", (t, n_chunk, len(self._prefilling)))
        # qmax mirrors the dispatcher: t_flat - b_lanes + 1.
        self._mixed_account(a, t, n_chunk, budget, budget + 1, tag)
        self._emit("mixed", lp=want_lp, **a)
        t0 = time.monotonic()
        args = (self.params, self._cache, self._sampling, operands,
                self._guide_dev)
        if sec:
            evt("", tag + "dispatch", "B")
        self.metrics.step_device_calls_total.inc(1, site="step")
        lp_devs = None
        if want_lp:
            ids_dev, clps, lvals, lids, self._cache, self._sampling = \
                self._mixed_lp_fn(*args)
            lp_devs = (clps, lvals, lids)
        else:
            ids_dev, self._cache, self._sampling = self._mixed_fn(*args)
        self.step_clock.dispatched(
            "seq_tail" if pack is self._mixed_tail_pack else "seq", t0,
            time.monotonic(), t)
        if sec:
            evt("", tag + "dispatch", "E",
                "arks_mixed_seq_lp" if want_lp else "arks_mixed_seq")
        self._flush_deferred(tag + "deliver")
        return (dec_slots, completing, chunk_take, want_lp, ids_dev,
                lp_devs, t0, self.ecfg.num_slots + budget)

    @_scoped("mixed")
    def _resolve_mixed(self, rec, exclude_s: float = 0.0) -> None:
        """Host-sync tail of a mixed dispatch: fan the decode tokens out,
        advance every prefilling sequence's position, and promote the
        sequences whose prompt completed (set_slot + registration — the
        same tail as the legacy final chunk, minus its extra sample_one
        dispatch)."""
        (dec_slots, completing, chunk_take, want_lp, ids_dev,
         lp_devs, t0, n_rows) = rec
        self._faults.fire("resolve")
        tag = "phase.mixed."
        sec = self.profiler.sections
        evt = self.trace.evt
        if sec:
            evt("", tag + "wait", "B")
        t_wait = time.monotonic()
        ids = np.asarray(ids_dev)   # [B] — host sync point
        self.step_clock.waited(t_wait, time.monotonic(), 0)
        if self._held_stat:
            self._count_held(ids, n_rows)
        if lp_devs is not None:
            clps = np.asarray(lp_devs[0])
            lvals = np.asarray(lp_devs[1])
            lids = np.asarray(lp_devs[2])
        if sec:
            evt("", tag + "wait", "E")
            evt("", tag + "fanout", "B")
        self._defer_delivery()
        n_live = len(self._slots)
        dt = max(time.monotonic() - t0 - exclude_s, 1e-6)
        for slot in dec_slots:
            lp_rows = None
            if (want_lp and self._slots[slot].request.params.logprobs
                    is not None):
                lp_rows = ([clps[slot]], [lvals[slot]], [lids[slot]])
            self._fanout_decode_tokens(slot, [int(ids[slot])], lp_rows, dt)
        if sec:
            evt("", tag + "fanout", "E",
                (len(dec_slots), n_live - len(self._slots)))
        self._mixed_finish_chunks(chunk_take, completing, ids, want_lp,
                                  lp_devs and (clps, lvals, lids), tag)

    def _promote_completing(self, completing, ids, want_lp, lp_host) -> None:
        """Promote sequences whose prompt completed inside a mixed (or
        spec-mixed) batch: ONE promotion program for all of them
        (_apply_set_slots), then registration — the same tail as the
        legacy final chunk, minus its extra sample_one dispatch."""
        if not completing:
            return
        rows, payload, firsts = [], [], []
        for slot, st, gid, grow0 in completing:
            p = st.request.params
            first = int(ids[slot])
            grow1 = self.guides.next_row(grow0, first) if gid >= 0 else 0
            rows.append((slot, p, st.key, True, len(st.ids), gid, grow1))
            firsts.append(first)
            if self.dispatcher is not None:
                payload.append(dict(
                    slot=slot, temperature=p.temperature, top_p=p.top_p,
                    top_k=p.top_k, seed=st.seed,
                    presence=p.presence_penalty,
                    frequency=p.frequency_penalty,
                    logit_bias=list(p.logit_bias),
                    min_tokens=p.min_tokens,
                    stop_ids=list(p.stop_token_ids),
                    ignore_eos=p.ignore_eos, num_prompt=len(st.ids),
                    guide=gid, guide_row=grow1))
        self._emit("set_slots", rows=payload)
        self._apply_set_slots(rows, "promote")
        for (slot, st, gid, _), first in zip(completing, firsts):
            del self._prefilling[slot]
            p = st.request.params
            first_lp = None
            if want_lp and p.logprobs is not None and lp_host is not None:
                clps, lvals, lids = lp_host
                first_lp = self._lp_entry(clps[slot], lvals[slot],
                                          lids[slot], p.logprobs)
            self._register_slot(st.request, slot, first, len(st.ids),
                                first_lp=first_lp, seed=st.seed)
            # Zero-cost harvest, as in the legacy chunk path: every full
            # prompt page is now written — register the digest chain so
            # later prompts share on device.
            self._register_prompt_pages(st.ids,
                                        self._slot_pages.get(slot, []),
                                        st.digests)

    # ------------------------------------------------------------------
    # Speculative decoding: draft+verify as a ragged mixed dispatch
    # ------------------------------------------------------------------

    @_scoped("spec")
    def _issue_spec_mixed(self):
        """Build and issue ONE spec-mixed dispatch: every decoding slot
        owns a fixed q_len=draft_len verify block (row 0 its last token —
        the draft's proposals are scattered into rows 1.. ON DEVICE), and
        prefill-chunk tokens ride the region after the blocks, so one
        program per iteration serves decode feeds + prefill chunks + spec
        verify.  ELIGIBLE slots advance 1..draft_len tokens by rejection
        sampling; disabled slots advance exactly one normally-sampled
        token (penalties/logprobs served); greedy slots are byte-exact vs
        the target-only mixed path, sampled slots exact in distribution.
        Returns the pending record for _resolve_spec_mixed."""
        DK = self.ecfg.draft_len
        tag = "phase.spec."
        if not self._mixed_begin(DK, tag):
            return None
        self._faults.fire("spec")
        num_slots = self.ecfg.num_slots
        spec_t = num_slots * DK
        sec = self.profiler.sections
        evt = self.trace.evt
        if sec:
            evt("", tag + "pack", "B")
        operands, a = self._spec_pack.host()

        dec_slots = list(self._slots.keys())
        for slot in dec_slots:
            st = self._slots[slot]
            r0 = slot * DK
            a["tokens"][r0] = self._last_token[slot]
            a["token_slot"][r0: r0 + DK] = slot
            a["token_pos"][r0: r0 + DK] = np.arange(
                self._lengths[slot], self._lengths[slot] + DK)
            a["sample_src"][slot] = r0
            a["feed_tokens"][slot] = self._last_token[slot]
            a["feed_active"][slot] = True
            a["seq_q_start"][slot] = r0
            a["seq_q_len"][slot] = DK
            a["seq_pos_start"][slot] = self._lengths[slot]
            a["spec_enable"][slot] = st.spec_ok

        completing, chunk_take, t = self._fill_chunk_lanes(a, spec_t)

        want_lp = any(self._slots[s].request.params.logprobs is not None
                      for s in dec_slots)
        want_lp = want_lp or any(
            st.request.params.logprobs is not None
            for _, st, _, _ in completing)
        a["lengths"][...] = self._lengths
        a["tables"][...] = self._tables
        n_chunk = sum(take for _, take in chunk_take)
        rows = len(dec_slots) * DK + n_chunk
        if sec:
            evt("", tag + "pack", "E",
                (rows, n_chunk, len(self._prefilling)))
        self._mixed_account(a, rows, n_chunk, self._mixed_budget,
                            spec_t + self._mixed_budget - num_slots + 1,
                            tag)
        self._emit("spec_mixed", lp=want_lp, **a)
        t0 = time.monotonic()
        args = (self.params, self._draft_params, self._cache,
                self._draft_cache, self._sampling, operands,
                self._guide_dev)
        if sec:
            evt("", tag + "dispatch", "B")
        self.metrics.step_device_calls_total.inc(1, site="step")
        lp_devs = None
        if want_lp:
            (out_dev, counts_dev, comp_dev, clps, lvals, lids, self._cache,
             self._draft_cache, self._sampling) = self._spec_mixed_lp_fn(
                 *args)
            lp_devs = (clps, lvals, lids)
        else:
            (out_dev, counts_dev, comp_dev, self._cache, self._draft_cache,
             self._sampling) = self._spec_mixed_fn(*args)
        self.step_clock.dispatched("spec", t0, time.monotonic(), rows)
        if sec:
            evt("", tag + "dispatch", "E",
                "arks_spec_mixed_lp" if want_lp else "arks_spec_mixed")
        self._flush_deferred(tag + "deliver")
        return (dec_slots, completing, chunk_take, want_lp, out_dev,
                counts_dev, comp_dev, lp_devs, t0)

    @_scoped("spec")
    def _resolve_spec_mixed(self, rec, exclude_s: float = 0.0) -> None:
        """Host-sync tail of a spec-mixed dispatch: fan each decoding
        slot's accepted block out (1..draft_len tokens), account the
        acceptance metrics, advance the prefilling sequences, and promote
        completed prompts — the same tail shape as _resolve_mixed."""
        (dec_slots, completing, chunk_take, want_lp, out_dev, counts_dev,
         comp_dev, lp_devs, t0) = rec
        self._faults.fire("resolve")
        DK = self.ecfg.draft_len
        tag = "phase.spec."
        sec = self.profiler.sections
        evt = self.trace.evt
        if sec:
            evt("", tag + "wait", "B")
        t_wait = time.monotonic()
        out = np.asarray(out_dev)        # [B, DK] — host sync point
        counts = np.asarray(counts_dev)  # [B]
        comp = np.asarray(comp_dev)      # [B]
        self.step_clock.waited(t_wait, time.monotonic(), 0)
        lp_host = None
        if lp_devs is not None:
            lp_host = (np.asarray(lp_devs[0]), np.asarray(lp_devs[1]),
                       np.asarray(lp_devs[2]))
        if sec:
            evt("", tag + "wait", "E")
            evt("", tag + "fanout", "B")
        self._defer_delivery()
        n_live = len(self._slots)
        dt = max(time.monotonic() - t0 - exclude_s, 1e-6)
        n_spec = accepted = 0
        for slot in dec_slots:
            st = self._slots[slot]
            c = max(1, min(int(counts[slot]), DK))
            if st.spec_ok:
                n_spec += 1
                accepted += c - 1
                self.metrics.spec_decode_accepted_length.observe(c)
            lp_rows = None
            if want_lp and st.request.params.logprobs is not None:
                # Disabled lp slots advance exactly one token (c == 1);
                # the entry comes from the position-0 verifier logits.
                lp_rows = ([lp_host[0][slot]], [lp_host[1][slot]],
                           [lp_host[2][slot]])
            self._fanout_decode_tokens(
                slot, [int(x) for x in out[slot][:c]], lp_rows, dt)
        if n_spec:
            self.metrics.spec_decode_proposed_tokens_total.inc(
                (DK - 1) * n_spec)
            self.metrics.spec_decode_accepted_tokens_total.inc(accepted)
            self._spec_proposed += (DK - 1) * n_spec
            self._spec_accepted += accepted
            self.metrics.spec_decode_acceptance_rate.set(
                self._spec_accepted / max(self._spec_proposed, 1))
        if sec:
            evt("", tag + "fanout", "E",
                (len(dec_slots), n_live - len(self._slots)))
        self._mixed_finish_chunks(chunk_take, completing, comp, want_lp,
                                  lp_host, tag)

    # ------------------------------------------------------------------
    # Stop handling
    # ------------------------------------------------------------------

    def _is_stop(self, st: _Slot, tok: int) -> bool:
        p = st.request.params
        if p.ignore_eos:
            return tok in p.stop_token_ids
        return tok in self.cfg.eos_token_ids or tok in self.tokenizer.eos_token_ids \
            or tok in p.stop_token_ids

    def _finish_reason(self, st: _Slot) -> str:
        if len(st.generated) >= st.request.params.max_tokens:
            return "length"
        return "stop"

    def _check_finished(self, slot: int) -> bool:
        st = self._slots[slot]
        tok = st.generated[-1]
        if self._is_stop(st, tok) or len(st.generated) >= st.request.params.max_tokens:
            self._finish(slot, self._finish_reason(st))
            return True
        return False

    def _release_slot_pages(self, slot: int) -> None:
        """Paged layout: return the slot's page references and park it at
        the write-drop sentinel (its garbage dispatch rows must never land
        in pages another slot may now own).  Index-retained prefix pages
        live on for future hits."""
        if not self._paged:
            return
        if self._residency is not None:
            # Engaged slots: slot_pages already lists staging + hot tail
            # (the decref below returns them); the host store just drops.
            self._residency.release(slot)
        pages = self._slot_pages.pop(slot, [])
        if pages:
            self._alloc.decref(pages)
        if self._win is not None:
            self._win.release(slot)
        self._pool_reserved.pop(slot, None)
        self._lengths[slot] = self._park_sentinel()

    def _clear_shaping(self, slot: int, p) -> None:
        """Re-arm shaped()'s lax.cond fast paths when a slot whose request
        had penalties, a bias, min_tokens or a guide is freed: a stale row
        on a FREE slot would keep every future dispatch paying the shaping
        reads."""
        if not (p.presence_penalty or p.frequency_penalty or p.logit_bias
                or p.min_tokens or p.guide is not None):
            return
        self._emit("clear_penalties", slot=slot)
        self.metrics.step_device_calls_total.inc(1, site="clear")
        self._sampling = self._clear_pen_fn(self._sampling, np.int32(slot))

    def _finish(self, slot: int, reason: str) -> None:
        st = self._slots.pop(slot)
        self._release_slot_pages(slot)
        self._free.append(slot)
        self._unpin_guide(st.request)
        p = st.request.params
        self._clear_shaping(slot, p)
        gen = st.generated
        # The stop token itself is not part of the output text.
        if reason == "stop" and gen and self._is_stop(st, gen[-1]):
            final_ids = gen[:-1]
        else:
            final_ids = gen[: st.request.params.max_tokens]
        delta = final_ids[st.num_emitted:]
        lp_delta = None
        if p.logprobs is not None and st.logprobs:
            lp_delta = st.logprobs[st.num_emitted: len(final_ids)]
        self._deliver(st.request, RequestOutput(
            request_id=st.request.request_id,
            token_ids=delta,
            logprobs=lp_delta,
            finished=True, finish_reason=reason,
            num_prompt_tokens=st.num_prompt,
            num_generated_tokens=len(final_ids)))
        now = time.monotonic()
        self.metrics.e2e_request_latency_seconds.observe(now - st.request.arrival_time)
        self.metrics.request_success_total.inc(reason=reason)
        self.metrics.num_requests_running.set(len(self._slots))
        self.trace.evt(st.request.request_id, "finish", "I", reason)

    # ------------------------------------------------------------------
    # Output delivery
    # ------------------------------------------------------------------

    def _deliver(self, req: Request, out: RequestOutput) -> None:
        """Hand ``out`` to ``req``'s reader: the ONE door from the engine
        thread to ``request.outputs`` (arkslint ``direct-output-put``), so
        that no frame overtakes an earlier one of its request.  While a
        deferral is open the frame joins it instead and leaves with
        _flush_deferred."""
        if self._deferred is not None:
            out.t_made = time.monotonic()
            self._deferred.append((req, out))
            return
        out.t_put = time.monotonic()
        req.outputs.put(out)
        self.metrics.fanout_outputs_total.inc(1)

    def _defer_delivery(self) -> None:
        """Open a deferral: the resolve that calls this leaves nothing in
        flight on the device (a sequential resolve never has a dispatch
        behind it; a pipelined one calls it when it popped the last), so
        every millisecond until the next dispatch is one the device
        idles, and that dispatch needs this step's token VALUES, not
        their delivery.  Each put wakes a reader thread that wants the
        GIL (docs/monitoring.md, ``deliver``): the frames wait for the
        dispatch and leave behind it."""
        if self._deferred is None:
            self._deferred = []

    def _flush_deferred(self, section: str = "phase.step.deliver") -> None:
        """Close the open deferral, if any, and deliver its frames in the
        order they were produced, as the step section ``section``.  Called
        right after the next step's dispatch (the device is busy, and the
        engine thread's next blocking call, the wait, gives the readers
        the GIL), and before anything that keeps the engine thread from
        that dispatch: a step that issues nothing, the residency forward,
        a resize or a model switch at its drained boundary, a replay gate
        taking a victim's queue (preemption, recovery), the loop's exit.
        (``idle`` is false over an open deferral, so the engine neither
        sleeps on its queue nor scales to zero over one.)"""
        batch = self._deferred
        if batch is None:
            return
        self._deferred = None
        sec = self.profiler.sections
        if sec:
            self.trace.evt("", section, "B")
        for req, out in batch:
            out.t_put = time.monotonic()
            req.outputs.put(out)
        if batch:
            self.metrics.fanout_outputs_total.inc(len(batch))
            self.metrics.fanout_deferred_outputs_total.inc(len(batch))
        if sec:
            self.trace.evt("", section, "E", len(batch))
