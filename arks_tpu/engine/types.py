"""Request/response dataclasses for the serving engine."""

from __future__ import annotations

import dataclasses
import queue
import time


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    max_tokens: int = 256
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0          # 0 = disabled
    stop_token_ids: tuple[int, ...] = ()
    ignore_eos: bool = False
    seed: int | None = None
    # OpenAI presence/frequency penalties over OUTPUT tokens (vLLM
    # semantics): logits -= presence*1[seen] + frequency*count.
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # None = no logprobs; 0 = chosen-token logprob only; N>0 = plus the
    # top-N alternatives (clamped to sampler.TOP_LOGPROBS_MAX).
    # Logprob-bearing slots ride the fused loop.
    logprobs: int | None = None
    # OpenAI logit_bias as (token_id, bias) pairs (bias in [-100, 100];
    # at most sampler.LOGIT_BIAS_MAX entries — the server rejects more).
    logit_bias: tuple[tuple[int, float], ...] = ()
    # vLLM-style min_tokens: eos/stop token ids are suppressed on device
    # until at least this many tokens have been generated.
    min_tokens: int = 0
    # Admission priority (vLLM semantics: LOWER value admits first; equal
    # priorities stay FIFO).  With SLO tiers configured (arks_tpu.slo)
    # this is the tier index, and under ARKS_PREEMPT a queued lower value
    # may seize a running higher-value slot via preemptive KV swap;
    # ARKS_QUEUE_AGING_S decays a queued request's effective priority so
    # the worst tier still admits under sustained load.
    priority: int = 0
    # Guided decoding: (kind, pattern) compiled by engine.guides —
    # ("json", "") for JSON mode, ("regex", pat) for a regex constraint.
    guide: tuple[str, str] | None = None


@dataclasses.dataclass
class PrefilledState:
    """Result of a detached prefill, transferable between engines.

    The KV tensors are [L, 1, T, Hkv, D] (T = prefill bucket length); the
    decode engine inserts them into its own slotted cache.  ``seed`` lets the
    decode engine reconstruct the sampling key stream exactly where the
    prefill engine left it (prefill consumed the base key; decode starts from
    fold_in(key, 1)).
    """

    first_token: int
    num_prompt: int
    seed: int
    k: object  # np.ndarray | jax.Array [L, 1, T, Hkv, D]
    v: object
    # First-token logprob data (chosen_logprob, [(token_id, logprob)...]),
    # present when the request asked for logprobs — the decode side serves
    # the logprob stream seamlessly from here (its own dispatches cover
    # every later token).
    first_lp: object | None = None
    # Guided decoding: the DFA state AFTER the first token, RELATIVE to
    # the guide's start row (the prefill engine sampled under the guide;
    # the decode engine rebases onto its own table — absolute rows would
    # break when the two engines compiled guides in different orders).
    guide_row: int = 0
    # Prompt token ids (rides the kv_transfer meta).  The decode side
    # needs them to key the transferred KV by chain digest: paged engines
    # register the inserted pages into the device prefix index and
    # publish them into the host spill tier, so a decode-side restart
    # keeps the prefill peer's warm prefixes.  None/[] from a pre-upgrade
    # prefill peer simply skips the publish.
    prompt_ids: list | None = None
    # Informational: the dtype the k/v tensors are stored in ("bf16" /
    # "float32" / ...).  Transferred KV is always full-width (the decode
    # engine re-quantizes on insert — int8 or int4-packed per its own
    # kv_cache_dtype); this marker lets a receiver sanity-check a peer
    # rather than change behavior.
    kv_dtype: str = "bf16"


@dataclasses.dataclass
class Request:
    request_id: str
    prompt_ids: list[int]
    params: SamplingParams
    arrival_time: float = dataclasses.field(default_factory=time.monotonic)
    # Per-request output stream: the engine puts RequestOutput items here;
    # the server consumes them (None-terminated via ``finished``).
    outputs: "queue.Queue[RequestOutput]" = dataclasses.field(default_factory=queue.Queue)
    # Disaggregated serving: KV produced by a prefill engine; when set, the
    # decode engine inserts it instead of running its own prefill.
    prefilled: PrefilledState | None = None
    # Engine-assigned sampling seed (set once at first admission when
    # params.seed is None).  Pinned on the REQUEST so fault recovery can
    # re-admit/replay it with the identical key stream — a fresh counter
    # draw on replay would silently change the resumed stream's tokens.
    assigned_seed: int | None = None
    # Multi-model serving: which pool model this request targets.  None =
    # the engine's primary model.  Requests for a non-active model park in
    # the ``awaiting_model`` state until the scheduler switches to it.
    model: str | None = None
    # Tenant identity (arks_tpu.tenancy): "namespace/username" minted by
    # the gateway (x-arks-tenant) and mapped here by the OpenAI server.
    # Drives the engine's weighted-fair admission and per-tenant queue
    # caps.  None = untenanted (direct-to-pod clients) — all such
    # requests share one fair-queue lane, the pre-tenancy behavior.
    tenant: str | None = None
    # End-to-end tracing: the W3C trace context for this request
    # (arks_tpu.obs.trace.TraceCtx), carrying the gateway-minted trace id
    # and any upstream (gateway/router) spans.  None = untraced or an
    # engine-local request; the engine mints a local trace id on demand.
    trace: object | None = None
    # Fleet prefix cache: peer base address ("host:port") the router
    # believes holds this prompt's warm prefix blocks (X-Arks-Peer-Hint).
    # On an admission miss with ARKS_PEER_FETCH, the engine fetches the
    # blocks from this peer over GET /v1/cache/blocks/{digest} instead
    # of re-prefilling.  None = no hint; ARKS_PEER_ADDRS is the static
    # fallback probe list.
    peer_hint: str | None = None


@dataclasses.dataclass
class RequestOutput:
    request_id: str
    token_ids: list[int]          # newly generated token ids in this chunk
    finished: bool = False
    finish_reason: str | None = None   # "stop" | "length" | "abort" | "error"
    num_prompt_tokens: int = 0
    num_generated_tokens: int = 0      # cumulative, set when finished
    ttft_s: float | None = None        # set on the first chunk
    # Machine-readable rejection code when finish_reason == "error"
    # (e.g. "context_length_exceeded" -> HTTP 400 at the server).
    error: str | None = None
    # Per-token logprob data aligned with token_ids (present only when the
    # request asked for logprobs): each entry is
    # (chosen_logprob, [(token_id, logprob), ...top-N...]).
    logprobs: list | None = None
    # The step clock's stamps (time.monotonic): when the engine put the
    # frame on the request's queue, and, for a frame a saturated resolve
    # held back for the next dispatch, when it was made.  The stream loop
    # reads them; they are never sent, and two frames that differ only in
    # them are the same frame.
    t_put: float | None = dataclasses.field(
        default=None, compare=False, repr=False)
    t_made: float | None = dataclasses.field(
        default=None, compare=False, repr=False)
