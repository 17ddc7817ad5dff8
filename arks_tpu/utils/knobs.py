"""The typed ``ARKS_*`` configuration-knob registry.

Every environment variable the runtime reads is declared here once, with
a type, default, one-line doc, and owning subsystem — and this module's
accessors are the ONLY sanctioned way to read one.  ``arkslint``
(``python -m arks_tpu.analysis``, rule ``knobs``) statically rejects raw
``os.environ``/``os.getenv`` reads of ``ARKS_*`` names anywhere else
under ``arks_tpu/``, and rejects accessor calls whose name is missing
from the registry — so a knob cannot exist without documentation, and
the generated ``docs/configuration.md`` table (``render_markdown()``)
is complete by construction.

Deliberately import-light (stdlib only): the router, gateway, and the
analyzer itself read knobs without dragging in JAX.

Reads are live (``os.environ`` at call time, no snapshot): tests and
launchers monkeypatch the environment and expect the next read to see
it.  Typed accessors raise ``ValueError`` naming the knob on a
malformed value — every call site used to hand-roll that message.
"""

from __future__ import annotations

import dataclasses
import os

__all__ = [
    "Knob", "REGISTRY", "is_registered", "raw", "get_str", "get_int",
    "get_float", "get_bool", "get_list", "push", "render_markdown",
]


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    type: str                       # str | int | float | bool | enum | list
    default: str | None             # raw (pre-parse) default; None = unset
    doc: str
    subsystem: str
    choices: tuple[str, ...] = ()   # for type == "enum"


REGISTRY: dict[str, Knob] = {}


def _k(name: str, type: str, default: str | None, doc: str, subsystem: str,
       choices: tuple[str, ...] = ()) -> None:
    if name in REGISTRY:
        raise ValueError(f"duplicate knob registration: {name}")
    REGISTRY[name] = Knob(name, type, default, doc, subsystem, choices)


# --------------------------------------------------------------- engine
_k("ARKS_FAULT_RETRIES", "int", "1",
   "Per-request fault retry budget before a culprit request is "
   "quarantined and failed alone.", "engine")
_k("ARKS_FAULT_INJECT", "str", None,
   "Chaos hook: comma-separated `phase:nth:kind` fault-injection specs "
   "(see engine/faults.py).", "engine")
_k("ARKS_FAULT_HANG_S", "float", "3600",
   "Sleep length of an injected `hang` fault (the watchdog-escalation "
   "fixture).", "engine")
_k("ARKS_DISPATCH_DEADLINE_S", "float", "0",
   "Watchdog deadline for a wedged device dispatch; past it the engine "
   "flips readiness and exits 70. 0 disables; must exceed the worst "
   "in-step jit compile.", "engine")
_k("ARKS_OVERLAP_DECODE", "enum", "auto",
   "Overlapped (async-dispatch) decode: auto = on where the platform "
   "supports it.", "engine", ("auto", "0", "1"))
_k("ARKS_PIPELINE_DEPTH", "int", "2",
   "In-flight dispatch depth of the pipelined decode loop; 0 falls back "
   "to the unpipelined step.", "engine")
_k("ARKS_MIXED_STEP", "enum", "auto",
   "Single mixed prefill+decode dispatch per step: auto = on where "
   "supported.", "engine", ("auto", "0", "1"))
_k("ARKS_RESIDENCY_WINDOW_PAGES", "int", "0",
   "Windowed-residency attention: device-page budget per slot for "
   "contexts larger than the device pool — cold pages spill to the "
   "host tier and stream back through a staging window while the "
   "kernel attends span-by-span with carried softmax state.  0 "
   "disables (out-of-pool contexts are rejected as before).  Requires "
   "the Pallas ragged mixed path.", "engine")
_k("ARKS_MIXED_CHUNK_TOKENS", "int", None,
   "Prefill-token budget of one mixed dispatch (defaults to the chunked-"
   "prefill chunk size; clamped to max_cache_len).", "engine")
_k("ARKS_ADMIT_BATCH_SIZES", "list", "8,4,2,1",
   "Descending jit-bucket sizes for fused admission dispatches.",
   "engine")
_k("ARKS_PAD_HEAD_DIM", "bool", "1",
   "Lane-pad stored KV head dim to 128 so d<128 models ride the Pallas "
   "decode kernels; 0 opts out.", "engine")
_k("ARKS_PREFIX_HOST_MB", "int", "256",
   "Host-RAM byte budget (MiB) of the tier-1 prefix KV cache; 0 "
   "disables the host tier.", "engine")
_k("ARKS_PREFIX_DISK_MB", "int", "0",
   "Local-disk byte budget (MiB) of the tier-2 prefix KV block store, "
   "fed from tier-1 LRU evictions; 0 disables the disk tier.", "engine")
_k("ARKS_PREFIX_DISK_DIR", "str", None,
   "Directory for the tier-2 prefix block store; epoch-stamped so warm "
   "prefixes survive engine restarts on the same pool layout.  Unset "
   "with ARKS_PREFIX_DISK_MB>0 uses <tmpdir>/arks-prefix-disk.",
   "engine")
_k("ARKS_PEER_FETCH", "bool", "0",
   "Fetch missing prefix KV blocks from peer replicas (router "
   "X-Arks-Peer-Hint or ARKS_PEER_ADDRS) over GET /v1/cache/blocks/"
   "{digest} instead of re-prefilling.", "engine")
_k("ARKS_PEER_FETCH_TIMEOUT_S", "float", "5",
   "Per-request HTTP timeout for one peer block fetch; a timeout falls "
   "back to chunked re-prefill of the uncovered tail.", "engine")
_k("ARKS_PEER_ADDRS", "list", None,
   "Static comma-separated peer base addresses (host:port) probed for "
   "prefix blocks when no router peer hint accompanies the request.",
   "engine")
_k("ARKS_PREEMPT", "bool", "0",
   "Enable preemptive KV swap: latency-tier arrivals seize running "
   "low-tier slots by spilling their decode state to host RAM.",
   "engine")
_k("ARKS_PREEMPT_MAX_INFLIGHT", "int", "1",
   "Max concurrent preemption swap-outs in flight.", "engine")
_k("ARKS_PREEMPT_COOLDOWN_S", "float", "2",
   "Minimum spacing between preemptions of the same slot.", "engine")
_k("ARKS_QUEUE_AGING_S", "float", "0",
   "Queue-aging half-life for tier promotion of starved requests; 0 "
   "disables aging.", "engine")
_k("ARKS_FAIR", "bool", "1",
   "Tenant-fair admission: weighted deficit round-robin across tenants "
   "within each SLO tier. 0 reverts to the flat priority heap (the "
   "tests' control arm).", "engine")
_k("ARKS_FAIR_QUANTUM_TOKENS", "int", "512",
   "Token credit (prompt + max_tokens cost units) each tenant earns per "
   "fair-queue round-robin visit.", "engine")
_k("ARKS_FAIR_WEIGHTS", "str", None,
   "Per-tenant fair-share weights as `namespace/user:weight,...`; "
   "unlisted tenants weigh 1. Shared by the engine's WDRR admission "
   "and the gateway's edge shedding.", "engine")
_k("ARKS_QUEUE_MAX", "int", "0",
   "Admission-queue depth cap across all tiers/tenants; a put past it "
   "is shed with 503 + drain-rate Retry-After. 0 = unbounded.",
   "engine")
_k("ARKS_QUEUE_TENANT_MAX", "int", "0",
   "Per-tenant admission-queue depth cap; a put past it is shed with "
   "429 + Retry-After while other tenants keep admitting. 0 = "
   "unbounded.", "engine")
_k("ARKS_SHED_DEADLINE", "float", "0",
   "Deadline-aware shedding factor: a popped request whose queue wait "
   "exceeds factor x its tier's ttft_ms budget is rejected before "
   "prefill (shed_deadline -> 503 + Retry-After). 0 = off.", "engine")
_k("ARKS_TENANT_LABEL_MAX", "int", "32",
   "Metric-label cardinality bound for tenant labels: the first N "
   "distinct tenants keep their id, later ones share the `other` "
   "bucket.", "engine")
_k("ARKS_SLO_TIERS", "str", None,
   "The SLO tier ladder, best tier first (see arks_tpu/slo.py for the "
   "spec grammar). Unset = no tiers.", "engine")
_k("ARKS_MODEL_SWITCH_POLICY", "enum", "drain",
   "Multi-model switch policy: drain (switch at empty) or timeslice "
   "(round-robin on a quantum).", "engine", ("drain", "timeslice"))
_k("ARKS_MODEL_SWITCH_QUANTUM_S", "float", "5",
   "Timeslice quantum for the timeslice switch policy.", "engine")
_k("ARKS_MODEL_POOL_HBM_MB", "int", "0",
   "HBM budget (MiB) for pooled model weights; LRU-evicts idle unpinned "
   "models. 0/unset = unlimited.", "engine")
_k("ARKS_GUIDE_MAX", "int", "8",
   "Max resident compiled guides (guided-decoding DFA tables).",
   "engine")
_k("ARKS_GUIDE_ROWS", "int", "4096",
   "Max total DFA rows across resident guides.", "engine")
_k("ARKS_GUIDE_CLASSES", "int", "2048",
   "Max token-equivalence classes per guide.", "engine")
_k("ARKS_GUIDE_COMPILE_WORKERS", "int", "2",
   "Guide-compilation worker-thread pool size.", "engine")
_k("ARKS_JSON_DEPTH", "int", "3",
   "Max nesting depth of the JSON-schema guide compiler.", "engine")

# ------------------------------------------------------------ multihost
_k("ARKS_COORDINATOR_ADDRESS", "str", None,
   "Leader pod address (host:port) for jax.distributed multi-host "
   "init; unset = single host.", "multihost")
_k("ARKS_PROCESS_ID", "int", "0",
   "Worker index within the gang (0 = leader; only the leader serves "
   "HTTP).", "multihost")
_k("ARKS_NUM_PROCESSES", "int", "1", "Gang size.", "multihost")
_k("ARKS_NUM_SLICES", "int", "1",
   "Slice count of a multi-slice topology (the k8s renderer passes it; "
   "an explicit --num-slices flag wins).", "multihost")
_k("ARKS_DISPATCH_ADDRESS", "str", None,
   "Explicit gang-dispatch channel address; defaults to the coordinator "
   "host on a derived port.", "multihost")
_k("ARKS_GANG_SECRET", "str", "arks-gang",
   "Shared secret authenticating gang dispatch/heartbeat peers.",
   "multihost")
_k("ARKS_GANG_HB_INTERVAL", "float", "2",
   "Follower heartbeat interval (seconds).", "multihost")
_k("ARKS_GANG_STALE_S", "float", "15",
   "Follower heartbeat age past which the leader reports the gang "
   "degraded.", "multihost")
_k("ARKS_GANG_WEDGE_FATAL_S", "float", "120",
   "Leader exits after a follower channel has been wedged this long so "
   "the gang driver restarts the gang.", "multihost")

# --------------------------------------------------------------- server
_k("ARKS_DRAIN_TIMEOUT", "float", "20",
   "SIGTERM grace: finish in-flight requests up to this many seconds "
   "before exiting.", "server")
_k("ARKS_TOOL_PARSER", "enum", "auto",
   "Tool-call parser dialect for /v1/chat/completions tools.", "server",
   ("auto", "hermes", "llama3", "mistral", "qwen"))

# -------------------------------------------------------------- kernels
_k("ARKS_ATTN_IMPL", "enum", "auto",
   "Decode attention implementation.", "kernels",
   ("auto", "pallas", "xla"))
_k("ARKS_ATTN_BLOCK_S", "int", "256",
   "Sequence block of the Pallas decode attention grid.", "kernels")
_k("ARKS_ATTN_BLOCK_B", "int", "16",
   "Batch block of the Pallas decode attention grid.", "kernels")
_k("ARKS_KERNEL_TUNE", "enum", "cached",
   "Kernel autotune mode: off = built-in defaults, cached = use the "
   "persisted table, sweep = retune and persist.", "kernels",
   ("off", "cached", "sweep"))
_k("ARKS_KERNEL_TUNE_CACHE", "str", None,
   "Autotune table path; defaults to ARKS_MODEL_DIR/kernel_tune.json, "
   "else ~/.cache/arks_tpu/kernel_tune.json.", "kernels")
_k("ARKS_MODEL_DIR", "str", None,
   "Model checkpoint directory (also anchors the autotune table).",
   "kernels")
_k("ARKS_INT4_GROUP", "int", "128",
   "int4 weight-quantization group size along the contraction dim.",
   "kernels")

# -------------------------------------------------------------- gateway
_k("ARKS_NATIVE", "bool", "1",
   "Use the native (compiled) gateway hot-path helpers when available; "
   "0 forces the pure-Python fallback.", "gateway")
_k("ARKS_NATIVE_LIB", "str", None,
   "Path to a prebuilt native helper .so (skips the on-demand build).",
   "gateway")
_k("ARKS_GW_COLD_START_WAIT_S", "float", "10",
   "How long gateway admission holds a request for a cold-starting "
   "model before 503ing.", "gateway")
_k("ARKS_GW_SHED_INFLIGHT", "int", "0",
   "Gateway edge-shedding trigger: once this many proxied requests are "
   "in flight, new arrivals from the most-over-share tenant "
   "(in-flight/weight, per ARKS_FAIR_WEIGHTS) get 429 + Retry-After at "
   "the edge. 0 = off.", "gateway")
_k("ARKS_GW_DISCONNECT_DRAIN_S", "float", "10",
   "After a streaming client disconnects mid-relay, keep draining the "
   "backend response (feeding the usage scanner) for up to this long "
   "so the stream's tokens are still metered exactly.", "gateway")

# --------------------------------------------------------------- router
_k("ARKS_PREFILL_ADDRS", "list", None,
   "Static prefill backend addresses (comma-separated host:port).",
   "router")
_k("ARKS_DECODE_ADDRS", "list", None,
   "Static decode backend addresses (comma-separated host:port).",
   "router")
_k("ARKS_ROUTER_UNIFIED", "bool", "0",
   "Treat every backend as both prefill and decode (single-tier "
   "routing).", "router")
_k("ARKS_ROUTER_RETRY_BACKOFF_S", "float", "0.05",
   "Backoff between failover attempts to the next backend candidate.",
   "router")
_k("ARKS_ROUTER_SKETCH", "bool", "1",
   "Cache-aware routing from backend prefix-digest sketches; 0 falls "
   "back to rendezvous/least-loaded only.", "router")
_k("ARKS_ROUTER_SKETCH_POLL_S", "float", "2.0",
   "Sketch poll interval per decode backend.", "router")
_k("ARKS_ROUTER_SKETCH_STALE_S", "float", "10",
   "Sketch age past which a backend's sketch is ignored for scoring.",
   "router")
_k("ARKS_ROUTER_SKETCH_T0_WEIGHT", "float", "1.0",
   "Extra score weight of a tier-0 (device) block over a host-tier "
   "block.", "router")
_k("ARKS_ROUTER_SKETCH_DISK_WEIGHT", "float", "0.5",
   "Score weight of a tier-2 (disk) block relative to a host-tier "
   "block; disk hits restore slower than RAM but still beat "
   "re-prefill.", "router")
_k("ARKS_ROUTER_SKETCH_MAX_BLOCKS", "int", "64",
   "Max prompt prefix blocks hashed per routing decision.", "router")
_k("ARKS_ROUTER_SKETCH_CHARS", "int", "256",
   "Prompt characters per prefix block digest.", "router")
_k("ARKS_ROUTER_SKETCH_BITS", "int", "16384",
   "Bloom filter width (bits) of the exported sketch.", "router")
_k("ARKS_ROUTER_SKETCH_HASHES", "int", "4",
   "Bloom filter hash count.", "router")
_k("ARKS_ROUTER_SKETCH_TOPK", "int", "128",
   "Top-K exact digests exported alongside the bloom filter.", "router")
_k("ARKS_ROUTER_SKETCH_LINKS", "int", "4096",
   "Max parent->child digest links kept in the sketch chain index.",
   "router")

# -------------------------------------------------------------- elastic
_k("ARKS_ELASTIC_COOLDOWN_S", "float", "30",
   "Minimum seconds between autoscaler-driven elastic actions on one "
   "application (scale-up-from-zero is exempt).", "elastic")
_k("ARKS_ELASTIC_BURN_HI", "float", "1.0",
   "SLO burn rate above which the signals-mode autoscaler scales up "
   "even when RPM alone would not.", "elastic")
_k("ARKS_ELASTIC_BURN_LO", "float", "0.25",
   "SLO burn rate below which (together with ARKS_ELASTIC_SAT_LO) "
   "signals-mode scale-down becomes eligible.", "elastic")
_k("ARKS_ELASTIC_SAT_HI", "float", "0.9",
   "Admission saturation above which the signals-mode autoscaler "
   "scales up.", "elastic")
_k("ARKS_ELASTIC_SAT_LO", "float", "0.3",
   "Admission saturation below which (together with "
   "ARKS_ELASTIC_BURN_LO) signals-mode scale-down becomes eligible.",
   "elastic")
_k("ARKS_ELASTIC_IDLE_ZERO_S", "float", "0",
   "Idle seconds after which a fully drained engine scales itself to "
   "zero (drops params + device KV, keeps host/disk prefix tiers); "
   "0 = never.", "elastic")
_k("ARKS_ELASTIC_WARMUP", "bool", "1",
   "Issue a self-enqueued warm-up request after a live resize or a "
   "scale-from-zero re-arm, before external traffic hits the new "
   "shape.", "elastic")
_k("ARKS_ELASTIC_JOIN_TIMEOUT_S", "float", "10",
   "Seconds the router's planned membership handoff waits for a "
   "joining backend's /readiness to go green before giving up.",
   "elastic")
_k("ARKS_SLO_BURN_WINDOW_S", "float", "60",
   "Rolling window (seconds) over which the engine computes per-tier "
   "SLO burn rates for /readiness and the signals-mode autoscaler.",
   "elastic")
_k("ARKS_SLO_ERROR_BUDGET", "float", "0.1",
   "Allowed fraction of requests missing their tier's ttft_ms target; "
   "burn rate = observed violation fraction / this budget (1.0 = "
   "burning exactly at budget).", "elastic")

# ------------------------------------------------------------------ obs
_k("ARKS_TRACE", "bool", "1",
   "Request tracing (span timelines, flight recorder); 0 disables.",
   "obs")
_k("ARKS_TRACE_RING", "int", "8192",
   "Per-thread trace event ring capacity.", "obs")
_k("ARKS_TRACE_SAMPLE", "float", "1.0",
   "Fraction of requests traced.", "obs")
_k("ARKS_TRACE_TAIL", "int", "256",
   "Flight-recorder tail length (events kept past a finished span).",
   "obs")
_k("ARKS_TRACE_FLUSH_S", "float", "0.2",
   "Trace assembly flush interval.", "obs")
_k("ARKS_TRACE_MAX", "int", "256",
   "Finished traces retained in the in-memory store.", "obs")
_k("ARKS_PROF_AUTO_ARM", "float", "0",
   "Auto-open a profiler window when a step exceeds this multiple of "
   "the trailing median step time; 0 = off.", "obs")
_k("ARKS_PROF_WINDOW_S", "float", "5",
   "Auto-armed profiler window length.", "obs")
_k("ARKS_PROF_DIR", "str", "/tmp/arks-prof",
   "Profiler trace output directory.", "obs")

# -------------------------------------------------------------- control
_k("ARKS_CONVERT_ORBAX", "bool", "0",
   "Convert downloaded safetensors to an Orbax sharded checkpoint after "
   "fetch.", "control")
_k("ARKS_SCRIPTS_IMAGE", "str", "arks-tpu/engine:latest",
   "Model-download worker image.", "control")
_k("ARKS_RUNTIME_DEFAULT_VLLM_IMAGE", "str", None,
   "Default vllm runtime image override.", "control")
_k("ARKS_RUNTIME_DEFAULT_SGLANG_IMAGE", "str", None,
   "Default sglang runtime image override.", "control")
_k("ARKS_RUNTIME_DEFAULT_DYNAMO_IMAGE", "str", None,
   "Default dynamo runtime image override.", "control")
_k("ARKS_RUNTIME_DEFAULT_JAX_IMAGE", "str", None,
   "Default native jax runtime image override.", "control")
_k("ARKS_GANG_LEADER_ADDRESS", "str", None,
   "Exported into GPU runtime containers as the distributed init "
   "address (not read in-process).", "control")
_k("ARKS_GANG_SIZE", "str", None,
   "Exported into runtime containers as the gang size (not read "
   "in-process).", "control")
_k("ARKS_GANG_WORKER_INDEX", "str", None,
   "Exported into runtime containers as the worker rank (not read "
   "in-process).", "control")


# ------------------------------------------------------------ accessors

def is_registered(name: str) -> bool:
    return name in REGISTRY


def _knob(name: str) -> Knob:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"{name} is not a registered knob — declare it in "
            "arks_tpu/utils/knobs.py (arkslint rule `knobs` enforces "
            "this)") from None


def is_set(name: str) -> bool:
    """Whether the environment states the knob (a registry default does
    not count): for a caller whose own default differs from the
    registry's and that has to tell a request from a default."""
    _knob(name)
    return os.environ.get(name) is not None


def raw(name: str, fallback: str | None = None) -> str | None:
    """The raw string value: environment, else the registry default,
    else ``fallback`` (for knobs whose default is computed at the call
    site).  Empty-string env values count as set."""
    knob = _knob(name)
    v = os.environ.get(name)
    if v is not None:
        return v
    if knob.default is not None:
        return knob.default
    return fallback


def get_str(name: str, fallback: str | None = None) -> str | None:
    v = raw(name, fallback)
    knob = REGISTRY[name]
    if v is not None and knob.type == "enum" and knob.choices \
            and v not in knob.choices:
        raise ValueError(
            f"{name}={v!r}: expected one of {'|'.join(knob.choices)}")
    return v


def get_int(name: str, fallback: int | None = None) -> int | None:
    v = raw(name)
    if v is None or v == "":
        return fallback
    try:
        return int(v)
    except ValueError:
        raise ValueError(f"{name}={v!r}: expected an integer") from None


def get_float(name: str, fallback: float | None = None) -> float | None:
    v = raw(name)
    if v is None or v == "":
        return fallback
    try:
        return float(v)
    except ValueError:
        raise ValueError(f"{name}={v!r}: expected a number") from None


def get_bool(name: str, fallback: bool = False) -> bool:
    """Bool knobs: "0"/"false"/"" (and unset without a default) are
    False, anything else is True — matching every historical call site
    (`!= "0"`, `== "1"`, `not in ("", "0", "false")`)."""
    v = raw(name)
    if v is None:
        return fallback
    return v.strip().lower() not in ("", "0", "false")


def get_list(name: str, sep: str = ",") -> list[str]:
    v = raw(name)
    if not v:
        return []
    return [part.strip() for part in v.split(sep) if part.strip()]


def push(name: str, value: str) -> None:
    """Write a knob into the process environment (launchers forwarding
    CLI flags to the engine/watchdog, which read knobs at start).  Keeps
    writes registry-checked too."""
    _knob(name)
    os.environ[name] = str(value)


# ------------------------------------------------------- doc generation

def render_markdown() -> str:
    """The `docs/configuration.md` knob table — generated, never hand
    edited (tests assert the file matches this output)."""
    out = [
        "# Configuration knobs",
        "",
        "Every `ARKS_*` environment variable the runtime reads, generated "
        "from the typed registry in `arks_tpu/utils/knobs.py` "
        "(`python -m arks_tpu.analysis --gen-knob-docs`).  Raw "
        "`os.environ` reads of `ARKS_*` names are rejected by arkslint "
        "(rule `knobs`), so this table is complete by construction.",
        "",
    ]
    subsystems: dict[str, list[Knob]] = {}
    for knob in REGISTRY.values():
        subsystems.setdefault(knob.subsystem, []).append(knob)
    for subsystem in sorted(subsystems):
        out.append(f"## {subsystem}")
        out.append("")
        out.append("| Name | Type | Default | Description |")
        out.append("|---|---|---|---|")
        for knob in sorted(subsystems[subsystem], key=lambda k: k.name):
            typ = knob.type
            if knob.type == "enum" and knob.choices:
                typ = "enum: " + " \\| ".join(knob.choices)
            default = "(unset)" if knob.default is None else \
                f"`{knob.default}`"
            doc = knob.doc.replace("|", "\\|")
            out.append(f"| `{knob.name}` | {typ} | {default} | {doc} |")
        out.append("")
    return "\n".join(out) + ""
