"""Serving-pod entrypoint: ``python -m arks_tpu.server [flags]``.

This is the TPU-native runtime command the workload controller generates —
the analogue of the vLLM/SGLang command lines the reference operator writes
(/root/reference/internal/controller/arksapplication_controller.go:941-1014).

Multi-host rendezvous contract (the LWS env-var contract translated to JAX
distributed init — reference controller :560-569):
  ARKS_COORDINATOR_ADDRESS  leader pod address ("host:port")
  ARKS_PROCESS_ID           worker index (0 = leader)
  ARKS_NUM_PROCESSES        gang size
When set, jax.distributed.initialize() is called before anything touches the
backend; collectives then run over ICI within a slice and DCN across slices.
"""

from __future__ import annotations

import argparse
import logging
import os

from arks_tpu.utils import knobs

log = logging.getLogger("arks_tpu.server")


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    p = argparse.ArgumentParser("arks_tpu.server")
    p.add_argument("--model", required=True, help="model config name (arks_tpu.models) "
                   "or path to a model dir with config.json")
    p.add_argument("--model-path", default=None, help="weights/tokenizer dir (optional; "
                   "random init without it)")
    p.add_argument("--served-model-name", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--tensor-parallel-size", "--tp", type=int, default=None, dest="tp")
    p.add_argument("--data-parallel-size", "--dp", type=int, default=1, dest="dp")
    p.add_argument("--context-parallel-size", "--cp", type=int, default=1,
                   dest="cp",
                   help="shard prefill T over a 'seq' mesh axis with ring "
                        "attention (long-context prefill; best on the "
                        "disaggregated prefill tier — decode replicates "
                        "across this axis)")
    p.add_argument("--pipeline-parallel-size", "--pp", type=int, default=1,
                   dest="pp",
                   help="shard layers (and their KV) over a 'stage' mesh "
                        "axis with a microbatched decode pipeline — HBM "
                        "capacity scaling for models beyond one chip; "
                        "exclusive with tp/dp/cp in one engine")
    p.add_argument("--num-slices", type=int, default=None,
                   help="multi-slice serving: an outermost 'slice' mesh "
                        "axis spanning ICI slices joined over DCN (v5p "
                        "multi-slice) — batch/dp shards across slices, tp "
                        "psums stay slice-local (parallel.mesh."
                        "make_multislice_mesh)")
    p.add_argument("--num-slots", type=int, default=8)
    p.add_argument("--max-model-len", type=int, default=1024)
    p.add_argument("--steps-per-dispatch", type=int, default=4)
    p.add_argument("--dtype", default=None)
    p.add_argument("--kv-cache-dtype", default="auto",
                   choices=("auto", "bf16", "int8", "int4"),
                   help="int8 halves KV HBM traffic and doubles cache capacity; "
                        "int4 packs token pairs per byte (paged layout only, "
                        "dequant fused on the page stream)")
    p.add_argument("--weight-dtype", default="bf16",
                   choices=("bf16", "int8", "int4"),
                   help="weight-only quantization: int8 (w8a16, per-channel "
                        "scales) fits 7B-class models on one 16GB chip and "
                        "halves decode weight reads; int4 (w4a16, groupwise "
                        "scales) halves them again — 13B-class single-chip, "
                        "or more HBM left for KV pages")
    p.add_argument("--kv-layout", default="auto",
                   choices=("auto", "slot", "paged"),
                   help="device KV layout: paged = block-table pool with "
                        "on-device prefix sharing (TPU default); slot = "
                        "contiguous per-slot cache (dp)")
    p.add_argument("--prefix-cache-mb", type=int, default=256,
                   help="host-RAM budget for prefix KV reuse (0 disables)")
    p.add_argument("--kv-pool-pages", type=int, default=0,
                   help="a model with window and full attention layers: "
                        "pages of the full layers' pool (0 = every slot's "
                        "whole context, num-slots x max-model-len); below "
                        "that, admission reserves a request's prompt + "
                        "max_tokens in pages and a request the pool cannot "
                        "hold yet waits at the head of the queue")
    p.add_argument("--draft-model", default=None,
                   help="speculative decoding: draft model config name or "
                        "dir (must share the target tokenizer); greedy "
                        "requests emit identical tokens, several per "
                        "dispatch")
    p.add_argument("--draft-model-path", default=None,
                   help="draft weights dir (random init without it)")
    p.add_argument("--draft-len", type=int, default=4,
                   help="tokens per speculative dispatch (draft proposes "
                        "draft-len - 1, target verifies all in one pass)")
    p.add_argument("--extra-model", action="append", default=None,
                   metavar="NAME[=PATH]",
                   help="register an additional model with the shared "
                        "weight pool (repeatable): NAME is a config name "
                        "or a model dir, =PATH an optional weights dir. "
                        "Requests route by their 'model' field; the engine "
                        "streams the weights in and switches at drained "
                        "boundaries (single-host only)")
    p.add_argument("--model-pool-hbm-mb", type=int, default=None,
                   help="HBM budget for pooled model weights in MiB "
                        "(ARKS_MODEL_POOL_HBM_MB; 0/unset = unlimited). "
                        "LRU-evicts idle unpinned models; the primary and "
                        "draft are pinned")
    p.add_argument("--drain-timeout", type=float,
                   default=knobs.get_float("ARKS_DRAIN_TIMEOUT"),
                   help="SIGTERM grace: finish in-flight requests up to "
                        "this many seconds before exiting (rolling updates "
                        "become request-lossless when it covers the longest "
                        "request; launchers set the ARKS_DRAIN_TIMEOUT env "
                        "default to fit their own kill escalation windows)")
    p.add_argument("--dispatch-deadline", type=float, default=None,
                   help="watchdog deadline in seconds for a wedged device "
                        "dispatch: past it the engine flips readiness, "
                        "dumps in-flight diagnostics, and exits 70 so the "
                        "pod restarts (sets ARKS_DISPATCH_DEADLINE_S; "
                        "0/unset disables; must exceed the worst in-step "
                        "jit compile — see docs/runbook.md)")
    p.add_argument("--fault-retries", type=int, default=None,
                   help="per-request fault retry budget before a culprit "
                        "request fails alone with an engine_fault 500 "
                        "(sets ARKS_FAULT_RETRIES; default 1)")
    p.add_argument("--expert-parallel-size", type=int, default=1,
                   help="chips that share each routed layer, experts apart "
                        "(expert parallelism as ONE chip sees it): this pod "
                        "holds the model config's expert count, the router "
                        "scores that many times this size, and the layer "
                        "returns its own experts' part of the result.  A "
                        "deployment's gang sets it per pod; 1 = the pod "
                        "holds every expert")
    p.add_argument("--expert-parallel-rank", type=int, default=0,
                   help="which share of the routed layers this pod holds "
                        "(0 .. expert-parallel-size - 1)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", default=None, help="force a jax platform (cpu for tests)")
    p.add_argument("--disaggregation-mode", choices=("prefill", "decode"),
                   default=None, dest="disagg",
                   help="PD-separated serving role (reference flag parity: "
                        "arksdisaggregatedapplication_controller.go:1672-1724)")
    return p.parse_args(argv)


def build_engine(args: argparse.Namespace):
    """Everything from the parsed flags to a constructed (not yet started)
    ``InferenceEngine``: platform, multi-host init, compile cache, mesh,
    weights, tokenizer, model pool.  ``main`` and ``chip_smoke.py`` both
    come through here, so the smoke proves the pod's own start-up path."""
    # Fault-tolerance knobs travel by env (the engine and its watchdog
    # read them at start); explicit flags win over inherited env.
    if args.dispatch_deadline is not None:
        knobs.push("ARKS_DISPATCH_DEADLINE_S", str(args.dispatch_deadline))
    if args.fault_retries is not None:
        knobs.push("ARKS_FAULT_RETRIES", str(args.fault_retries))

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    coord = knobs.get_str("ARKS_COORDINATOR_ADDRESS")
    if coord:
        pid = knobs.get_int("ARKS_PROCESS_ID")
        nproc = knobs.get_int("ARKS_NUM_PROCESSES")
        log.info("multi-host init: coordinator=%s process=%d/%d", coord, pid, nproc)
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=pid)
    # After the multi-host init: placing the cache asks for the backend,
    # and jax.distributed.initialize must come before the backend exists.
    from arks_tpu.utils import compile_cache
    compile_cache.configure()

    from arks_tpu.engine.engine import EngineConfig, InferenceEngine
    from arks_tpu.engine.tokenizer import load_tokenizer
    from arks_tpu.models import get_config
    from arks_tpu.models.config import ModelConfig

    if os.path.isdir(args.model):
        cfg = ModelConfig.from_hf_config(args.model, name=os.path.basename(args.model))
        model_path = args.model_path or args.model
    else:
        cfg = get_config(args.model)
        model_path = args.model_path
    if args.expert_parallel_size != 1 or args.expert_parallel_rank:
        cfg = cfg.with_expert_share(args.expert_parallel_size,
                                    args.expert_parallel_rank)

    devs = jax.devices()
    n_dev = len(devs)
    # A process that came up on the wrong platform serves from
    # interpret-mode kernels on the CPU and says nothing else about it.
    log.info("jax %s on platform=%s device_kind=%s devices=%d",
             jax.__version__, devs[0].platform, devs[0].device_kind, n_dev)
    # The k8s renderer passes the slice count by env (ARKS_NUM_SLICES);
    # an explicit --num-slices flag wins — including an explicit 1 (the
    # unset default is None, so forcing single-slice in a multi-slice pod
    # is expressible).
    if args.num_slices is None:
        args.num_slices = knobs.get_int("ARKS_NUM_SLICES")
    if (args.dp < 1 or args.cp < 1 or args.pp < 1 or args.num_slices < 1
            or (args.tp is not None and args.tp < 1)):
        raise SystemExit("parallel-size flags must be >= 1")
    if args.pp > 1:
        tp = args.tp or 1  # pp is exclusive with tp; don't auto-fill tp
    else:
        tp = args.tp or max(
            n_dev // (args.dp * args.cp * args.num_slices), 1)
    want = tp * args.dp * args.cp * args.pp * args.num_slices
    if want > n_dev:
        raise SystemExit(
            f"requested tp={tp} x dp={args.dp} x cp={args.cp} "
            f"x pp={args.pp} needs {want} devices but only "
            f"{n_dev} are visible")
    nproc = knobs.get_int("ARKS_NUM_PROCESSES")
    mesh = None
    if want > 1:
        from arks_tpu.parallel.mesh import make_mesh
        if nproc > 1:
            # Multi-host: the mesh MUST span processes with equal local
            # device counts, or some processes own no shard and every
            # cross-process collective deadlocks.  Take want/nproc devices
            # from each process (jax.devices()[:want] would grab them all
            # from process 0 when a host exposes extras).
            if want % nproc:
                raise SystemExit(
                    f"tp*dp*cp={want} must be divisible by the gang size {nproc}")
            per = want // nproc
            taken: dict[int, int] = {}
            devices = []
            for d in jax.devices():
                if taken.get(d.process_index, 0) < per:
                    taken[d.process_index] = taken.get(d.process_index, 0) + 1
                    devices.append(d)
            if len(devices) < want:
                raise SystemExit(
                    f"gang of {nproc} processes exposes only {len(devices)} "
                    f"usable devices, need {want}")
        else:
            # Use exactly the devices the plan asks for; a host may expose
            # more (e.g. a forced multi-device CPU platform) than the spec
            # wants.
            devices = jax.devices()[:want]
        if args.num_slices > 1:
            from arks_tpu.parallel.mesh import make_multislice_mesh
            mesh = make_multislice_mesh(
                args.num_slices, tensor_parallel=tp, data_parallel=args.dp,
                context_parallel=args.cp, pipeline_parallel=args.pp,
                devices=devices)
        else:
            mesh = make_mesh(tensor_parallel=tp, data_parallel=args.dp,
                             context_parallel=args.cp,
                             pipeline_parallel=args.pp, devices=devices)

    params = None
    if model_path:
        from arks_tpu.models.weights import load_params
        params = load_params(cfg, model_path, mesh=mesh, dtype=args.dtype,
                             weight_dtype=args.weight_dtype)

    ecfg = EngineConfig(
        model=cfg.name, num_slots=args.num_slots, max_cache_len=args.max_model_len,
        prefill_buckets=tuple(b for b in (32, 64, 128, 256, 512, 1024, 2048, 4096)
                              if b <= args.max_model_len),
        steps_per_dispatch=args.steps_per_dispatch,
        tensor_parallel=args.tp, data_parallel=args.dp,
        context_parallel=args.cp, pipeline_parallel=args.pp,
        dtype=args.dtype, kv_cache_dtype=args.kv_cache_dtype,
        weight_dtype=args.weight_dtype, seed=args.seed,
        prefix_cache_mb=args.prefix_cache_mb,
        kv_pool_pages=args.kv_pool_pages,
        kv_layout=args.kv_layout,
        draft_model=args.draft_model, draft_len=args.draft_len,
    )
    # Shared weight pool: created whenever anything multi-model is in play
    # (extra models, an explicit budget, or a draft — the draft is served
    # FROM the pool rather than a second standalone load_params, so its
    # residency shows in /v1/models and counts against the budget).
    pool = None
    if args.extra_model or args.model_pool_hbm_mb is not None or args.draft_model:
        from arks_tpu.engine.model_pool import ModelPool
        pool = ModelPool(hbm_budget_mb=args.model_pool_hbm_mb)

    draft_cfg = draft_params = None
    if args.draft_model:
        if os.path.isdir(args.draft_model):
            draft_cfg = ModelConfig.from_hf_config(
                args.draft_model, name=os.path.basename(args.draft_model))
            # A weights DIR as --draft-model loads from that dir, mirroring
            # --model's behavior (random-initializing silently would make
            # the draft useless — ~0 acceptance — with no error).
            draft_path = args.draft_model_path or args.draft_model
        else:
            draft_cfg = get_config(args.draft_model)
            draft_path = args.draft_model_path
        if draft_path:
            from arks_tpu.models.weights import load_params_streaming

            def _draft_loader(dc=draft_cfg, dp=draft_path):
                return load_params_streaming(dc, dp, mesh=mesh,
                                             dtype=args.dtype)

            pool.register(draft_cfg.name, draft_cfg, model_path=draft_path,
                          loader=_draft_loader, pinned=True)
            draft_params = pool.load(draft_cfg.name)
    # Real weights without tokenizer assets = broken mount; fail fast then.
    from arks_tpu.models.weights import has_real_weights
    tokenizer = load_tokenizer(
        model_path if model_path and os.path.isdir(model_path) else None,
        strict=has_real_weights(model_path))
    return InferenceEngine(cfg, ecfg, tokenizer, params=params, mesh=mesh,
                           draft_params=draft_params, draft_cfg=draft_cfg,
                           pool=pool)


def build_server(args: argparse.Namespace, engine):
    """Register the extra pool models, start the engine's step loop (every
    role but disaggregated prefill) and return the role's HTTP server,
    not yet listening."""
    from arks_tpu.models.config import ModelConfig
    from arks_tpu.server.openai_server import OpenAIServer

    served = args.served_model_name or engine.cfg.name
    # Extra pool models (after the multihost wiring so the single-host-only
    # check in register_model sees the dispatcher).
    for spec in args.extra_model or []:
        name, _, path = spec.partition("=")
        if os.path.isdir(name):
            engine.register_model(
                ModelConfig.from_hf_config(name, name=os.path.basename(name)),
                model_path=path or name)
        else:
            engine.register_model(name, model_path=path or None)

    if args.disagg and engine.cfg.latent:
        raise ValueError(
            f"model {engine.cfg.name!r} (latent attention): "
            f"--disaggregation-mode {args.disagg} hands K and V blocks "
            "between pods (kv_transfer, the AKV1 format); a latent page "
            "is not carried"
            + (", nor the recurrent state of its linear layers"
               if engine.cfg.linear else ""))
    if args.disagg and engine.cfg.windowed:
        raise ValueError(
            f"model {engine.cfg.name!r} (window and full attention layers): "
            f"--disaggregation-mode {args.disagg} hands every layer's page "
            "of one pool between pods (kv_transfer, the AKV1 format); the "
            "two pools of such a model are not carried")
    if args.disagg and engine.cfg.recurrent:
        kind = engine.cfg.recurrent_kind
        raise ValueError(
            f"model {engine.cfg.name!r} ({kind} layers): "
            f"--disaggregation-mode {args.disagg} hands every layer's page "
            "between pods (kv_transfer, the AKV1 format); the recurrent "
            f"state of such a model's {kind} layers is not carried")
    if args.disagg == "prefill":
        from arks_tpu.server.disagg import PrefillServer
        # No decode loop: the engine only runs detached prefills.
        server = PrefillServer(engine, served, host=args.host, port=args.port)
    elif args.disagg == "decode":
        from arks_tpu.server.disagg import DecodeServer
        engine.start()
        server = DecodeServer(engine, served, host=args.host, port=args.port)
    else:
        engine.start()
        server = OpenAIServer(engine, served, host=args.host, port=args.port)
    return server


def main() -> None:
    args = parse_args()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    engine = build_engine(args)

    # Multi-host gang: process 0 serves HTTP and broadcasts every device
    # dispatch; the other processes mirror them so the gang's collectives
    # stay in lockstep (arks_tpu.engine.multihost).
    coord = knobs.get_str("ARKS_COORDINATOR_ADDRESS")
    nproc = knobs.get_int("ARKS_NUM_PROCESSES")
    if coord and nproc > 1:
        import signal as _signal

        from arks_tpu.engine.multihost import (
            DispatchFollower, DispatchLeader, dispatch_address)
        dhost, dport = dispatch_address(coord)
        pid = knobs.get_int("ARKS_PROCESS_ID")
        if pid != 0:
            # The gang driver SIGTERMs every member at once; a follower
            # dying instantly would strand the leader's drain mid-
            # collective.  Followers ignore SIGTERM and exit when the
            # leader (who coordinates the drain) closes the channel.
            _signal.signal(_signal.SIGTERM, _signal.SIG_IGN)
            log.info("follower %d/%d: mirroring leader dispatches", pid, nproc)
            DispatchFollower(engine, dhost, dport).run()
            return
        engine.dispatcher = DispatchLeader("0.0.0.0", dport, nproc - 1)

    server = build_server(args, engine)
    # Graceful drain: SIGTERM (rolling update, scale-down, kubelet stop)
    # flips readiness off, 503s new work, and lets in-flight requests
    # finish before serve_forever returns.
    import signal
    import threading

    def _on_term(signum, frame):
        log.info("SIGTERM: draining in-flight requests (up to %.0fs)",
                 args.drain_timeout)
        threading.Thread(target=server.drain, args=(args.drain_timeout,),
                         name="drain", daemon=True).start()

    signal.signal(signal.SIGTERM, _on_term)

    log.info("serving %s on %s:%d (mode=%s)", server.served_model_name,
             args.host, args.port, args.disagg or "unified")
    server.start(background=False)
    engine.stop()
    if engine.dispatcher is not None:
        engine.dispatcher.close()  # releases followers (they exit on close)
    log.info("drained; exiting")


if __name__ == "__main__":
    main()
