"""Multi-host serving: leader dispatch replication.

Under ``jax.distributed`` every process must execute the SAME jitted
computations in the same order — collectives hang otherwise.  The engine's
scheduler runs only on the leader (process 0, the one that serves HTTP);
follower processes mirror its device dispatches.

Mechanism: before each device dispatch the leader broadcasts a tiny
(op, host-args) record over a TCP channel; followers execute the identical
jit call against their OWN device state (params/cache/sampling are
constructed identically on every process — same spec, same seed or same
checkpoint shards).  Device-side lockstep then comes for free: the leader's
host-sync on a dispatch result cannot complete until followers join the
collectives.

This replaces what the reference gets from Ray/NCCL inside vLLM containers
(/root/reference/internal/controller/arksapplication_controller.go:941-1014
only wires rendezvous env vars; the engine brings its own execution model —
SURVEY.md §2.4).  The channel is a trusted intra-gang link (same security
domain as the NCCL/gloo sockets themselves).

Wire format: 4-byte big-endian length + pickled (op, payload) tuple, after
a mutual shared-secret handshake (the secret comes from the gang's env —
ARKS_GANG_SECRET — injected by whoever launches the gang).  Followers prove
identity with the secret; the leader proves itself with a derived ack, so a
port-squatting process can neither take a follower slot nor feed a follower
pickles.  Beyond the handshake the link is trusted, like the gloo/NCCL
sockets beside it.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import socket
import struct
import threading
import time

from arks_tpu.utils import knobs

log = logging.getLogger("arks_tpu.multihost")

DISPATCH_PORT_OFFSET = 1  # default dispatch port = coordinator port + 1


def dispatch_address(coordinator: str) -> tuple[str, int]:
    """Dispatch endpoint: explicit ARKS_DISPATCH_ADDRESS when the launcher
    reserved one (the local gang driver does — derived ports can collide on
    a shared host), else coordinator port + 1 (fine where each process has
    its own network namespace, e.g. one pod per host)."""
    explicit = knobs.get_str("ARKS_DISPATCH_ADDRESS")
    if explicit:
        host, _, port = explicit.partition(":")
        return host, int(port)
    host, _, port = coordinator.partition(":")
    return host, int(port) + DISPATCH_PORT_OFFSET


def _secret() -> bytes:
    return knobs.get_str("ARKS_GANG_SECRET").encode()


def _leader_ack(secret: bytes) -> bytes:
    return hashlib.sha256(secret + b"/leader-ack").digest()


def _send_msg(sock: socket.socket, obj) -> None:
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(struct.pack(">I", len(data)) + data)


def _recv_msg(sock: socket.socket):
    hdr = _recv_exact(sock, 4)
    (n,) = struct.unpack(">I", hdr)
    return pickle.loads(_recv_exact(sock, n))


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("dispatch channel closed")
        buf += chunk
    return buf


class DispatchLeader:
    """Leader side: accepts follower connections, broadcasts dispatches.

    Worker-wedge detection: followers HEARTBEAT on the channel's return
    direction (it is otherwise leader→follower only), and a per-connection
    reader thread tracks the last-seen timestamp.  ``follower_health``
    surfaces staleness to the serving readiness gate (so a hung-but-
    connected worker drops the gang out of Service endpoints within a
    bounded window), and a monitor thread ESCALATES past
    ``ARKS_GANG_WEDGE_FATAL_S``: the leader exits so the gang driver
    restarts the whole group — the same shared-fate policy as a broken
    channel (engine._emit), and the behavior the reference buys from LWS
    RecreateGroupOnPodRestart (arksapplication_controller.go:581-584),
    which only reacts to pod DEATH; the heartbeat also catches hangs."""

    def __init__(self, bind_host: str, port: int, num_followers: int,
                 accept_timeout_s: float = 120.0):
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((bind_host, port))
        self._srv.listen(num_followers)
        self._conns: list[socket.socket] = []
        self._lock = threading.Lock()
        self._hb_lock = threading.Lock()
        self._last_hb: list[float] = []
        self._wedge_fatal_s = knobs.get_float("ARKS_GANG_WEDGE_FATAL_S")
        secret = _secret()
        deadline = time.monotonic() + accept_timeout_s
        while len(self._conns) < num_followers:
            self._srv.settimeout(max(0.1, deadline - time.monotonic()))
            try:
                conn, addr = self._srv.accept()
            except socket.timeout:
                raise TimeoutError(
                    f"only {len(self._conns)}/{num_followers} followers "
                    "connected to the dispatch channel")
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # Handshake: follower proves the gang secret; a stray connection
            # (port scanner) must not consume a follower slot.
            try:
                conn.settimeout(10)
                proof = _recv_exact(conn, 32)
                if proof != hashlib.sha256(secret).digest():
                    raise ConnectionError("bad gang secret")
                conn.sendall(_leader_ack(secret))
                conn.settimeout(None)
            except (OSError, ConnectionError) as e:
                log.warning("rejecting dispatch connection from %s: %s",
                            addr, e)
                conn.close()
                continue
            log.info("follower connected from %s", addr)
            self._conns.append(conn)
            self._last_hb.append(time.monotonic())
        for i, conn in enumerate(self._conns):
            threading.Thread(target=self._hb_reader, args=(i, conn),
                             name=f"dispatch-hb-{i}", daemon=True).start()
        if self._conns and self._wedge_fatal_s > 0:
            threading.Thread(target=self._wedge_monitor,
                             name="dispatch-wedge-monitor",
                             daemon=True).start()

    def _hb_reader(self, idx: int, conn: socket.socket) -> None:
        """Drain the follower's return direction (heartbeats only)."""
        while True:
            try:
                op, _ = _recv_msg(conn)
            except (OSError, ConnectionError):
                return  # channel death is handled by broadcast/sendall
            if op == "hb":
                with self._hb_lock:
                    self._last_hb[idx] = time.monotonic()

    def _wedge_monitor(self) -> None:
        while True:
            time.sleep(max(self._wedge_fatal_s / 8, 0.25))
            health = self.follower_health(self._wedge_fatal_s)
            if health["stale"]:
                log.critical(
                    "follower(s) %s heartbeat stale > %.0fs (hung, not "
                    "dead); exiting so the gang driver restarts the whole "
                    "group", health["stale"], self._wedge_fatal_s)
                os._exit(71)

    def follower_health(self, stale_after_s: float) -> dict:
        """Heartbeat ages per follower; ``stale`` lists followers not heard
        from within ``stale_after_s`` (the readiness gate's input)."""
        now = time.monotonic()
        with self._hb_lock:
            ages = [now - t for t in self._last_hb]
        return {
            "followers": len(ages),
            "max_heartbeat_age_s": round(max(ages, default=0.0), 3),
            "stale": [i for i, a in enumerate(ages) if a > stale_after_s],
        }

    def broadcast(self, op: str, payload: dict) -> None:
        # Serialize ONCE: insert_kv payloads carry whole KV tensors.
        data = pickle.dumps((op, payload), protocol=pickle.HIGHEST_PROTOCOL)
        framed = struct.pack(">I", len(data)) + data
        with self._lock:
            for conn in self._conns:
                conn.sendall(framed)

    def close(self) -> None:
        with self._lock:
            for conn in self._conns:
                try:
                    _send_msg(conn, ("stop", {}))
                except OSError:
                    pass
                conn.close()
            self._conns.clear()
        self._srv.close()


class DispatchFollower:
    """Follower side: mirrors the leader's dispatches onto a local engine.

    Holds the transient cross-op state the leader keeps in locals (the last
    prefill's KV) and executes each op with this process's own device state.
    """

    def __init__(self, engine, leader_host: str, port: int,
                 connect_timeout_s: float = 120.0):
        import jax

        self.engine = engine
        self._jax = jax
        # Pipelined decode replay: the follower threads its OWN device
        # state between "decode_pipe" ops (the leader cannot broadcast
        # token values it never fetched); a fresh op re-seeds it.
        self._pipe_state = None
        self._pipe_cols = None
        secret = _secret()
        deadline = time.monotonic() + connect_timeout_s
        while True:
            try:
                self._sock = socket.create_connection((leader_host, port),
                                                      timeout=5)
                # Mutual handshake: prove the gang secret, then require the
                # leader's derived ack — never unpickle bytes from an
                # unauthenticated peer (a port squatter could otherwise
                # feed arbitrary pickles = code execution).
                self._sock.settimeout(10)
                self._sock.sendall(hashlib.sha256(secret).digest())
                ack = _recv_exact(self._sock, 32)
                if ack != _leader_ack(secret):
                    raise ConnectionError("leader failed gang-secret handshake")
                self._sock.settimeout(None)
                break
            except OSError:
                sock = getattr(self, "_sock", None)
                if sock is not None:
                    try:
                        sock.close()
                    except OSError:
                        pass
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.5)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def _hb_loop(self, interval_s: float) -> None:
        """Send liveness beats on the channel's return direction.  A
        separate thread from the dispatch loop ON PURPOSE: a worker wedged
        inside a dispatch (deadlocked collective, stuck DMA) keeps its
        socket open but stops beating only if the whole process stops —
        SIGSTOP, OOM-thrash, runaway GC — which is exactly the "hung, not
        dead" class the leader's wedge monitor exists for.  jit compiles
        and device waits release the GIL, so beats flow through them."""
        while not self._hb_stop.is_set():
            try:
                with self._send_lock:
                    _send_msg(self._sock, ("hb", {}))
            except (OSError, ConnectionError):
                return
            self._hb_stop.wait(interval_s)

    def run(self) -> None:
        """Dispatch loop; returns when the leader sends stop/disconnects."""
        import jax
        import jax.numpy as jnp

        from arks_tpu.engine import sampler as sampler_mod

        eng = self.engine
        self._hb_stop = threading.Event()
        self._send_lock = threading.Lock()
        threading.Thread(
            target=self._hb_loop,
            args=(knobs.get_float("ARKS_GANG_HB_INTERVAL"),),
            name="dispatch-hb", daemon=True).start()
        try:
            self._run_inner(eng, jax, jnp)
        finally:
            self._hb_stop.set()

    def _run_inner(self, eng, jax, jnp) -> None:
        while True:
            try:
                op, p = _recv_msg(self._sock)
            except (ConnectionError, OSError):
                log.info("dispatch channel closed; follower exiting")
                return
            if op == "stop":
                return
            try:
                self._apply(eng, jax, jnp, op, p)
            except Exception as e:
                # A deterministic device fault raises here AND on the
                # leader; the leader's recovery broadcasts "recover" +
                # "reset" next, which rebuilds this process's device state
                # too.  (A follower-only fault diverges instead — the next
                # collective then hangs and jax's coordination service
                # kills the gang, which the driver restarts.)
                from arks_tpu.engine import faults as faults_mod
                faults_mod.swallowed("follower_dispatch", e)
                log.exception("dispatch op %r failed; awaiting reset", op)

    @staticmethod
    def _shape_args(p: dict, jnp, sampler_mod, eng):
        """Follower-side (bias_ids, bias_vals, sup_ids, min_first, guide,
        guide_row, guide_tables) jnp args from an emit payload, defaulting
        to the empty columns — ONE definition, or leader/follower replay
        diverges per op."""
        import numpy as _np
        nb = sampler_mod.LOGIT_BIAS_MAX
        ns = sampler_mod.SUPPRESS_MAX
        return (
            jnp.asarray(p.get("bias_ids", _np.full((nb,), -1, _np.int32))),
            jnp.asarray(p.get("bias_vals", _np.zeros((nb,), _np.float32))),
            jnp.asarray(p.get("sup_ids", _np.full((ns,), -1, _np.int32))),
            jnp.asarray(p.get("min_first", 0), jnp.int32),
            jnp.asarray(p.get("guide", -1), jnp.int32),
            jnp.asarray(p.get("guide_row", 0), jnp.int32),
            eng._guide_dev)

    @staticmethod
    def _slot_row(p: dict, sampler_mod) -> tuple:
        """A ``set_slot`` payload as a row of ``_apply_set_slots``: the
        base key rebuilt from the seed on the host, folded inside the
        program as on the leader."""
        from arks_tpu.engine.types import SamplingParams

        params = SamplingParams(
            temperature=p["temperature"], top_p=p["top_p"],
            top_k=p["top_k"],
            presence_penalty=p.get("presence", 0.0),
            frequency_penalty=p.get("frequency", 0.0),
            logit_bias=tuple((int(t), float(b))
                             for t, b in p.get("logit_bias", ())),
            min_tokens=p.get("min_tokens", 0),
            stop_token_ids=tuple(p.get("stop_ids", ())),
            ignore_eos=p.get("ignore_eos", False))
        return (p["slot"], params, sampler_mod.np_prng_key(p["seed"]), True,
                p.get("num_prompt", 0), p.get("guide", -1),
                p.get("guide_row", 0))

    def _apply(self, eng, jax, jnp, op: str, p: dict) -> None:
        from arks_tpu.engine import sampler as sampler_mod

        if op in ("admit_batch", "admit_batch_lp"):
            # Fused batched admission: prefill + sample + insert + set_slot
            # for M prompts in one dispatch (mirrors the leader's
            # _admit_fn exactly).  Paged engines receive the page rows by
            # value — the allocator runs on the leader only.
            import numpy as _np
            keys = jnp.asarray(_np.stack(
                [sampler_mod.np_prng_key(s) for s in p["seeds"]]))
            fn = (eng._admit_lp_fn if op == "admit_batch_lp"
                  else eng._admit_fn)
            pages = p.get("pages")
            out = fn(eng.params, eng._cache, eng._sampling,
                     jnp.asarray(p["tokens"]),
                     jnp.asarray(p["lengths"], jnp.int32),
                     jnp.asarray(p["slots"], jnp.int32),
                     None if pages is None else jnp.asarray(pages),
                     None if pages is None else jnp.asarray(
                         p["n_pages"], jnp.int32),
                     jnp.asarray(p["temperature"], jnp.float32),
                     jnp.asarray(p["top_p"], jnp.float32),
                     jnp.asarray(p["top_k"], jnp.int32), keys,
                     jnp.asarray(p["presence"], jnp.float32),
                     jnp.asarray(p["frequency"], jnp.float32),
                     jnp.asarray(p["bias_ids"], jnp.int32),
                     jnp.asarray(p["bias_vals"], jnp.float32),
                     jnp.asarray(p["sup_ids"], jnp.int32),
                     jnp.asarray(p["min_first"], jnp.int32),
                     jnp.asarray(p["min_until"], jnp.int32),
                     jnp.asarray(p.get("guide",
                                       _np.full((len(p["seeds"]),), -1,
                                                _np.int32)), jnp.int32),
                     jnp.asarray(p.get("guide_row",
                                       _np.zeros((len(p["seeds"]),),
                                                 _np.int32)), jnp.int32),
                     eng._guide_dev)
            eng._cache, eng._sampling = out[-4], out[-3]
        elif op == "chunk_paged":
            _logits, eng._cache = eng._chunk_fn(
                eng.params, eng._cache, jnp.asarray(p["tables_row"]),
                jnp.asarray(p["tokens"]),
                jnp.asarray(p["start"], jnp.int32),
                jnp.asarray(p["valid"], jnp.int32))
            self._last_logits = _logits
        elif op == "insert_pages":
            eng._cache = eng._insert_pages_fn(
                eng._cache, jnp.asarray(p["k"]), jnp.asarray(p["v"]),
                jnp.asarray(p["pages"]),
                jnp.asarray(p["n_pages"], jnp.int32))
        elif op in ("prefill_detached", "prefill_detached_lp"):
            # Disaggregated prefill on a gang: mirror the replicated-KV
            # prefill program (the leader materializes the full block for
            # the wire transfer; followers just keep collectives aligned).
            key = jnp.asarray(sampler_mod.np_prng_key(p["seed"]))
            fn = (eng._prefill_detached_lp_fn if op.endswith("_lp")
                  else eng._prefill_detached_fn)
            out = fn(eng.params, jnp.asarray(p["tokens"]),
                     jnp.asarray([p["length"]], jnp.int32),
                     jnp.float32(p["temperature"]),
                     jnp.float32(p["top_p"]),
                     jnp.int32(p["top_k"]), key,
                     *self._shape_args(p, jnp, sampler_mod, eng))
            jax.block_until_ready(out[0])
        elif op == "insert_kv":
            # Disaggregated decode: KV arrives by value (the leader got
            # it over the wire, not from a local prefill).
            eng._cache = eng._insert_fn(
                eng._cache, jnp.asarray(p["k"]), jnp.asarray(p["v"]),
                jnp.asarray(p["slot"]))
        elif op in ("set_slot", "set_slots"):
            # One slot's registration, or all of a step's promotions (and,
            # with no rows, the warm-up of one compiled size): the same
            # call of the same program as the leader's.
            rows = [p] if op == "set_slot" else p["rows"]
            eng._apply_set_slots(
                [self._slot_row(r, sampler_mod) for r in rows],
                "promote" if op == "set_slots" else "admit",
                size=p.get("size"))
        elif op == "recover":
            # Leader entered fault recovery: log the surviving-request
            # manifest (the streams about to be replayed through ordinary
            # chunk/set_slot ops) and drop the threaded pipeline state —
            # the next decode_pipe op after a recovery is always fresh.
            self._pipe_state = None
            self._pipe_cols = None
            log.warning(
                "leader fault recovery (phase=%s kind=%s): replaying %d "
                "surviving request(s): %s", p.get("phase"), p.get("kind"),
                len(p.get("manifest", ())),
                [rid for rid, _, _ in p.get("manifest", ())])
        elif op == "clear_penalties":
            eng._sampling = eng._clear_pen_fn(
                eng._sampling, jnp.asarray(p["slot"], jnp.int32))
        elif op == "chunk":
            _logits, eng._cache = eng._chunk_fn(
                eng.params, eng._cache, jnp.asarray(p["slot"], jnp.int32),
                jnp.asarray(p["tokens"]),
                jnp.asarray(p["start"], jnp.int32),
                jnp.asarray(p["valid"], jnp.int32))
            self._last_logits = _logits
        elif op in ("sample_one", "sample_one_lp"):
            key = jnp.asarray(sampler_mod.np_prng_key(p["seed"]))
            fn = (eng._sample_one_lp_fn if op == "sample_one_lp"
                  else eng._sample_one_fn)
            fn(self._last_logits,
               jnp.float32(p["temperature"]),
               jnp.float32(p["top_p"]),
               jnp.int32(p["top_k"]), key,
               *self._shape_args(p, jnp, sampler_mod, eng))
        elif op == "decode":
            fn = eng._decode_lp_fn if p.get("lp") else eng._decode_fn
            tables = p.get("tables")
            eng._cache, eng._sampling, toks = fn(
                eng.params, eng._cache, jnp.asarray(p["tokens"]),
                jnp.asarray(p["lengths"]), eng._sampling,
                None if tables is None else jnp.asarray(tables),
                eng._guide_dev)
            # Host-sync like the leader, but via block_until_ready —
            # a follower may not address every shard of toks.
            jax.block_until_ready(toks)
        elif op == "decode_pipe":
            # Pipelined decode (ARKS_PIPELINE_DEPTH): the op stream carries
            # NO host token values — a fresh op ships the host-built state
            # (pipeline entry), every later op consumes this process's own
            # device arrays threaded from the previous dispatch, exactly
            # like the leader.  No host sync either: lockstep rides the
            # collectives inside the program, and blocking here would
            # re-introduce on the follower the per-step stall the pipeline
            # exists to remove.
            if p.get("fresh"):
                self._pipe_state = (jnp.asarray(p["tokens"]),
                                    jnp.asarray(p["lengths"], jnp.int32),
                                    jnp.asarray(p["alive"]))
                cols = [jnp.asarray(p["stop_ids"]),
                        jnp.asarray(p["dead_len"], jnp.int32)]
                if "spec_enable" in p:
                    cols.append(jnp.asarray(p["spec_enable"]))
                self._pipe_cols = tuple(cols)
            elif self._pipe_state is None:
                raise RuntimeError(
                    "decode_pipe without fresh state: leader/follower "
                    "pipeline streams diverged")
            tables = p.get("tables")
            tables = None if tables is None else jnp.asarray(tables)
            # Same program resolution as the leader (_pipe_call prefers
            # this process's warmed executable when one exists).
            if eng._draft_cfg is not None:
                # Spec engines thread the draft cache too; the program
                # returns (cache, dcache, sampling, ...).
                out = eng._pipe_call(bool(p.get("lp")), eng.params,
                                     eng._draft_params, eng._cache,
                                     eng._draft_cache, *self._pipe_state,
                                     *self._pipe_cols, eng._sampling,
                                     tables, eng._guide_dev)
                eng._cache, eng._draft_cache, eng._sampling = \
                    out[0], out[1], out[2]
            else:
                out = eng._pipe_call(bool(p.get("lp")), eng.params,
                                     eng._cache, *self._pipe_state,
                                     *self._pipe_cols, eng._sampling,
                                     tables, eng._guide_dev)
                eng._cache, eng._sampling = out[0], out[1]
            self._pipe_state = out[-3:]
        elif op == "mixed":
            # Unified mixed prefill+decode dispatch (ARKS_MIXED_STEP): the
            # whole batch description arrives by value — followers never
            # need the leader's scheduler state, only the identical jit
            # call (override keys included, so gang sampling stays in
            # lockstep without the guide/seed registries).
            fn = eng._mixed_lp_fn if p.get("lp") else eng._mixed_fn
            out = fn(eng.params, eng._cache, eng._sampling,
                     eng._mixed_pack_of(len(p["tokens"])).host_from(p),
                     eng._guide_dev)
            eng._cache, eng._sampling = out[-2], out[-1]
            jax.block_until_ready(out[0])
        elif op == "draft_prefill":
            # Speculative decoding: the draft cache mirrors the leader's
            # (identical draft params: same spec + same seed/shards).
            eng._draft_cache = eng._draft_prefill_fn(
                eng._draft_params, eng._draft_cache,
                jnp.asarray(p["tokens"]),
                jnp.asarray([p["length"]], jnp.int32),
                jnp.asarray(p["slot"]))
        elif op == "spec_mixed":
            # Spec-mixed dispatch (draft propose + ragged verify + accept
            # inside the mixed program): the whole batch description
            # arrives by value like "mixed"; key lockstep rides the shared
            # _sampling state, which both sides evolve with the kernel's
            # deterministic splits.
            fn = (eng._spec_mixed_lp_fn if p.get("lp")
                  else eng._spec_mixed_fn)
            out = fn(eng.params, eng._draft_params, eng._cache,
                     eng._draft_cache, eng._sampling,
                     eng._spec_pack.host_from(p), eng._guide_dev)
            eng._cache, eng._draft_cache, eng._sampling = \
                out[-3], out[-2], out[-1]
            jax.block_until_ready(out[1])
        elif op == "guides":
            # Guide-table sync: load the leader's host tables and refresh
            # the device copies NOW — ops after this one in the channel
            # may reference the new rows.
            eng.guides.load_state(p["class_ids"], p["trans"], p["version"])
            eng._guide_dev = (jnp.asarray(eng.guides.class_ids),
                              jnp.asarray(eng.guides.trans))
            eng._guide_ver = eng.guides.version
        elif op == "reset":
            eng._reset_device_state()
        else:
            log.warning("unknown dispatch op %r", op)
