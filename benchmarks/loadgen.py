"""The load generator: a child process that never imports JAX.

    python3 -m benchmarks.loadgen --port P --model NAME --mix FILE --seed S
        --load L --seconds W --out FILE   (L: callers, or requests a second)

The parent holds the chip and the server; a generator on threads of that
process would share its GIL with the engine's step loop and be measured as
a slow server.  One thread, non-blocking sockets (``selectors``): hundreds
of SSE streams cost one wake-up per burst of frames, not one thread each.

Timeline (``time.monotonic``, which parent and child share on Linux):
prime (sessions only) -> t0 -> ramp (load offered, not judged) -> window
opens at t0 + ramp_s -> closes ``--seconds`` later -> drain_s -> every
socket is closed (the server aborts what is still running) -> exit.
Events go to stdout as JSON lines the parent reads; the per-request records
go to ``--out``.

``--tail-s T`` (a ``--trace 2`` run): everything up to the end of the drain
is as above, except that the load does not stop at the window's close:
callers keep cycling, arrivals continue (``traffic.py`` draws them from a
continuation of its own).  At the end of the drain the records are written
to ``--out`` as they stand then, less the requests sent after the window
closed: what a run without a tail would have written.  Then ``tail`` is
announced and the load goes on until the parent closes this process's
standard input (its traced slice is done) or ``T`` seconds have passed; the
records as they stand at the very end go to ``--out`` + ``.tail``.

The SSE parsing is a copy of ``bench_serving.py::_client_main``'s (first
content frame = first frame whose choice carries text); listed in PERF.md
for a later PR to delete the original.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import selectors
import socket
import sys
import time

from benchmarks.traffic import Schedule


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class Stream:
    """One POST /v1/completions with ``stream: true``."""

    __slots__ = ("rec", "sock", "buf", "head_done", "client")

    def __init__(self, rec: dict, sock: socket.socket, client: int | None):
        self.rec, self.sock, self.client = rec, sock, client
        self.buf = b""
        self.head_done = False


def _record(req: dict, due: float | None) -> dict:
    return {"id": req["id"], "due": due, "sent": None, "status": None,
            "first": None, "frames": [], "finish": None, "usage": None,
            "done": False, "error": None, "max_tokens": req["max_tokens"],
            "prompt_tokens": len(req["prompt"])}


def _open(port: int, model: str, req: dict, due: float | None,
          client: int | None) -> Stream:
    body = json.dumps({
        "model": model, "prompt": req["prompt"], "stream": True,
        "stream_options": {"include_usage": True},
        "max_tokens": req["max_tokens"], "temperature": 0.0,
        "ignore_eos": True}).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
            "Content-Type: application/json\r\nConnection: close\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    rec = _record(req, due)
    sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    rec["sent"] = time.monotonic()
    if due is None:
        rec["due"] = rec["sent"]
    sock.sendall(head + body)
    sock.setblocking(False)
    return Stream(rec, sock, client)


def _feed(st: Stream, data: bytes, now: float) -> None:
    """Parse what arrived.  Chunk-size lines of the chunked encoding never
    start with ``data:``, and a frame's JSON holds no raw newline, so the
    stream is read line by line."""
    st.buf += data
    rec = st.rec
    if not st.head_done:
        end = st.buf.find(b"\r\n\r\n")
        if end < 0:
            return
        status = st.buf[:end].split(b"\r\n", 1)[0].split()
        rec["status"] = int(status[1]) if len(status) > 1 else 0
        st.buf = st.buf[end + 4:]
        st.head_done = True
    if rec["status"] != 200:
        return
    *lines, st.buf = st.buf.split(b"\n")
    for line in lines:
        if not line.startswith(b"data: "):
            continue
        payload = line[6:].strip()
        if payload == b"[DONE]":
            rec["done"] = True
            continue
        obj = json.loads(payload)
        if "error" in obj:
            rec["error"] = json.dumps(obj["error"])[:200]
            continue
        for ch in obj.get("choices") or ():
            text = ch.get("text")
            if text:
                if rec["first"] is None:
                    rec["first"] = now
                rec["frames"].append((now, len(text)))
            if ch.get("finish_reason"):
                rec["finish"] = ch["finish_reason"]
        if obj.get("usage"):
            rec["usage"] = obj["usage"]


def run(args, write) -> tuple[dict, bool]:
    """(the records as they stand at the end, whether a snapshot was
    written at the end of the drain: ``write(result)`` is called for it)."""
    with open(args.mix) as f:
        mix = json.load(f)
    tail_s = max(args.tail_s, 0.0)
    sched = Schedule(mix, args.seed, load=args.load, seconds=args.seconds,
                     tail_s=mix["drain_s"] + tail_s if tail_s else 0.0)
    sel = selectors.DefaultSelector()
    records: list[dict] = []
    live: dict[int, Stream] = {}

    def start(req: dict, due: float | None, client: int | None) -> None:
        try:
            st = _open(args.port, args.model, req, due, client)
        except OSError as e:
            now = time.monotonic()
            records.append(dict(_record(req, due or now), sent=now, status=0,
                                error=f"{type(e).__name__}: {e}"))
            if client is not None:
                idle_clients.append(client)
            return
        records.append(st.rec)
        live[st.sock.fileno()] = st
        sel.register(st.sock, selectors.EVENT_READ, st)

    stop_asked = False

    def pump(timeout: float) -> None:
        nonlocal stop_asked
        for key, _ in sel.select(max(timeout, 0.0)):
            st: Stream = key.data
            if st is None:
                # Standard input: the parent closed it (or wrote to it);
                # the tail is over.
                sel.unregister(sys.stdin)
                stop_asked = True
                continue
            try:
                data = st.sock.recv(1 << 16)
            except BlockingIOError:
                continue
            except OSError as e:
                data = b""
                st.rec["error"] = st.rec["error"] or f"{type(e).__name__}"
            if data:
                _feed(st, data, time.monotonic())
                continue
            sel.unregister(st.sock)
            live.pop(st.sock.fileno(), None)
            st.sock.close()
            if st.client is not None:
                idle_clients.append(st.client)

    idle_clients: list[int] = []

    # Prime: each session's system prompt once, 32 at a time, waited for.
    prime = sched.prime()
    t_prime = time.monotonic()
    while prime or live:
        while prime and len(live) < 32:
            start(prime.pop(), None, None)
        pump(0.05)
    n_prime = len(records)
    prime_failed = sum(1 for r in records if not r["done"])
    records.clear()
    emit(event="primed", requests=n_prime, failed=prime_failed,
         seconds=time.monotonic() - t_prime)

    t0 = time.monotonic()
    t_open = t0 + mix["ramp_s"]
    t_close = t_open + args.seconds
    t_end = t_close + mix["drain_s"]
    emit(event="start", t0=t0, t_open=t_open, t_close=t_close,
         clients=sched.clients, offered=sched.count())

    def result(recs: list[dict]) -> dict:
        return {"t0": t0, "t_open": t_open, "t_close": t_close,
                "t_end": t_end, "loop": sched.loop,
                "clients": sched.clients, "offered": sched.count(),
                "prime_requests": n_prime, "prime_failed": prime_failed,
                "records": recs}

    nxt = 0
    if sched.loop == "closed":
        idle_clients.extend(range(sched.clients))
    announced_open = announced_close = False
    t_stop = t_end + tail_s         # without a tail: the end of the drain
    snapshot = None
    if tail_s:
        sel.register(sys.stdin, selectors.EVENT_READ, None)
    while True:
        now = time.monotonic()
        if not announced_open and now >= t_open:
            emit(event="window_open", t=now, live=len(live))
            announced_open = True
        if not announced_close and now >= t_close:
            emit(event="window_close", t=now, live=len(live))
            announced_close = True
        if tail_s and snapshot is None and now >= t_end:
            snapshot = copy.deepcopy([r for r in records
                                      if r["sent"] < t_close])
            write(result(snapshot))
            emit(event="tail", t=now, t_end=t_end, live=len(live))
        if now >= t_stop or stop_asked:
            break
        wait = min(t_stop, t_end if snapshot is None else t_stop,
                   t_close if not announced_close else t_stop,
                   t_open if not announced_open else t_stop) - now
        if now < t_close or tail_s:
            if sched.loop == "closed":
                while idle_clients:
                    start(sched.request(nxt), None, idle_clients.pop())
                    nxt += 1
            else:
                while nxt < len(sched.due) and t0 + sched.due[nxt] <= now:
                    start(sched.request(nxt), t0 + sched.due[nxt], None)
                    nxt += 1
                if nxt < len(sched.due):
                    wait = min(wait, t0 + sched.due[nxt] - time.monotonic())
        pump(min(wait, 0.05))
    for st in list(live.values()):
        sel.unregister(st.sock)
        st.sock.close()
    return result(records), snapshot is not None


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--model", required=True)
    p.add_argument("--mix", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--load", type=float, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--tail-s", type=float, default=0.0,
                   help="keep the load going for up to this long after "
                        "the drain, until standard input closes "
                        "(--trace 2)")
    args = p.parse_args(argv)
    assert "jax" not in sys.modules

    def write(out: dict, path: str = args.out) -> None:
        with open(path + ".part", "w") as f:
            json.dump(out, f)
        os.replace(path + ".part", path)

    out, had_tail = run(args, write)
    assert "jax" not in sys.modules, "the load generator imported JAX"
    write(out, args.out + ".tail" if had_tail else args.out)
    emit(event="done", records=len(out["records"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
