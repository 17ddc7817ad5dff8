"""One command, one cell, one run.

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s>
                              --trace <0|1|2> [--rehearse]

Order of events (everything before the window opens is set-up):
platform check -> compile cache -> reference weights from the seed, parked
on the host -> the pod, through the server entry point's own functions ->
warm-up (the pipelined programs, the probes, one plain stream) -> the
correctness comparison -> the load generator as a child process that never
imports JAX -> ramp -> the measured window -> drain -> one JSON line.

``--trace 1`` profiles a slice inside the window and prints the per-layer
metrics.  ``--trace 2`` is a ``--trace 0`` run with a traced tail: the same
run until the window has closed and the drain has passed (the load
generator keeps the load going and hands over the records as they stood at
the end of the drain), then the profiler is started and stopped once into a
trace that is thrown away, then ``trace_s`` seconds of the same traffic are
traced through the program's own window; the last line carries the
end-to-end numbers of the closed window and the per-layer metrics side by
side.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.  ``--rehearse`` is for the CPU at ``tiny`` size: it runs
the same code end to end and prints ``correct: false`` and no device metric.
"""

from __future__ import annotations

import time

_T_IMPORT = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

from benchmarks import client_metrics, manifest  # noqa: E402


def _process_age_s() -> float:
    """Seconds this process had lived when this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(up - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


_T_START = _T_IMPORT - _process_age_s()


def info(**fields) -> None:
    """An earlier line of standard output: what was compared, what the
    closed loop's time to first token was, where the set-up time went."""
    print(json.dumps(fields), flush=True)


def _child_events(proc: subprocess.Popen, on_event) -> threading.Thread:
    def pump() -> None:
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("{"):
                on_event(json.loads(line))
    t = threading.Thread(target=pump, name="loadgen-events", daemon=True)
    t.start()
    return t


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU rehearsal of a tiny cell of "
                        "benchmarks/rehearsal.json: no device metric, "
                        "correct is false")
    p.add_argument("--load", type=float, default=None,
                   help="sweeps only: offer this load (callers, or "
                        "requests a second) in place of the cell's share "
                        "of its knee")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(levelname)s "
                               "%(message)s")
    man = manifest.load(rehearsal=args.rehearse)
    cell = manifest.cell(man, args.workload)
    deploy, mix = cell["deploy"], cell["mix"]
    if args.load:
        cell["load"] = args.load

    # ---- the platform, before anything is built ------------------------
    from benchmarks import pod as podlib
    dev = podlib.device_info()
    t_backend = time.monotonic()
    if not args.rehearse and (dev["platform"] != "tpu"
                              or dev["count"] < cell["chips"]):
        print(f"benchmarks.run: needs {cell['chips']} TPU chip(s); jax "
              f"reports {dev}", file=sys.stderr)
        return 3
    cache_dir = podlib.place_compile_cache()
    meter = podlib.CompileMeter()

    # ---- the reference's weights, made before the engine needs the room
    from benchmarks import correctness
    spec = deploy["correct"]
    weights = correctness.reference_weights(cell["reference"],
                                            cell["config"], deploy, args.seed)
    t_refw = time.monotonic()

    pod = podlib.build(cell["config_name"], cell["config_dir"], deploy,
                       args.seed, platform="cpu" if args.rehearse else None)
    rc = 1
    try:
        t_build = time.monotonic()
        resident = podlib.memory_in_use_bytes()
        # ---- warm-up: the programs this cell's shapes use --------------
        state = pod.engine._pipe_warm_wait(900.0)
        depth = int(pod.labels.get("pipeline_depth", "0"))
        if depth and state != "ready":
            raise RuntimeError(f"pipelined programs: warm state {state!r}")
        prompts = correctness.probes(spec, args.seed)
        served = correctness.serve(pod.engine, prompts,
                                   spec["decode_tokens"])
        warm = podlib.complete(pod.port, cell["config_name"],
                               "a" * spec["prompt_tokens"][-1],
                               spec["decode_tokens"])
        if warm["status"] != 200:
            raise RuntimeError(f"warm-up stream: HTTP {warm['status']}")
        t_warm = time.monotonic()
        cmp_ = correctness.compare(cell["reference"], cell["config"], weights,
                                   prompts, served, spec)
        del weights
        logits_ok = correctness.verdict(cmp_, spec)
        info(check="logprob_err", value=cmp_["logprob_err"],
             limit=spec["limit"], ok=logits_ok, detail=cmp_)
        t_check = time.monotonic()

        # ---- the load generator: a child without JAX ------------------
        out_dir = os.path.join(manifest.ROOT, ".bench_out")
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{args.workload}.{args.seed}.{args.trace}"
        rec_path = os.path.join(out_dir, f"{tag}.records.json")
        cmd = [sys.executable, "-m", "benchmarks.loadgen",
               "--port", str(pod.port), "--model", cell["config_name"],
               "--mix", cell["mix_path"],
               "--seed", str(args.seed), "--load", repr(cell["load"]),
               "--seconds", str(args.seconds), "--out", rec_path]
        if args.trace == 2:
            cmd += ["--tail-s", str(_TAIL_CAP_S)]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        marks: dict = {}
        prof_dir = os.path.join(out_dir, f"{tag}.profile")
        timers: list[threading.Timer] = []

        def on_event(ev: dict) -> None:
            kind = ev.get("event")
            if kind == "primed":
                info(phase="primed", **{k: v for k, v in ev.items()
                                        if k != "event"})
            elif kind == "start" and args.trace == 1:
                # A slice of the window, not the whole of it: a trace of
                # every operation of tens of seconds comes back too large.
                at = ev["t_open"] + mix.get("trace_offset_s", 1.0)
                span = min(mix["trace_s"],
                           max(args.seconds - 1.5, 0.5))

                def start_trace() -> None:
                    marks["trace"] = pod.engine.profiler.start(prof_dir)
                    marks["trace_t0"] = time.monotonic()

                def stop_trace() -> None:
                    marks["trace_t1"] = time.monotonic()
                    marks["trace_stop"] = pod.engine.profiler.stop()
                    pod.engine.trace.flush()
                    marks["phase_spans"] = pod.engine.trace.phase_spans()

                for delay, fn in ((at, start_trace), (at + span, stop_trace)):
                    tm = threading.Timer(max(delay - time.monotonic(), 0), fn)
                    tm.daemon = True
                    tm.start()
                    timers.append(tm)
            elif kind == "tail" and args.trace == 2:
                # The window has closed and the drain has passed: the
                # numbers are fixed.  Trace a slice of the tail, then let
                # the generator go (it stops when its input closes).
                span = min(mix["trace_s"], _TAIL_CAP_S / 3)
                tm = threading.Thread(
                    target=_traced_tail, name="traced-tail", daemon=True,
                    args=(pod.engine.profiler, prof_dir, span, marks, proc))
                tm.start()
                timers.append(tm)
            elif kind == "window_open":
                marks["open_t"] = ev["t"]
                marks["open_metrics"] = podlib.scrape(pod.port)
                marks["open_compiles"] = meter.mark()
            elif kind == "window_close":
                marks["close_t"] = ev["t"]
                marks["close_metrics"] = podlib.scrape(pod.port)
                marks["window_compiles"] = meter.since(marks["open_compiles"])

        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, text=True, env=env,
            cwd=manifest.ROOT,
            stdin=subprocess.PIPE if args.trace == 2 else None)
        try:
            pump = _child_events(proc, on_event)
            child_rc = proc.wait(
                timeout=mix["ramp_s"] + args.seconds + mix["drain_s"] + 240
                + (_TAIL_CAP_S if args.trace == 2 else 0))
            pump.join(10)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        for tm in timers:
            tm.join(30)
        if child_rc != 0:
            raise RuntimeError(f"the load generator exited {child_rc}")
        with open(rec_path) as f:
            run = json.load(f)
        os.remove(rec_path)
        client = client_metrics.reduce(run, chips=cell["chips"])
        # A reader that lays the client's records on the traced slice
        # (attn_roofline) needs the records that cover it: in a --trace 2
        # run those of the tail, which hold the window's and go on.
        sliced = run
        if args.trace == 2:
            sliced = _tail_line(rec_path + ".tail", marks,
                                cell["chips"]) or run
        setup_s = run["t_open"] - _T_START
        info(phase="setup", setup_s=setup_s,
             backend_s=t_backend - _T_START, reference_weights_s=t_refw
             - t_backend, build_s=t_build - t_refw, warm_s=t_warm - t_build,
             compare_s=t_check - t_warm, loadgen_start_s=run["t0"] - t_check,
             ramp_s=run["t_open"] - run["t0"], cache_dir=cache_dir,
             resident_after_build_bytes=resident,
             resident_after_window_bytes=podlib.memory_in_use_bytes(),
             **meter.since())
        info(phase="client", **client)
        compiled = marks.get("window_compiles") or {"compiles": -1}
        info(check="compilations_in_window", value=compiled["compiles"],
             limit=0, ok=compiled["compiles"] == 0)
        info(check="wrong_streams", value=client["n_wrong_streams"], limit=0,
             ok=client["n_wrong_streams"] == 0, ids=client["wrong_streams"])
        info(check="prime_failed", value=run["prime_failed"], limit=0,
             ok=run["prime_failed"] == 0)
        correct = (logits_ok and compiled["compiles"] == 0
                   and client["n_wrong_streams"] == 0
                   and run["prime_failed"] == 0
                   and client["finished_streams"] > 0)

        # ---- the numbers ----------------------------------------------
        values = dict(client, setup_s=setup_s)
        device = dict(dev, memory_peak_bytes=podlib.memory_peak_bytes())
        result: dict = {"correct": bool(correct) and not args.rehearse,
                        "attempted": client["attempted"],
                        "failed": client["failed"]}
        metrics = {}
        if args.trace != 1:
            for m in cell["end_to_end"]:
                v = values.get(m["name"])
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if args.trace:
            from benchmarks import trace_reduce
            ctx = {"cell": cell, "client": client, "run": sliced,
                   "metrics_open": marks.get("open_metrics") or {},
                   "metrics_close": marks.get("close_metrics") or {},
                   "traces": _engine_traces(pod, run),
                   "device": None, "memory_peak_bytes":
                   device["memory_peak_bytes"], "engine": pod.engine,
                   "platform": dev["platform"], "kind": dev["kind"]}
            if (marks.get("trace") or {}).get("ok") and not args.rehearse:
                if args.trace == 2:
                    # The program's window: its own spans, laid on the
                    # trace by the clock anchors it wrote (clock.py).
                    from benchmarks import clock
                    stop = marks["trace_stop"]
                    joined = clock.offset(trace_reduce.find_xplane(prof_dir))
                    info(phase="clock", **joined,
                         guess_s=-stop["t0_monotonic"])
                    ctx["device"] = trace_reduce.reduce_dir(
                        prof_dir, stop["t0_monotonic"], stop["t1_monotonic"],
                        phase_spans=stop["spans"],
                        clock_offset_s=joined["offset_s"])
                else:
                    ctx["device"] = trace_reduce.reduce_dir(
                        prof_dir, marks["trace_t0"], marks["trace_t1"],
                        phase_spans=marks.get("phase_spans"),
                        clock_offset_s=-marks["trace_t0"])
                info(phase="step_programs", programs={
                    k: {"runs": len(v), "median_ms": sorted(v)[len(v) // 2]
                        * 1e3} for k, v in trace_reduce.step_programs(
                        ctx["device"]["modules"]).items()})
                device["busy_s"] = ctx["device"]["busy_s"]
                device["window_s"] = ctx["device"]["window_s"]
                result["breakdown"] = ctx["device"]["breakdown"]
            for m in cell["per_layer"]:
                v = manifest.load_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        import shutil
        shutil.rmtree(prof_dir, ignore_errors=True)
        result["metrics"] = metrics
        result["device"] = device
        rc = 0
    finally:
        pod.close()
    print(json.dumps(result), flush=True)
    return rc


# A ``--trace 2`` run's tail: the generator keeps the load going for at
# most this long after the drain; it ends as soon as the traced slice does.
_TAIL_CAP_S = 90.0


def _traced_tail(profiler, prof_dir: str, span_s: float, marks: dict,
                 proc: subprocess.Popen) -> None:
    """After the drain: start and stop the profiler once into a trace that
    is thrown away (the first start in a process costs what later ones do
    not), then trace ``span_s`` seconds through the program's own window,
    then close the generator's input so that it ends."""
    import shutil
    try:
        warm = prof_dir + ".discard"
        t = time.monotonic()
        if profiler.start(warm).get("ok"):
            profiler.stop()
        shutil.rmtree(warm, ignore_errors=True)
        marks["discard_s"] = time.monotonic() - t
        marks["trace"] = profiler.start(prof_dir)
        time.sleep(span_s)
        t = time.monotonic()
        marks["trace_stop"] = profiler.stop()
        marks["stop_s"] = time.monotonic() - t
    finally:
        proc.stdin.close()


def _tail_line(path: str, marks: dict, chips: int) -> dict | None:
    """The generator's records as they stood at the very end (returned).
    Prints what the client saw in the traced slice of the tail, beside an
    untraced slice of the same length at the end of the drain: what an
    open window costs, by ``client_metrics.reduce`` on both."""
    try:
        with open(path) as f:
            tail = json.load(f)
        os.remove(path)
    except OSError:
        return None
    stop = marks.get("trace_stop") or {}
    if not stop.get("ok"):
        return tail
    t0, t1 = stop["t0_monotonic"], stop["t1_monotonic"]

    def cut(a: float, b: float) -> dict:
        c = client_metrics.reduce(dict(tail, t_open=a, t_close=b, t_end=b),
                                  chips=chips)
        return {"output_tok_s": c["output_tok_s"],
                "itl_p50_ms": c["itl_p50_ms"], "itl_p95_ms": c["itl_p95_ms"]}

    info(phase="tail", traced=cut(t0, t1),
         untraced=cut(tail["t_end"] - (t1 - t0), tail["t_end"]),
         slice_s=t1 - t0, discard_s=marks.get("discard_s"),
         stop_s=marks.get("stop_s"), spans=len(stop.get("spans") or ()))
    return tail


def _engine_traces(pod, run: dict) -> list[dict]:
    """The assembled request timelines of requests that arrived inside the
    window (``obs/trace.py`` stamps ``time.monotonic``, the clock the load
    generator uses)."""
    pod.engine.trace.flush()
    return [t for t in pod.engine.trace.store.all()
            if run["t_open"] <= t["start"] < run["t_close"]]


if __name__ == "__main__":
    sys.exit(main())
