"""Read the correctness number on many seeds, for the program as the cells
run it and for its lower-precision controls, in one process.

    python3 -m benchmarks.check_correct --config qwen2.5-7b \
        --seeds 11,12,13 [--control weight_int4] [--rehearse]

A control is the program itself with one of ``deploy.json``'s
``correct.controls`` switched on (int4 weights for int8, int4 KV for
int8): the step below what the configuration states.  The benchmark's own
runs never run a control; this tool and ``benchmarks/tests`` do.  One JSON
line per seed; the last line gives the largest and the smallest reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

from benchmarks import manifest


def read_one(config_name: str, seed: int, control: str | None,
             platform: str | None = None) -> dict:
    from benchmarks import correctness, pod as podlib

    cdir = manifest.config_dir(config_name)
    with open(os.path.join(cdir, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(cdir, "deploy.json")) as f:
        deploy = json.load(f)
    config = manifest.with_share(config, deploy)
    ref = manifest.load_reference(deploy["reference"])
    spec = deploy["correct"]
    t0 = time.monotonic()
    weights = correctness.reference_weights(ref, config, deploy, seed)
    pod = podlib.build(config_name, cdir, deploy, seed,
                       overrides=spec["controls"][control] if control
                       else None, platform=platform)
    try:
        pod.engine._pipe_warm_wait(900.0)
        prompts = correctness.probes(spec, seed)
        served = correctness.serve(pod.engine, prompts,
                                   spec["decode_tokens"])
        out = correctness.compare(ref, config, weights, prompts, served,
                                  spec)
        out["ok"] = correctness.verdict(out, spec)
    finally:
        pod.close()
        del pod, weights
        gc.collect()
    return dict(out, seed=seed, control=control, limit=spec["limit"],
                seconds=time.monotonic() - t0)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", default=None)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    from benchmarks import pod as podlib
    dev = podlib.device_info()
    if not args.rehearse and dev["platform"] != "tpu":
        print(f"check_correct: needs a TPU; jax reports {dev}",
              file=sys.stderr)
        return 3
    podlib.place_compile_cache()
    vals = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = read_one(args.config, seed, args.control,
                     "cpu" if args.rehearse else None)
        vals.append(r["logprob_err"] if r["logprob_err"] is not None
                    else float("nan"))
        print(json.dumps(r), flush=True)
    print(json.dumps({"config": args.config, "control": args.control,
                      "device": dev, "seeds": len(vals), "min": min(vals),
                      "max": max(vals)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
