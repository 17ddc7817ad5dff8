"""The whole command, end to end, on the CPU at tiny size."""

import json
import subprocess
import sys

from benchmarks import manifest


def _run(*extra):
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--seed", "2147483999",
         "--seconds", "3", *extra], cwd=manifest.ROOT, capture_output=True,
        text=True, timeout=600)


def test_without_a_tpu_nothing_is_built_and_nothing_printed():
    p = _run("--workload", "qwen2.5-7b.chat.flood", "--trace", "0")
    assert p.returncode == 3 and p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_rehearsal_runs_end_to_end_and_claims_nothing():
    p = _run("--workload", "tiny.rehearsal.open", "--trace", "1",
             "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    # counts and host-side waits only: nothing that a chip alone can give
    assert not {"seq_step_ms_p50", "pipe_step_ms_p50", "attn_roofline",
                "device_idle_share"} & set(last["metrics"])
    assert "busy_s" not in last["device"]
    assert "gen_late_p95_ms" in last["metrics"]
