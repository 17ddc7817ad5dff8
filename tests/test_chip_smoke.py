"""chip_smoke.py, rehearsed on the CPU, and what it rests on.

The smoke's phases are functions that take the model and the shapes.  On
the chip the command hands them qwen2.5-7b; here a test hands them the
``tiny`` model, so wrong paths, arguments and control flow are found
without chip time.  The steering is all in this file: the program has no
option for it.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from arks_tpu.utils import compile_cache  # noqa: E402

# One 128-token page behind a 128-token chunk: the smallest shape the
# quantized update kernels take (their scale chunk is 128 lanes).
PARITY = dict(hkv=2, g=2, d=16, page=128, max_pages=2, chunk=128,
              decode_lanes=3)


# ---------------------------------------------------------------------------
# The command
# ---------------------------------------------------------------------------


def test_command_without_a_tpu_fails_before_building_anything():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env,
                       cwd=REPO)
    assert r.returncode != 0
    lines = [json.loads(x) for x in r.stdout.strip().splitlines()]
    assert lines[-1]["ok"] is False and "no TPU" in lines[-1]["error"]
    assert [x.get("phase") for x in lines[:-1]] == ["device"]


@pytest.mark.parametrize("kv,tp", [("int8", 0), ("int4", 0), ("int8", 2)])
def test_kernel_parity_phase(kv, tp):
    """tp=2: both sides under a mesh with one KV head a device, the way the
    four-chip phase runs it (kernels inside shard_map)."""
    from arks_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(tensor_parallel=tp, devices=jax.devices()[:tp]) if tp \
        else None
    res = chip_smoke.kernel_parity(kv=kv, mesh=mesh, **PARITY)
    assert res["pool_bit_equal"] and res["rel_diff"] <= res["rel_bound"]
    assert (res["mesh"] or {}).get("model", 0) == tp


def test_kernel_parity_phase_with_a_shared_chunk():
    """The flood's shape in small: the chunk's rows shared among several
    prefilling lanes beside the decode lanes."""
    res = chip_smoke.kernel_parity(kv="int8", **{**PARITY, "chunk_lanes": 5})
    assert res["pool_bit_equal"] and res["rel_diff"] <= res["rel_bound"]
    assert res["chunk_lanes"] == 5


def test_head_group_parity_phase():
    res = chip_smoke.head_group_parity(kv="int8", **PARITY)
    assert res["max_abs_diff_by_head_group"] == {"1": 0.0}


def test_kernel_parity_phase_can_fail():
    with pytest.raises(chip_smoke.SmokeFailure, match="exceeds"):
        chip_smoke.kernel_parity(kv="int8", rel_bound=0.0, **PARITY)


def test_pod_phases_with_the_tiny_model(monkeypatch, capsys):
    """The rehearsal: build through the server's own functions, warm up,
    drive the checked traffic over real HTTP, read /metrics, stop — the
    paged pool, the mixed step and the depth-2 pipeline as a TPU resolves
    them, with the XLA oracle in the kernels' place."""
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "2")
    labels = {"kv_layout": "paged", "decode_impl": "xla",
              "mixed_step": "true", "mixed_grid": "ragged",
              "kv_dtype": "engine", "pipeline_depth": "2",
              "tensor_parallel": "1"}
    # 256-token pages in a 768-token window; the 300-token prompt spans
    # two chunks and the prefix pair shares exactly one page.
    traffic = chip_smoke.Traffic(prompt_lens=(8, 40, 300), max_tokens=8,
                                 streams=3, waves=1, prefix_len=256,
                                 timeout_s=300)
    argv = chip_smoke.server_argv("tiny", num_slots=4, max_model_len=768,
                                  weight_dtype="bf16", tp=1,
                                  extra=("--kv-layout", "paged"))
    out = chip_smoke.run_pod(argv, labels, traffic, chip_smoke.CompileMeter())
    assert out["requests"] == 2 + 4 + 2 and out["prefix_hit_tokens"] >= 256
    assert out["pipelined_issues"] > 0
    out_text = capsys.readouterr().out
    phases = [json.loads(x)["phase"] for x in out_text.strip().splitlines()]
    assert phases == ["build", "launch_plan", "warmup", "traffic", "metrics"]
    lines = [json.loads(x) for x in out_text.strip().splitlines()]
    assert lines[2]["pipe_programs"] == "ready"
    assert lines[2]["pipelined_dispatches"] > 0 == lines[3]["compiles"]

    # A label the engine did not resolve to is a failed check, and the pod
    # is stopped on the way out.
    with pytest.raises(chip_smoke.SmokeFailure, match="engine_config_info"):
        chip_smoke.build_pod(argv, {**labels, "kv_dtype": "int8"},
                             chip_smoke.CompileMeter())


# ---------------------------------------------------------------------------
# The compile cache helper
# ---------------------------------------------------------------------------


def test_compile_cache_env_set_sets_no_directory_in_code(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.configure() == "/somewhere/else"
    assert calls == []


def test_compile_cache_is_not_placed_on_the_cpu(monkeypatch):
    """What ships for ``--platform cpu``: no cache, so a second start of a
    multi-device CPU server has nothing to read back (jaxlib 0.9.0 aborts
    on some such entries)."""
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert jax.default_backend() == "cpu"
    assert compile_cache.configure() is None
    assert calls == []


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first, second = compile_cache.configure(), compile_cache.configure()
    want = os.path.join(REPO, ".jax_compile_cache")
    assert first == second == want
    assert calls == [("jax_compilation_cache_dir", want)] * 2
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()
    # Another process resolves the same path (no pid, time or temp name).
    code = ("from arks_tpu.utils import compile_cache as c; "
            "print(c.DEFAULT_DIR)")
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV_VAR}
    outs = {subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=60,
                           check=True).stdout.strip() for _ in range(2)}
    assert outs == {want}


# ---------------------------------------------------------------------------
# The decode_impl label names the path that is traced
# ---------------------------------------------------------------------------


def _tiny_engine(mesh=None, **kw):
    from arks_tpu.engine.engine import EngineConfig, InferenceEngine
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.models import get_config
    return InferenceEngine(get_config("tiny"), EngineConfig(
        model="tiny", num_slots=2, max_cache_len=256, prefix_cache_mb=0,
        **kw), ByteTokenizer(), mesh=mesh)


def test_decode_impl_label_follows_the_traced_choice(monkeypatch):
    """On a TPU ``auto`` asks for the kernels; a shape they cannot take
    (head dim 8, lane padding off) runs the XLA path and must say so."""
    from arks_tpu.ops import attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("ARKS_PAD_HEAD_DIM", "0")
    assert attention.default_decode_impl() == "pallas"
    assert attention.kernel_blockers(8) and not attention.kernel_blockers(128)
    eng = _tiny_engine()
    assert eng.resolved_config["decode_impl"] == "xla"
    assert eng.resolved_config["kv_layout"] == "slot"
    # The dispatchers decide from the same list the label was read from.
    assert not attention._use_pallas(None, 8, None, False, "model")
    assert attention._use_pallas(None, 128, None, False, "model")
    # Asked for by name, the kernels are not quietly given up.
    monkeypatch.setenv("ARKS_ATTN_IMPL", "pallas")
    with pytest.raises(ValueError, match="cannot take the Pallas"):
        _tiny_engine()


def test_decode_impl_label_under_replicated_kv_heads(monkeypatch):
    """tp=8 over tiny-gqa's 4 KV heads replicates them: the kernels'
    shard_map path does not apply, whatever the backend."""
    import numpy as np
    from jax.sharding import Mesh

    from arks_tpu.ops import attention
    mesh = Mesh(np.array(jax.devices()[:8]), ("model",))
    assert attention.kernel_blockers(128, mesh, kv_sharded=False)
    assert not attention.kernel_blockers(128, mesh, kv_sharded=True)


# ---------------------------------------------------------------------------
# The pipeline_depth label names the depth that serves
# ---------------------------------------------------------------------------


def test_meshed_engine_resolves_to_depth_zero_and_says_so(monkeypatch):
    """Under a mesh the pipe programs never built (their specimen arrays
    sat on the default device next to mesh-sharded weights) and the engine
    served sequentially while the label said 2.  It now resolves to 0."""
    from arks_tpu.parallel.mesh import make_mesh
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "2")
    one = _tiny_engine()
    assert one.resolved_config["pipeline_depth"] == "2" and one._pipe_depth == 2
    eng = _tiny_engine(mesh=make_mesh(tensor_parallel=2,
                                      devices=jax.devices()[:2]))
    assert eng.resolved_config["tensor_parallel"] == "2"
    assert eng.resolved_config["pipeline_depth"] == "0"
    assert eng._pipe_depth == 0
    assert eng._pipe_warm_wait(1.0) is None      # nothing to build


def test_stop_joins_the_pipe_program_build(monkeypatch):
    """The off-thread build is a bound method: until it returns it keeps
    the engine, its weights and its pool alive.  stop() waits for it, so a
    stopped engine is collectable (the next one needs the device)."""
    import gc
    import weakref
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "2")
    eng = _tiny_engine()
    eng._pipe_kick_warmup()
    thread = eng._pipe_warm_thread
    assert thread is not None
    eng.stop()
    assert not thread.is_alive() and eng._pipe_warm_state == "ready"
    ref = weakref.ref(eng)
    del eng, thread
    gc.collect()
    assert ref() is None

