"""Pipeline parallelism vs the unsharded oracle on the virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from arks_tpu.models import get_config
from arks_tpu.models import transformer as tf
from arks_tpu.parallel.mesh import make_mesh
from arks_tpu.parallel import pipeline as pp
from arks_tpu.train import sft


@pytest.mark.parametrize("stages,m", [(2, 2), (2, 4)])
def test_pipeline_forward_matches_dense(stages, m):
    cfg = get_config("tiny")  # 2 layers → 1 per stage at S=2
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    b, t = 4, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0, cfg.vocab_size)

    # Oracle: plain stacked-scan forward (pre-final-norm hidden states).
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    h = jnp.take(params["embed"], tokens, axis=0)

    def body(h, lp):
        h, _, _ = tf.prefill_layer(h, lp, cfg, positions, None)
        return h, None
    ref, _ = jax.lax.scan(body, h, params["layers"])

    mesh = make_mesh(tensor_parallel=1, pipeline_parallel=stages,
                     devices=jax.devices()[:stages])
    params_pp = pp.shard_params_pp(params, mesh)
    got = pp.pipeline_forward(params_pp, cfg, tokens, mesh, num_microbatches=m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_train_step_matches_dense():
    cfg = get_config("tiny")
    optimizer = optax.adamw(1e-3)
    b, t = 4, 16
    key = jax.random.PRNGKey(2)
    tokens = jax.random.randint(key, (b, t), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones((b, t), jnp.float32)

    state_ref = sft.train_init(cfg, jax.random.PRNGKey(0), optimizer)
    step_ref = sft.make_train_step(cfg, optimizer)
    state_ref, loss_ref = step_ref(state_ref, tokens, targets, mask)

    mesh = make_mesh(tensor_parallel=1, pipeline_parallel=2,
                     devices=jax.devices()[:2])
    state_pp = pp.pp_train_init(cfg, jax.random.PRNGKey(0), optimizer, mesh)
    step_pp = pp.make_pp_train_step(cfg, optimizer, mesh, num_microbatches=2)
    state_pp, loss_pp = step_pp(state_pp, tokens, targets, mask)

    np.testing.assert_allclose(float(loss_pp), float(loss_ref), rtol=1e-5)
    for a, b_ in zip(jax.tree.leaves(state_pp.params),
                     jax.tree.leaves(state_ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-5, atol=5e-5)


def test_pipeline_rejects_indivisible():
    cfg = get_config("tiny")  # 2 layers
    mesh = make_mesh(tensor_parallel=1, pipeline_parallel=4,
                     devices=jax.devices()[:4])
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(ValueError, match="stages"):
        pp.pipeline_forward(params, cfg, jnp.zeros((4, 8), jnp.int32), mesh, 2)


def test_serving_engine_with_pipeline_parallelism():
    """Serving PP end to end: an engine with pipeline_parallel=2 shards
    layers AND their KV over the stage mesh axis, pipelines decode
    microbatches, and produces the same greedy tokens as the single-device
    engine — including the one-shot prefill -> insert -> decode path."""
    from arks_tpu.engine import (
        EngineConfig, InferenceEngine, Request, SamplingParams)
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.models import get_config

    cfg = get_config("tiny")
    prompts = [[int(x) % cfg.vocab_size for x in range(5, 29)],   # 24 tokens
               [int(x) % cfg.vocab_size for x in range(40, 50)]]  # 10 tokens

    def run(pp):
        ecfg = EngineConfig(model="tiny", num_slots=4, max_cache_len=64,
                            prefill_buckets=(16, 32), steps_per_dispatch=4,
                            pipeline_parallel=pp, prefix_cache_mb=0)
        eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
        if pp > 1:
            # Chunked prefill + prefix cache off; cache stage-sharded.
            assert eng._chunk == 0 and eng._prefix is None
        reqs = [Request(f"p{i}", p, SamplingParams(max_tokens=5,
                                                   temperature=0.0,
                                                   ignore_eos=True))
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.add_request(r)
        for _ in range(100):
            eng.step(block_s=0.01)
            if (eng.num_running == 0 and eng._queue.empty()
                    and eng._deferred is None):
                break
        outs = []
        for r in reqs:
            ids = []
            while True:
                out = r.outputs.get(timeout=60)
                ids.extend(out.token_ids)
                if out.finished:
                    break
            outs.append(ids)
        return outs

    assert run(2) == run(1)


def test_serving_engine_pp_paged():
    """The paged layout composes with pipeline parallelism: the pool
    shards over 'stage' on its layer dim, admissions insert through the
    block tables, decode pipelines microbatches against table-mapped
    pages (pp_decode_step_paged), and greedy output matches the pp=1 slot
    oracle.  Slot reuse is exercised too: more prompts than slots forces
    page free/realloc between requests."""
    from arks_tpu.engine import (
        EngineConfig, InferenceEngine, Request, SamplingParams)
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.models import get_config

    cfg = get_config("tiny")
    prompts = [[int(x) % cfg.vocab_size for x in range(5, 29)],
               [int(x) % cfg.vocab_size for x in range(40, 50)],
               [3] * 17,
               [int(x) % cfg.vocab_size for x in range(7, 38)],
               [9, 8, 7, 6, 5],
               [int(x) % cfg.vocab_size for x in range(11, 43)]]

    def run(pp, layout):
        ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                            prefill_buckets=(16, 32), steps_per_dispatch=4,
                            pipeline_parallel=pp, prefix_cache_mb=0,
                            kv_layout=layout)
        eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
        eng.start()
        outs = []
        try:
            reqs = [Request(f"p{i}", list(p), SamplingParams(
                max_tokens=6, temperature=0.0, ignore_eos=True))
                for i, p in enumerate(prompts)]
            for r in reqs:
                eng.add_request(r)
            for r in reqs:
                ids = []
                while True:
                    out = r.outputs.get(timeout=120)
                    ids.extend(out.token_ids)
                    if out.finished:
                        break
                outs.append(ids)
        finally:
            eng.stop()
        return outs

    assert run(2, "paged") == run(1, "slot")
