"""The one harness of the engine tests (ROADMAP D9): a preset's published
config, an engine of the block files' shape, the three requests on its
slots, the loop that drains them, the comparison of two engines' streams
and the invariants of the window pool.

What an engine costs on the CPU is its leaves (a quantised preset's
initialiser compiles for ~10 s) and its step programs (~8 s at the first
dispatch, ~6 s more for the two pipe programs at depth 2).  ``conftest.py``
takes both away for the second engine of a worker that is equal to an
earlier one (a seed's tree drawn once, an equal program compiled once), so
every test takes an engine of its own from ``engine`` / ``fresh``: a cache
of built engines on top of that returned 0-8 % of the six files that used
it and was taken out again (PR 50)."""

import contextlib
import inspect
import json
import os
import queue
import sys
import time

import numpy as np

from arks_tpu.engine.engine import EngineConfig, InferenceEngine
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.engine.types import Request, SamplingParams
from arks_tpu.models import config as config_mod
from arks_tpu.models.config import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)                 # ``benchmarks`` is imported
CONFIGS = os.path.join(ROOT, "benchmarks", "configs")

# The block files' engine: two slots of 256 tokens in pages of 16, int8
# leaves, bf16 pages; and where a block's own differs (the window blocks
# take a third slot, a latent pool its rows in the engine's dtype).
BLOCK = dict(num_slots=2, max_cache_len=256, prefill_buckets=(16,),
             prefill_chunk=16, weight_dtype="int8", kv_cache_dtype="bf16",
             seed=3)
SHAPE = {
    "tiny-swa-moe": dict(num_slots=3),
    "tiny-swa-sink-moe": dict(num_slots=3),
    "tiny-latent-linear-moe": dict(kv_cache_dtype="auto"),
    "tiny-mla-moe": dict(max_cache_len=128, kv_cache_dtype="auto", seed=0),
    "tiny-shortcut-mla-moe": dict(kv_cache_dtype="auto"),
}
# Tokens a request decodes in a block's shared streams.
DECODE = {"tiny-swa-moe": 12, "tiny-swa-sink-moe": 12}


def as_drawn(w):
    """A head-split leaf ``[.., H, D, K]`` (``tf.split_heads``: a GQA
    stack's q / k / v, the latent block's ``wq_b`` / ``wkv_b``) in the order
    it is DRAWN in, ``[.., K, H x D]``: the same numbers, which is what the
    reference families generate and a checkpoint of the old order held."""
    return w.reshape(*w.shape[:-3], -1, w.shape[-1]).swapaxes(-1, -2)


def published(name: str, **restored) -> dict:
    """``benchmarks/configs/<name>/config.json`` with ``restored`` laid over
    it: what a benchmark file's ``reduced`` lists put back, or a tiny
    preset's file with one key changed."""
    with open(os.path.join(CONFIGS, name, "config.json")) as f:
        return {**json.load(f), **restored}


def deploy(name: str) -> dict:
    with open(os.path.join(CONFIGS, name, "deploy.json")) as f:
        return json.load(f)


def reference(preset: str, family: str, **over):
    """(the reference family's module, ``preset``'s config file as its
    ``deploy.json`` shares it out) for a block's step program to be held
    against."""
    from benchmarks import manifest
    return manifest.load_reference(family), manifest.with_share(
        published(preset, **over), deploy(preset))


def _cfg(preset):
    return get_config(preset) if isinstance(preset, str) else preset


def engine_config(preset, base=None, **over) -> EngineConfig:
    """``BLOCK`` in the preset's ``SHAPE`` with ``over`` laid on it; a file
    whose engines are another shape altogether gives its own ``base``."""
    name = _cfg(preset).name
    if base is None:
        base = {**BLOCK, **SHAPE.get(name, {})}
    return EngineConfig(**{**base, "model": name, **over})


def engine(preset, **over) -> InferenceEngine:
    """A fresh engine of ``preset`` (a name or a ``ModelConfig``); the
    caller stops it, or never gets it where the preflight refuses it."""
    return InferenceEngine(_cfg(preset), engine_config(preset, **over),
                           ByteTokenizer())


def warmed(preset, **over) -> InferenceEngine:
    """``engine(...)`` with its pipe programs compiled, where it steps ahead
    of the host at all: serving warms them in the background and stays
    sequential meanwhile, and a short workload would end before the
    pipelined path opens."""
    eng = engine(preset, **over)
    if eng._pipe_depth:
        assert eng._pipe_warm_wait(120.0) == "ready"
    return eng


@contextlib.contextmanager
def fresh(preset, **over):
    """``warmed(...)``, stopped behind the ``with``."""
    eng = warmed(preset, **over)
    try:
        yield eng
    finally:
        eng.stop()


def requests(n_decode=10, logprobs=None, lengths=(70, 9, 133)):
    """Three greedy requests: on two slots the third takes a slot one of the
    others just left."""
    rng = np.random.default_rng(1)
    sp = SamplingParams(max_tokens=n_decode, temperature=0.0,
                        ignore_eos=True, logprobs=logprobs)
    return [Request(f"r{i}", (2 + rng.integers(0, 200, n)).tolist(), sp)
            for i, n in enumerate(lengths)]


def _drain_outputs(r, toks, lps) -> bool:
    """What ``r``'s queue holds now; True once its last frame is read."""
    done = False
    while not r.outputs.empty():
        o = r.outputs.get()
        toks[r.request_id] += o.token_ids
        lps[r.request_id] += [lp for lp, _ in (o.logprobs or ())]
        if o.finished:
            assert o.finish_reason == "length", o.error
            done = True
    return done


def drain(eng, reqs, each_step=None, steps=1000):
    """Step ``eng`` until every request has ended: ({id: tokens}, {id:
    chosen log-probabilities})."""
    for r in reqs:
        eng.add_request(r)
    done = set()
    toks = {r.request_id: [] for r in reqs}
    lps = {r.request_id: [] for r in reqs}
    for _ in range(steps):
        eng.step()
        if each_step is not None:
            each_step(eng)
        done |= {r.request_id for r in reqs if _drain_outputs(r, toks, lps)}
        if len(done) == len(reqs):
            return toks, lps
    raise AssertionError(f"requests did not finish in {steps} steps: "
                         f"{sorted(set(toks) - done)} still open")


def serve(eng):
    """The block's three requests with their log-probabilities through
    ``eng``, the window pool's invariant checked after every step where
    there is one: ``(tokens, log-probabilities)``."""
    return drain(eng, requests(DECODE.get(eng.cfg.name, 10), logprobs=1),
                 held_invariant if eng._win else None)


def drive(eng, n_steps=4000, recover=False):
    """Step ``eng`` as its own thread would, until nothing runs, waits in
    the queue, prefills or is parked (on a guide's compile, a restore, a
    fetch): a loop that leaves a parked request behind has its reader wait
    for a frame that no step will make.  With ``recover`` a step's fault is
    routed as ``_run_loop`` routes it."""
    for _ in range(n_steps):
        try:
            eng.step(block_s=0.01)
        except Exception as e:  # noqa: BLE001
            if not recover:
                raise
            eng._recover_from_fault(e)
        if (eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None and not eng._prefilling
                and not eng._awaiting_guide and not eng._awaiting_fetch
                and not eng._awaiting_restore and eng.state == "serving"):
            return


def collect(req, timeout=120.0, logprobs=False):
    """A request's whole stream off its queue, as a client reads it: (token
    ids, the last frame), with the frames' log-probability entries between
    them where ``logprobs``.  A frame that does not come in ``timeout``
    seconds (120 at the most) fails the test by the request's name."""
    ids, lps, timeout = [], [], min(timeout, 120.0)
    while True:
        try:
            out = req.outputs.get(timeout=timeout)
        except queue.Empty:
            raise AssertionError(f"waited {timeout:g} s for a frame of "
                                 f"request {req.request_id!r}")
        ids.extend(out.token_ids)
        lps.extend(out.logprobs or ())
        if out.finished:
            return (ids, lps, out) if logprobs else (ids, out)


def streams_agree(got, want, atol=2e-3, whole=2):
    """Two engines' greedy streams of the same requests, ``(tokens,
    log-probabilities)`` each.  Two compiled programs round differently:
    where two logits tie the streams may part; up to there they are equal,
    and there the two chosen log-probabilities are (a tie).  ``whole`` of
    the streams are equal to their ends."""
    (toks, lps), (toks0, lps0) = got, want
    for rid in toks:
        same = next((i for i, (a, b) in enumerate(zip(toks[rid], toks0[rid]))
                     if a != b), len(toks[rid]))
        assert same >= 1, (rid, toks[rid], toks0[rid])
        n = min(same + 1, len(lps[rid]))
        np.testing.assert_allclose(lps[rid][:n], lps0[rid][:n], atol=atol)
    assert sum(toks[r] == toks0[r] for r in toks) >= whole


def held_invariant(eng):
    """After every step: no slot holds more window pages than the
    per-slot bound or the same page twice, and the pool's free list holds
    every page no live slot holds."""
    win = eng._win
    held = 0
    for slot in list(eng._slots) + list(eng._prefilling):
        first, pages = win.held(slot)
        assert len(pages) <= win.per_slot
        assert len(set(pages)) == len(pages)
        held += len(pages)
    assert win.pages_in_use == held
    assert win.alloc.free_pages == win.alloc.num_pages - held


def pool_invariant(eng):
    """After every step: what admission promised fits the pool, and no
    slot owns more full pages than it was promised."""
    held_invariant(eng)
    assert sum(eng._pool_reserved.values()) <= eng._pool_budget
    for slot in list(eng._slots) + list(eng._prefilling):
        assert len(eng._slot_pages[slot]) <= eng._pool_reserved[slot]
    assert (eng._alloc.num_pages - eng._alloc.free_pages
            <= sum(eng._pool_reserved.values()))


def wait_for(predicate, timeout=30.0, interval=0.05, what=None):
    """Poll until ``predicate()`` is truthy and return what it gave.  No wait
    outlasts 120 s (no ``pytest-timeout`` here: a test ends its own waits);
    one that runs out fails naming ``what``, or the predicate's source."""
    timeout = min(timeout, 120.0)
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = predicate()
        if v:
            return v
        time.sleep(interval)
    if what is None:
        try:
            what = " ".join(inspect.getsource(predicate).split())
        except (OSError, TypeError):
            what = repr(predicate)
    raise AssertionError(f"waited {timeout:g} s for: {what}")


def arks_state():
    """What one process's tests share besides the devices: the ``ARKS_*``
    variables and the preset registry."""
    return ({k: v for k, v in os.environ.items() if k.startswith("ARKS_")},
            dict(config_mod._REGISTRY))


def put_back(found) -> list:
    """Restore ``arks_state()``'s ``found``; the names that had changed."""
    env, registry = found
    now_env, now_registry = arks_state()
    left = sorted(k for k in set(env) | set(now_env)
                  if env.get(k) != now_env.get(k))
    left += sorted(f"preset {k}" for k in set(registry) | set(now_registry)
                   if registry.get(k) != now_registry.get(k))
    for k in now_env:
        del os.environ[k]
    os.environ.update(env)
    config_mod._REGISTRY.clear()
    config_mod._REGISTRY.update(registry)
    return left
