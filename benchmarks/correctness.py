"""The comparison that decides ``correct``: served logprobs against the
plain reference.

What is compared.  A seeded sample of prompts (their lengths are in the
configuration's ``deploy.json``: short, one chunk, several chunks, so a
later chunk attends over pages an earlier one wrote) is sent to the live
engine together, greedy, with ``logprobs=8``, for a few tokens each.  The
engine answers each token with the eight largest log-probabilities of its
next-token distribution and their ids: the first comes out of chunked
prefill, the rest out of decode steps through the paged cache, mixed with
the other probes' chunks and then pipelined.  The reference is the family
the configuration's ``deploy.json`` names (``cell["reference"]``, a module
of ``benchmarks/references/``; this file imports none by name): it makes
its own weights from the seed (``generate_weights``), runs ONE full causal
forward in float32 over prompt + the tokens the engine chose, and its
log-softmax is read at the same positions and ids.

The number.  For every (probe, token) position: the rms over the eight ids
of ``served - reference``, divided by the standard deviation of the
reference's logits at that position (the scale of a logit; about 1.2 at
these widths).  ``logprob_err`` is that error at the ``quantile`` of the
positions that count (nearest rank; ``deploy.json``), reported for the
prefill positions (the first token of each probe) and the decode positions
apart as well.  A dense model states ``quantile`` 1.0, the largest: one
position out of line, in one probe, fails the run.

Which positions count, and why a routed model states 0.9.  A token whose
router puts the last chosen expert and the first one left out within a
hair of each other takes either, by whatever rounding happened upstream:
the served side in bfloat16 and the reference in float32 then run
different experts, that position reads 0.3 to 3 logit sigmas off, and no
precision is at fault (one layer alone, same input: ``moe_ffn_grouped``
is within 0.9 % of the float32 reference on every one of 512 tokens, PR
23).  The reference knows where that can happen from its own float32
router logits: it returns each layer's margin at each compared position
(in the units in which the family selects; ``decoder``: router logits, so
``exp(margin)`` is the ratio of the two experts' probabilities), and a
position whose smallest margin is under
``tie_margin`` is set aside as a tie and reported, not gated; at least
``min_clean_positions`` have to remain.  On the chip (PR 23, 21 seeds,
1092 positions of probes of 200 tokens and more) 152 positions read more
than 0.3 off; 142 of them had a margin under 0.12, which 58 % of all
positions have, so ``tie_margin`` is 0.12.  What the margin cannot foresee
is a flip in the CONTEXT: a flipped earlier token writes other keys and
values, and every later position attends to them; short contexts suffer
most (after a flip in a 24-token probe every later position read 0.2 to
2.5 off), which is why the routed configuration's probes are 200 tokens
and longer.  Of the 463 positions that counted, 10 still read 0.3 to 1.1
off, up to 4 of a run's 28, and ``quantile`` 0.9 leaves that tenth out: a
fault has to move more than a tenth of the counted positions to fail the
run, which a wrong expert, a wrong routing rule or a wrong chunk of one
probe does.

The limit is per configuration, in ``deploy.json`` under ``correct``, with
the two readings it was set from (largest sound reading, smallest control
reading); PERF.md carries the table.
"""

from __future__ import annotations

import random

import numpy as np

from benchmarks.references._common import log_softmax

TOP = 8


def reference_weights(ref, config: dict, deploy: dict, seed: int) -> dict:
    """The reference family ``ref``'s own weights for this seed, at the
    width the deployment serves (``deploy.json``'s ``weight-dtype``)."""
    from benchmarks.pod import pod_seed
    bits = {"int8": 8, "bf16": 0}[deploy["server_args"]["weight-dtype"]]
    return ref.generate_weights(config, pod_seed(seed), bits)


def probes(spec: dict, seed: int, vocab_bytes: int = 256) -> list[list[int]]:
    """Prompt token ids of the probe sample: ``spec["prompt_tokens"]``
    lengths, ids in the byte tokenizer's range (2..257)."""
    rng = random.Random(f"probes/{seed}")
    return [[2 + rng.randrange(vocab_bytes) for _ in range(n)]
            for n in spec["prompt_tokens"]]


def serve(engine, prompts: list[list[int]], decode_tokens: int,
          timeout_s: float = 600.0) -> list[dict]:
    """Send the probes to the live engine together; per probe the chosen
    token ids and, per token, the top ids and their log-probabilities."""
    from arks_tpu.engine.types import Request, SamplingParams

    reqs = []
    for i, ids in enumerate(prompts):
        req = Request(f"bench-probe-{i}", list(ids), SamplingParams(
            max_tokens=decode_tokens, temperature=0.0, ignore_eos=True,
            logprobs=TOP))
        engine.add_request(req)
        reqs.append(req)
    out = []
    for req in reqs:
        toks, tops = [], []
        while True:
            o = req.outputs.get(timeout=timeout_s)
            toks.extend(o.token_ids)
            tops.extend(top for _, top in (o.logprobs or ()))
            if o.finished:
                if o.finish_reason != "length":
                    raise RuntimeError(f"probe {req.request_id} finished "
                                       f"{o.finish_reason!r}: {o.error}")
                break
        if len(toks) != decode_tokens or len(tops) != decode_tokens:
            raise RuntimeError(f"probe {req.request_id}: {len(toks)} tokens, "
                               f"{len(tops)} logprob entries, wanted "
                               f"{decode_tokens}")
        out.append({"tokens": toks,
                    "top_ids": [[int(t) for t, _ in top] for top in tops],
                    "top_lps": [[float(v) for _, v in top] for top in tops]})
    return out


def _at_quantile(values: np.ndarray, q: float) -> float | None:
    """Nearest rank: the smallest value with a share ``q`` of the values
    at or under it; ``q`` 1.0 is the largest."""
    if not values.size:
        return None
    v = np.sort(values.ravel())
    return float(v[max(int(np.ceil(q * v.size)) - 1, 0)])


def layout(prompts: list[list[int]], served: list[dict]
           ) -> tuple[np.ndarray, np.ndarray]:
    """What the reference's one forward runs over: ``tokens [B, T]``, each
    prompt with the tokens the engine chose but the last, right-padded, and
    ``rows [B, k]``, the positions whose next-token distributions the
    engine reported."""
    k = len(served[0]["tokens"])
    seqs = [p + s["tokens"][:-1] for p, s in zip(prompts, served)]
    t_max = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), t_max), np.int32)
    rows = np.zeros((len(seqs), k), np.int32)
    for i, (p, s) in enumerate(zip(prompts, seqs)):
        tokens[i, :len(s)] = s
        rows[i] = len(p) - 1 + np.arange(k)
    return tokens, rows


def compare(ref, config: dict, weights: dict, prompts: list[list[int]],
            served: list[dict], spec: dict) -> dict:
    """``logprob_err`` and what it was made of; ``ref`` is the
    configuration's reference family, ``spec`` is ``deploy.json``'s
    ``correct`` (``tie_margin``, ``quantile``)."""
    tie_margin, q = spec.get("tie_margin"), spec.get("quantile", 1.0)
    tokens, rows = layout(prompts, served)
    k = rows.shape[1]
    per_layer: list[np.ndarray] = []
    logits = ref.forward(config, weights, tokens, rows, margins=per_layer)
    ref_lp = log_softmax(logits)
    scale = logits.std(axis=-1)                          # [B, k]
    errs = np.zeros(rows.shape)
    for i, s in enumerate(served):
        for j in range(k):
            ids = np.asarray(s["top_ids"][j])
            d = np.asarray(s["top_lps"][j]) - ref_lp[i, j, ids]
            errs[i, j] = np.sqrt(np.mean(d * d)) / scale[i, j]
    top1 = float(np.mean([[s["tokens"][j] in np.argsort(ref_lp[i, j])[-TOP:]
                           for j in range(k)]
                          for i, s in enumerate(served)]))
    margin = np.min(per_layer, axis=0) if per_layer else None   # [B, k]
    tie = np.zeros(errs.shape, bool) if margin is None or tie_margin is None \
        else margin < tie_margin
    return {"logprob_err": _at_quantile(errs[~tie], q),
            "logprob_err_prefill": _at_quantile(errs[:, :1][~tie[:, :1]], q),
            "logprob_err_decode": _at_quantile(errs[:, 1:][~tie[:, 1:]], q),
            "logprob_err_largest": _at_quantile(errs[~tie], 1.0),
            "logprob_err_ties": _at_quantile(errs[tie], 1.0),
            "logprob_err_mean": float(errs.mean()),
            "logprob_err_median": float(np.median(errs)),
            "quantile": q,
            "clean_positions": int((~tie).sum()),
            "tie_positions": int(tie.sum()),
            "chosen_in_reference_top8": top1,
            "logit_std": float(scale.mean()),
            "per_position": [
                [round(float(errs[i, j]), 4), None if margin is None
                 else round(float(margin[i, j]), 4)]
                for i in range(errs.shape[0]) for j in range(k)]}


def verdict(cmp_: dict, spec: dict) -> bool:
    """Every position that counts is inside the limit, and enough count."""
    return (cmp_["logprob_err"] is not None
            and cmp_["logprob_err"] <= spec["limit"]
            and cmp_["clean_positions"] >= spec.get("min_clean_positions", 1))
