"""One general traffic generator: a mix file's parameters + a seed -> requests.

Standard library only: the load generator's child process imports this and
must never import JAX.

A mix file (``benchmarks/traffic/<mix>.json``) has these keys:

``loop``              ``"closed"`` (callers that wait for their reply) or
                      ``"open"`` (arrivals on a schedule).
``load_share``        the load offered, as a share of the knee stored in
                      ``benchmarks/knees/<config>.<mix>.json`` with the
                      sweep that found it: callers (closed loop, rounded)
                      or requests a second (open loop).
``ramp_s``            load offered before the window opens (set-up).
``drain_s``           after the window closes, how long a request that was
                      due inside it may still wait for its first token.
``trace_s``           length of the profiler slice of a ``--trace 1`` run,
                      and of the traced tail of a ``--trace 2`` run.
``shape_seed``        seed of the fixed pools below.  A pool holds one
                      (prompt, output) size pair per caller (closed loop)
                      or per arrival of the run (open loop), so the first
                      wave, or the whole run, uses every pair exactly once.
``prompt_tokens`` / ``output_tokens``
                      a distribution: ``{"dist": "lognormal", "median",
                      "sigma", "min", "max"}`` or ``{"dist": "uniform",
                      "min", "max"}``.
``max_total_tokens``  prompt + output never exceeds this.
``sessions``          absent, or ``{"count", "system_tokens", "recent"}``:
                      a request is the next turn of a session; its prompt
                      is the session's history plus ``prompt_tokens`` new
                      tokens; the history then grows by that prompt and a
                      reply of the generator's own making, ``output_tokens``
                      long, and starts again from the system prompt when
                      the next turn would pass ``max_total_tokens``.  A
                      session is never picked again within its ``recent``
                      following arrivals.

The sizes, the inter-arrival gaps and (sessions) which session takes which
turn come from ``shape_seed`` alone: every run replays the same schedule,
as a recorded trace would be replayed.  The run's seed decides the text of
every prompt (and, in ``run.py``, the weights and the probes).  Why not
another order per seed: two runs of one seed agree to 0.1 % in tokens per
second, while seeds that shuffled or rotated the same sizes read 11-14 %
apart, by which requests the window's edges cut (PR 23: 2 x 6 runs each
way on the chip).  The seed must not change the work.

A ``--trace 2`` run keeps the load going after the measured window for its
traced tail (``tail_s``).  The tail is drawn from a continuation of its own
(``tail/<shape_seed>``), after the run's schedule is complete: the first
``count()`` arrivals, their sizes and their due times are the same bit for
bit with and without a tail.  A closed loop needs none: its callers cycle
through the same pool for as long as they are kept going.
"""

from __future__ import annotations

import math
import random
import string

_ALPHABET = string.ascii_letters + " "


def _draw(rng: random.Random, d: dict) -> int:
    if d["dist"] == "uniform":
        return rng.randint(d["min"], d["max"])
    if d["dist"] == "lognormal":
        x = rng.lognormvariate(math.log(d["median"]), d["sigma"])
        return int(min(max(round(x), d["min"]), d["max"]))
    raise ValueError(f"unknown distribution {d['dist']!r}")


def _text(rng: random.Random, n: int) -> str:
    return "".join(rng.choices(_ALPHABET, k=n))


class Schedule:
    """Request ``i`` of a run, for any ``i``, as a function of (mix, seed).

    ``request(i)`` must be called with i = 0, 1, 2, ... in order (sessions
    carry state from turn to turn)."""

    def __init__(self, mix: dict, seed: int, *, load: float,
                 seconds: float, tail_s: float = 0.0) -> None:
        self.mix, self.seed = mix, int(seed)
        self.loop = mix["loop"]
        shape = random.Random(f"sizes/{mix['shape_seed']}")
        limit = mix["max_total_tokens"]
        if not load or load <= 0:
            raise ValueError("a mix needs a load: callers or requests/s")
        self._next = 0
        self.total_s = mix["ramp_s"] + seconds
        if self.loop == "closed":
            self.clients = n = max(int(round(load)), 1)
            self.due = None
        else:
            self.clients = 0
            n = max(int(round(load * self.total_s)), 1)
            grng = random.Random(f"gaps/{mix['shape_seed']}")
            gaps = [grng.expovariate(1.0) for _ in range(n)]
            scale = self.total_s / sum(gaps)
            t, self.due = 0.0, []
            for g in gaps:
                self.due.append(t)     # the first is due at 0
                t += g * scale
        def pair(rng: random.Random) -> tuple[int, int]:
            p = _draw(rng, mix["prompt_tokens"])
            o = _draw(rng, mix["output_tokens"])
            return p, min(o, limit - p)

        self._pool = [pair(shape) for _ in range(n)]
        self._offered = n
        if self.due is not None and tail_s > 0:
            # Arrivals past the run's end, at the run's rate, each with a
            # size pair of its own: appended, so request i < n reads the
            # pool entry and the due time it always read.
            trng = random.Random(f"tail/{mix['shape_seed']}")
            t = self.total_s
            while True:
                t += trng.expovariate(load)
                if t >= self.total_s + tail_s:
                    break
                self.due.append(t)
                self._pool.append(pair(trng))
        s = mix.get("sessions")
        self._sessions = None
        if s:
            srng = random.Random(f"sessions/{self.seed}")
            self._sessions = [_text(srng, s["system_tokens"])
                              for _ in range(s["count"])]
            self._history = list(self._sessions)
            self._recent: list[int] = []
            self._pick_rng = random.Random(f"pick/{mix['shape_seed']}")

    def prime(self) -> list[dict]:
        """Requests sent once during set-up: each session's system prompt."""
        if not self._sessions:
            return []
        return [{"id": f"prime-{i}", "prompt": p, "max_tokens": 1}
                for i, p in enumerate(self._sessions)]

    def request(self, i: int) -> dict:
        if i != self._next:
            raise ValueError(f"request({i}) out of order (next {self._next})")
        self._next += 1
        p, o = self._pool[i % len(self._pool)]
        rng = random.Random(f"text/{self.seed}/{i}")
        req = {"id": f"r{i}", "max_tokens": o,
               "due": None if self.due is None else self.due[i]}
        if self._sessions is None:
            req["prompt"] = _text(rng, p)
            return req
        s = self.mix["sessions"]
        free = [k for k in range(s["count"]) if k not in self._recent]
        k = self._pick_rng.choice(free)
        self._recent = (self._recent + [k])[-s["recent"]:]
        if len(self._history[k]) + p + o > self.mix["max_total_tokens"]:
            self._history[k] = self._sessions[k]
        prompt = self._history[k] + _text(rng, p)
        self._history[k] = prompt + _text(rng, o)
        req.update(prompt=prompt, session=k)
        return req

    def count(self) -> int | None:
        """Open loop: how many requests the run offers (its tail, if any,
        not counted).  Closed: None."""
        return None if self.due is None else self._offered
