"""The one traffic generator: reads a mix's parameters, makes its requests.

A mix is a JSON file ``bench/traffic/<name>.json``. Its ``arrivals.kind``
is one of

* ``poisson``  open loop: requests fall due at a fixed rate, whatever the
  server does;
* ``backlog``  closed: a queue kept at least ``depth`` requests deep (a
  number, or ``"n_slots"`` for the configuration's slot count);
* ``session_backlog`` closed loop of sessions: ``sessions`` sessions are
  open at once, each with one ask outstanding; a session sends its next
  ask about its document when the previous answer returns, and a session
  that has asked ``asks`` questions is replaced by a new one.

Lengths are ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
``{"dist": "uniform", "min", "max"}`` (inclusive), in tokens.

Every seed gets the same sizes and gaps: they are drawn from the mix's
``shape_seed``; the run's seed only permutes their order, within
consecutive groups of as many requests (or sessions) as the configuration
has slots, and draws the token ids. So the work of a run, and of each batch-sized
stretch of it, does not change with the seed, and two runs of one seed
get identical requests.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator, Optional

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request as the load generator will send it."""

    rid: int
    due: Optional[float]  # seconds from the window's start; None = backlog
    prompt: np.ndarray    # (P,) int32
    n_new: int
    session: int = -1     # session whose document it asks about, -1 = none


def seed_sequence(seed: int, *tag: int) -> np.random.SeedSequence:
    """A numpy seed from a run seed of any size (more than 32 bits)."""
    return np.random.SeedSequence([int(seed) % 2 ** 64, *tag])


def _lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _gaps(rng: np.random.Generator, rate: float, span: float) -> np.ndarray:
    """Exponential gaps of a Poisson process at ``rate`` over ``span`` s:
    as many as the rate gives, scaled so that they fill the span."""
    n = max(1, int(round(rate * span)))
    g = rng.exponential(1.0 / rate, n)
    return g * (span / g.sum())


class Traffic:
    """One run's requests, from a mix, a seed and the window's length."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int,
                 n_slots: int):
        self.mix = mix
        self.kind = mix["arrivals"]["kind"]
        self.vocab = vocab
        self.warmup_s = float(mix["warmup_s"])
        self.group = n_slots
        self._shape = np.random.default_rng(int(mix["shape_seed"]))
        self._order = np.random.default_rng(seed_sequence(seed, 1))
        self._tokens = np.random.default_rng(seed_sequence(seed, 2))
        span = self.warmup_s + float(seconds)
        if self.kind == "poisson":
            self.specs = self._poisson(span)
        elif self.kind in ("backlog", "session_backlog"):
            arr = mix["arrivals"]
            depth = arr["depth" if self.kind == "backlog" else "sessions"]
            self.depth = n_slots if depth == "n_slots" else int(depth)
            self.specs = None
            self._backlog = (self.backlog() if self.kind == "backlog"
                             else self._session_sizes())
            self._asked: dict[int, list] = {}  # rid -> its session's state
            self._rid = self._sid = 0
        else:
            raise ValueError(f"unknown arrivals kind {self.kind!r}")

    @property
    def open_loop(self) -> bool:
        return self.kind == "poisson"

    @property
    def shares_prefix(self) -> bool:
        """Whether requests of the mix share a document as their prefix."""
        return self.kind == "session_backlog"

    def _perm(self, n: int) -> np.ndarray:
        """A permutation of range(n) that keeps each group of
        ``self.group`` consecutive positions together."""
        g = self.group
        return np.concatenate(
            [i + self._order.permutation(min(g, n - i))
             for i in range(0, n, g)]).astype(np.int64)

    def _draw(self, n: int) -> np.ndarray:
        return self._tokens.integers(0, self.vocab, n).astype(np.int32)

    def _poisson(self, span: float) -> list[Spec]:
        rate = float(self.mix["arrivals"]["rate_per_s"])
        gaps = _gaps(self._shape, rate, span)
        n = len(gaps)
        plen = _lengths(self._shape, self.mix["prompt"], n)
        olen = _lengths(self._shape, self.mix["output"], n)
        gaps = gaps[self._perm(n)]
        perm = self._perm(n)
        due = np.cumsum(gaps) - gaps[0] - self.warmup_s
        return [Spec(i, float(due[i]), self._draw(int(plen[perm[i]])),
                     int(olen[perm[i]])) for i in range(n)]

    def refill(self, queued: int, retired) -> list[Spec]:
        """Closed loop: the requests to send now, given how many wait in
        the queue and which request ids the last step retired."""
        if self.kind == "backlog":
            return [next(self._backlog)
                    for _ in range(max(self.depth - queued, 0))]
        out = []
        if self._sid == 0:
            out = [self._open_session() for _ in range(self.depth)]
        for rid in retired:
            session = self._asked.pop(rid, None)
            if session is not None:
                out.append(self._ask(session) if session[2]
                           else self._open_session())
        return out

    def _open_session(self) -> Spec:
        doc_len, asks = next(self._backlog)
        session = [self._sid, self._draw(doc_len), list(asks)]
        self._sid += 1
        return self._ask(session)

    def _ask(self, session: list) -> Spec:
        sid, document, asks = session
        q_len, n_new = asks.pop(0)
        prompt = np.concatenate([document, self._draw(q_len)])
        spec = Spec(self._rid, None, prompt, n_new, session=sid)
        self._asked[self._rid] = session
        self._rid += 1
        return spec

    def _session_sizes(self) -> Iterator[tuple[int, list]]:
        """Endless sessions (document length, [(question, answer)...]):
        sizes in blocks of 256 sessions drawn from the shape seed, each
        block permuted by the run's seed."""
        lo, hi = self.mix["arrivals"]["asks"]
        while True:
            asks = self._shape.integers(lo, hi + 1, 256)
            doc = _lengths(self._shape, self.mix["document"], 256)
            q = [_lengths(self._shape, self.mix["prompt"], k) for k in asks]
            o = [_lengths(self._shape, self.mix["output"], k) for k in asks]
            for j in self._perm(256):
                yield int(doc[j]), [(int(a), int(b))
                                    for a, b in zip(q[j], o[j])]

    def backlog(self) -> Iterator[Spec]:
        """Endless closed-loop requests: the mix's sizes, in blocks of 256
        drawn from the shape seed, each block permuted by the run's seed."""
        rid = 0
        while True:
            plen = _lengths(self._shape, self.mix["prompt"], 256)
            olen = _lengths(self._shape, self.mix["output"], 256)
            for j in self._perm(256):
                yield Spec(rid, None, self._draw(int(plen[j])), int(olen[j]))
                rid += 1
