"""The benchmark's own traffic: seeded raw streams and their classification.

Copied from the program's ``repro.data.streams`` (``gen_stock_drift``,
``gen_soccer``, ``classify`` and its E-BL priorities) so that a later change
to the program cannot change the yardstick.  A configuration file names its
dataset and generator parameters; a workload file names the push size, the
loop (closed or open) and, for an open loop, the wall-clock rate.

Classification runs per push on the host: the program receives only the
resulting ``EventBatch`` columns.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Raw:
    """Column-oriented raw events of one dataset."""
    kind: str              # 'stock' | 'soccer'
    type_id: np.ndarray    # symbol | player id (-1: no player event)
    attr: np.ndarray       # stock: rise 1/0 | soccer: 2 striker, 1 defend, 0
    group: np.ndarray      # stock: 0 | soccer: striker id bound to the event

    @property
    def n(self) -> int:
        return int(self.type_id.shape[0])


def gen_stock(n: int, rng, carry, num_symbols: int, pattern_symbols: int,
              hot_fraction: float, p_class: float):
    """NYSE-like quotes (``streams.gen_stock_drift`` without drift, same
    draws): the pattern symbols take ``hot_fraction`` of the ticks and a
    hot tick is a matchable rise with probability ``p_class``."""
    hot = rng.integers(0, pattern_symbols, size=n)
    cold = rng.integers(pattern_symbols, num_symbols, size=n)
    is_hot = rng.random(n) < hot_fraction
    type_id = np.where(is_hot, hot, cold).astype(np.int32)
    rise = ((rng.random(n) < p_class) & is_hot).astype(np.int32)
    return (type_id, rise, np.zeros(n, np.int32)), carry


def gen_soccer(n: int, rng, carry, num_players: int, num_strikers: int,
               p_striker: float, p_defend: float):
    """RTLS-like positions (``streams.gen_soccer``): striker possessions
    open windows; defend events carry the last striker in possession
    (``carry``: the last one of the previous block, -1 before any)."""
    r = rng.random(n)
    is_striker = r < p_striker
    is_defend = (~is_striker) & (r < p_striker + p_defend)
    striker_ids = rng.integers(0, num_strikers, size=n).astype(np.int32)
    cur = np.maximum.accumulate(np.where(is_striker, np.arange(n), -1))
    last = np.where(cur >= 0, striker_ids[np.maximum(cur, 0)], carry)
    defender = rng.integers(num_strikers, num_players,
                            size=n).astype(np.int32)
    type_id = np.where(is_striker, striker_ids,
                       np.where(is_defend, defender, -1)).astype(np.int32)
    attr = np.where(is_striker, 2, np.where(is_defend, 1, 0)).astype(np.int32)
    group = np.where(is_striker, striker_ids, last).astype(np.int32)
    return (type_id, attr, group), int(group[-1]) if n else carry


GENERATORS = {"stock": gen_stock, "soccer": gen_soccer}


class Stream:
    """An unbounded raw stream, generated on demand in blocks of
    ``BLOCK`` events, block ``b`` from the seed sequence ``(seed, b)``;
    a tenant lane's stream has the seed ``(seed, lane)``, so its blocks
    come from ``(seed, lane, b)``.

    Blocks keep the generator's temporaries small: a process that frees
    large host buffers raises glibc's mmap and trim thresholds, and that
    changed the program's own host-path speed by 16% between otherwise
    equal runs.  Blocks also mean no run can outgrow its stream."""

    BLOCK = 1 << 18

    def __init__(self, dataset: dict, seed: int | tuple[int, ...]):
        self.kind = dataset["name"]
        self._gen = GENERATORS[self.kind]
        self._params = {k: v for k, v in dataset.items() if k != "name"}
        self._seed = list(seed) if isinstance(seed, tuple) else [seed]
        self._blocks: list = []
        self._carry = -1
        self._rand: list = []

    def _grow(self, n: int) -> None:
        while len(self._blocks) * self.BLOCK < n:
            rng = np.random.default_rng(self._seed + [len(self._blocks)])
            cols, self._carry = self._gen(self.BLOCK, rng, self._carry,
                                          **self._params)
            self._blocks.append(cols)
            self._rand.append(rng.random(self.BLOCK).astype(np.float32))

    def _take(self, parts: list, lo: int, hi: int) -> np.ndarray:
        self._grow(hi)
        b0, b1 = lo // self.BLOCK, (hi - 1) // self.BLOCK
        out = np.concatenate(parts[b0:b1 + 1]) if b1 > b0 else parts[b0]
        return out[lo - b0 * self.BLOCK:hi - b0 * self.BLOCK]

    def raw(self, lo: int, hi: int) -> Raw:
        self._grow(hi)
        cols = [self._take([b[k] for b in self._blocks], lo, hi)
                for k in range(3)]
        return Raw(self.kind, *cols)

    def uniforms(self, lo: int, hi: int) -> np.ndarray:
        return self._take(self._rand, lo, hi)


def _classify_one(q: dict, raw: Raw):
    """(class, bind, open, potential class) of each event for one query,
    as ``streams._classify_one`` defines them."""
    t, a, g = raw.type_id, raw.attr, raw.group
    if raw.kind == "stock":
        is_pat = t < q["num_symbols"]
        pot = np.where(is_pat, t + 1, 0)
        cls = np.where(is_pat & (a == 1), t + 1, 0)
        opens = cls == 1
        bind = np.full(raw.n, -1, np.int32)
    else:
        cls = np.where(a == 1, 1, 0)
        opens = a == 2
        bind = g
        pot = np.where(a == 2, 2, np.where(t >= 0, 1, 0))
    return (cls.astype(np.int32), bind.astype(np.int32), opens.astype(bool),
            pot.astype(np.int32))


class Classifier:
    """Per-push ``streams.classify`` over a ``Stream``.

    The E-BL type priorities need stream-wide type frequencies; they are
    taken once, over the stream's first block (the distribution does not
    drift), where ``classify`` takes them over the whole stream it is
    given.  Arrival stamps are ``index / rate``: the simulated clock that
    drives Algorithm 1."""

    def __init__(self, queries: list[dict], stream: Stream, rate: float):
        self.queries, self.stream, self.rate = queries, stream, float(rate)
        self.util_max = 1e-9
        self._rep, self._freq = [], []
        head = stream.raw(0, Stream.BLOCK)
        for q in queries:
            _, _, _, pot = _classify_one(q, head)
            if q["query"] == "Q1":
                seq = np.arange(1, q["num_symbols"] + 1)
                rep = np.bincount(seq, minlength=q["num_symbols"] + 1)
                rep = rep.astype(float)
            else:
                rep = np.zeros(3)
                rep[1], rep[2] = q["any_n"], 1.0
            freq = np.bincount(pot, minlength=len(rep)).astype(float)
            freq /= head.n
            u = np.where(pot > 0, rep[pot] / np.maximum(freq[pot], 1e-9), 0)
            self.util_max = max(self.util_max, float(u.max()))
            self._rep.append(rep)
            self._freq.append(freq)

    def columns(self, lo: int, hi: int) -> dict[str, np.ndarray]:
        """The ``EventBatch`` fields of events [lo, hi), as numpy."""
        raw = self.stream.raw(lo, hi)
        m, P = hi - lo, len(self.queries)
        cls = np.zeros((m, P), np.int32)
        bind = np.zeros((m, P), np.int32)
        opens = np.zeros((m, P), bool)
        util = np.zeros(m)
        for p, q in enumerate(self.queries):
            cls[:, p], bind[:, p], opens[:, p], pot = _classify_one(q, raw)
            rep, freq = self._rep[p], self._freq[p]
            util += np.where(pot > 0,
                             rep[pot] / np.maximum(freq[pot], 1e-9), 0.0)
        return dict(
            ev_class=cls, ev_bind=bind, ev_open=opens,
            ev_id=raw.type_id.copy(), ev_rand=self.stream.uniforms(lo, hi),
            ebl_raw=(1.0 - util / self.util_max).astype(np.float32),
            arrival=(np.arange(lo, hi) / self.rate).astype(np.float32))
