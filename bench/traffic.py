"""Seeded catalog and request generation: one general generator that
every traffic mix file parameterises.

The arithmetic follows the program's own generators but is kept here, so
that a change under ``src/`` cannot move the yardstick:

- the catalog is ``repro.core.catalog.embedding_catalog``: directions
  uniform on the sphere, radii Gamma(2, 120), so typical distances
  between objects are O(100);
- popularity is ``repro.core.demand.zipf``: weight rank**-alpha over a
  seeded permutation of ranks, so rank is independent of geometry;
- open-loop arrivals are the Poisson process of ``serve.stream.StreamSpec``:
  independent exponential gaps.

The window's requests are independent draws from the popularity law, in
blocks of ``batch``: block k is drawn from its own stream of the seed,
so it is the same however many blocks came before it, and a mix offered
above capacity draws blocks as the window asks for them and never runs
out. Only the demand history the set-up placement is solved from is
stratified: each block of ``batch`` takes one request from each
1/``batch`` quantile of the popularity CDF (Latin-hypercube sampling),
so the placement, and with it the hit rate, moves little from seed to
seed. Its length is the same for every cell.

A request's prompt is a fixed function of the seed and its object id:
an object stands for one query text, so a repeated request repeats its
prompt.
"""
from __future__ import annotations

import zlib

import numpy as np

SEED_MASK = (1 << 64) - 1      # SeedSequence takes non-negative ints
HISTORY_REQUESTS = 1 << 20     # the demand history the placement sees


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per named stream of one run's seed."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) & SEED_MASK,
                                zlib.crc32(stream.encode())]))


def catalog(n: int, dim: int, seed: int) -> np.ndarray:
    """(n, dim) float32 embeddings: uniform directions, Gamma(2, 120)
    radii."""
    g = rng(seed, "catalog")
    coords = g.standard_normal((n, dim), dtype=np.float32)
    coords /= np.linalg.norm(coords, axis=1, keepdims=True)
    coords *= g.gamma(2.0, 120.0, size=n).astype(np.float32)[:, None]
    return coords


def zipf_cdf(n: int, alpha: float, seed: int) -> np.ndarray:
    """Cumulative request probability over object ids, Zipf(alpha) on a
    seeded rank permutation."""
    ranks = rng(seed, "popularity").permutation(n) + 1
    cdf = np.cumsum(ranks.astype(np.float64) ** -alpha)
    return cdf / cdf[-1]


def draw_objects(cdf: np.ndarray, n: int, stratum: int,
                 g: np.random.Generator) -> np.ndarray:
    """n object ids; each block of ``stratum`` draws one id from each
    1/stratum quantile of ``cdf``, in a random order."""
    blocks = -(-n // stratum)
    u = (np.arange(stratum)[None, :] + g.random((blocks, stratum))) / stratum
    u = g.permuted(u, axis=1).ravel()[:n]
    return _ids(cdf, u)


def iid_objects(cdf: np.ndarray, n: int,
                g: np.random.Generator) -> np.ndarray:
    """n object ids drawn independently by ``cdf``."""
    return _ids(cdf, g.random(n))


def _ids(cdf: np.ndarray, u: np.ndarray) -> np.ndarray:
    # searched in ascending order (several times faster over a large
    # cdf), returned in the order drawn
    order = np.argsort(u)
    ids = np.empty(len(u), np.int64)
    ids[order] = cdf.searchsorted(u[order], side="right")
    return np.minimum(ids, len(cdf) - 1, out=ids)


def prompts(objects: np.ndarray, length: int, vocab: int,
            seed: int) -> np.ndarray:
    """(n, length) int32 tokens in [0, vocab): a splitmix64 hash of
    (seed, object id, position)."""
    with np.errstate(over="ignore"):
        x = objects.astype(np.uint64)[:, None] * np.uint64(length)
        x = x + np.arange(length, dtype=np.uint64)[None, :]
        x += np.uint64((int(seed) * 0x9E3779B97F4A7C15) & SEED_MASK)
        for shift, mul in ((30, 0xBF58476D1CE4E5B9),
                           (27, 0x94D049BB133111EB)):
            x ^= x >> np.uint64(shift)
            x *= np.uint64(mul)
        x ^= x >> np.uint64(31)
    return (x % np.uint64(vocab)).astype(np.int32)


def arrivals(rate: float, seconds: float,
             g: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds) of an open-loop Poisson stream at
    ``rate`` per second: cumulative independent exponential gaps."""
    parts, t = [], 0.0
    chunk = int(rate * seconds + 8 * np.sqrt(rate * seconds)) + 16
    while t < seconds:
        part = t + np.cumsum(g.exponential(1.0 / rate, size=chunk))
        parts.append(part)
        t = float(part[-1])
    due = np.concatenate(parts)
    return due[due < seconds]


class Schedule:
    """What the window offers: request i is in block i // ``batch``,
    whose object ids and prompts are drawn when first asked for. ``due``
    is, for an open-loop mix, the second at which each request falls due
    (None for a mix offered above capacity, whose batches leave full)."""

    def __init__(self, cdf: np.ndarray, prompt_len: int, vocab: int,
                 seed: int, batch: int, due: np.ndarray | None = None):
        self.cdf, self.prompt_len, self.vocab = cdf, prompt_len, vocab
        self.seed, self.batch, self.due = seed, batch, due
        self._objects: list = []
        self._tokens: list = []
        self._flat = (0, np.zeros(0, np.int64),
                      np.zeros((0, prompt_len), np.int32))

    def draw(self, n: int) -> None:
        """Draw the blocks that hold the first ``n`` requests."""
        while len(self._objects) * self.batch < n:
            g = rng(self.seed, f"window.{len(self._objects)}")
            objs = iid_objects(self.cdf, self.batch, g)
            self._objects.append(objs)
            self._tokens.append(prompts(objs, self.prompt_len, self.vocab,
                                        self.seed))

    def take(self, lo: int, hi: int) -> tuple[np.ndarray, np.ndarray]:
        """(object ids, prompts) of requests lo to hi."""
        self.draw(hi)
        k, r = divmod(lo, self.batch)
        if r == 0 and hi - lo == self.batch:
            return self._objects[k], self._tokens[k]
        return self.objects[lo:hi], self.tokens[lo:hi]

    def _joined(self):
        if self._flat[0] != len(self._objects):
            self._flat = (len(self._objects), np.concatenate(self._objects),
                          np.concatenate(self._tokens))
        return self._flat

    @property
    def objects(self) -> np.ndarray:
        """Object ids of every request drawn so far."""
        return self._joined()[1]

    @property
    def tokens(self) -> np.ndarray:
        return self._joined()[2]


def history(cdf: np.ndarray, batch: int, seed: int) -> np.ndarray:
    """Object ids of the demand history the set-up placement is solved
    from, stratified in blocks of the cell's batch, with the run's
    seed."""
    return draw_objects(cdf, HISTORY_REQUESTS, batch, rng(seed, "history"))


def schedule(mix: dict, cdf: np.ndarray, vocab: int, seed: int,
             seconds: float) -> Schedule:
    """The window's requests. ``arrival`` is ``saturate`` (batches of
    ``batch`` back to back, drawn as the window asks for them) or
    ``poisson`` (open loop at ``rate`` per second, every request due in
    the window drawn here)."""
    batch, due = int(mix["batch"]), None
    if mix["arrival"] == "poisson":
        due = arrivals(float(mix["rate"]), seconds, rng(seed, "arrivals"))
    elif mix["arrival"] != "saturate":
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    sched = Schedule(cdf, int(mix["prompt_len"]), vocab, seed, batch, due)
    if due is not None:
        sched.draw(len(due))
        sched._joined()            # here, not at the window's first batch
    return sched
