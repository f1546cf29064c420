"""The client side of the measured window: offers the schedule to
``SimCacheEngine.serve`` and records when each batch left and returned.

Two ways to offer load, chosen by the mix file's ``arrival``:

- ``saturate``: batches of ``batch`` requests back to back, so every
  batch leaves full and the engine's capacity is what is measured; each
  batch's requests are drawn just before it is sent, which the window's
  time includes (well under 1 % of a batch);
- ``poisson``: open loop. Requests fall due on the schedule whether or
  not the engine keeps up; whenever the engine is free the batcher sends
  the largest power of two of the requests due, up to ``batch``, oldest
  first. Power-of-two batches are the buckets the engine compiles, so no
  batch is padded and no new shape appears in the window.

A request's latency runs from when it was due until ``serve`` returned
its batch, so a stall counts against every request waiting behind it.
Requests still queued when the window closes are served afterwards and
keep their latency; ``drain_s`` bounds that wait, and any request left
after it has failed.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import traffic


@dataclasses.dataclass
class Batch:
    lo: int                    # first request (index into the schedule)
    hi: int
    sent: float                # seconds since the window opened
    returned: float
    out: list                  # what serve returned, one entry a request

    @property
    def n(self) -> int:
        return self.hi - self.lo


@dataclasses.dataclass
class Window:
    seconds: float
    batches: list
    offered: int               # requests the window offered
    due: np.ndarray | None     # open loop: due time of each request

    def served(self) -> np.ndarray:
        """Indices of the requests that were served, in order."""
        return np.concatenate([np.arange(b.lo, b.hi) for b in self.batches]
                              or [np.zeros(0, np.int64)])

    def returned(self) -> np.ndarray:
        return np.concatenate([np.full(b.n, b.returned)
                               for b in self.batches] or [np.zeros(0)])

    def sent(self) -> np.ndarray:
        return np.concatenate([np.full(b.n, b.sent)
                               for b in self.batches] or [np.zeros(0)])

    @property
    def failed(self) -> int:
        return self.offered - sum(b.n for b in self.batches)


class Recorder:
    """Keeps what the timed path returns while attached: every result of
    the engine's lookup, in call order (one per served batch), and the
    logits of the miss prefill on the calls whose index, counted from 0,
    is in ``prefill_calls``. Both pass through untouched."""

    def __init__(self, eng, prefill_calls):
        self.lookups: list = []
        self.logits: dict = {}
        self._calls = set(int(c) for c in prefill_calls)
        self._count = 0
        net, lookup, prefill = eng.simcache, eng.simcache.lookup, eng.prefill

        def recorded_lookup(*args, **kwargs):
            res = lookup(*args, **kwargs)
            self.lookups.append(res)
            return res

        def recorded_prefill(tokens):
            out = prefill(tokens)
            if self._count in self._calls:
                self.logits[self._count] = out
            self._count += 1
            return out

        net.lookup, eng.prefill = recorded_lookup, recorded_prefill
        self._targets = (net, eng)

    def detach(self) -> None:
        net, eng = self._targets
        del net.lookup, eng.prefill


def _serve(eng, sched: traffic.Schedule, lo: int, hi: int, span):
    objects, tokens = sched.take(lo, hi)
    with span("bench.serve"):
        out, _ = eng.serve(objects, tokens)
    return out


def saturate(eng, sched: traffic.Schedule, seconds: float, span,
             tick=None, clock=time.perf_counter) -> Window:
    batches, b = [], sched.batch
    t0 = clock()
    lo = 0
    while clock() - t0 < seconds:
        if tick is not None:
            tick(clock() - t0)
        sent = clock() - t0
        out = _serve(eng, sched, lo, lo + b, span)
        batches.append(Batch(lo, lo + b, sent, clock() - t0, out))
        lo += b
    return Window(seconds=seconds, batches=batches, offered=lo, due=None)


def open_loop(eng, sched: traffic.Schedule, seconds: float, span,
              drain_s: float, tick=None, clock=time.perf_counter,
              sleep=time.sleep) -> Window:
    due, cap = sched.due, sched.batch
    batches, lo = [], 0
    t0 = clock()
    while lo < len(due):
        now = clock() - t0
        if now > seconds + drain_s:
            break
        ready = int(np.searchsorted(due, now, side="right")) - lo
        if ready <= 0:
            wait = due[lo] - now
            if wait > 2e-3:
                with span("bench.idle"):
                    sleep(wait - 1e-3)
            continue
        n = min(cap, 1 << (ready.bit_length() - 1))
        if tick is not None:
            tick(now)
        out = _serve(eng, sched, lo, lo + n, span)
        batches.append(Batch(lo, lo + n, now, clock() - t0, out))
        lo += n
    return Window(seconds=seconds, batches=batches, offered=len(due),
                  due=due)


def stall_summary(win: Window) -> str:
    """Median, 99th percentile and longest wall time of a batch in
    ``serve``, and the batches that took over twice the median with the
    time they lost against it: a slow run that loses its time in a few
    stalls reads apart from one slower on every batch."""
    if not win.batches:
        return "no batches"
    t = np.array([b.returned - b.sent for b in win.batches]) * 1e3
    med = float(np.median(t))
    slow = t[t > 2 * med]
    return (f"median {med:.3f} ms, p99 {np.percentile(t, 99):.3f} ms, "
            f"max {t.max():.3f} ms; {len(slow)} over twice the median, "
            f"losing {float(np.sum(slow - med)) / 1e3:.3f} s")


def end_to_end(win: Window) -> dict:
    """The cell's end-to-end numbers over every request of the window.

    ``req_per_s`` is the requests served over the seconds from the
    window's open until the last of them returned: all the work and all
    the time, with no batch cut in two at the close. Latency percentiles
    (open loop) are over every request offered; one that never returned
    counts as slower than every other."""
    returned = win.returned()
    out = {"req_per_s": len(returned) / float(returned.max())
           if len(returned) else 0.0}
    if win.due is not None:
        lat = (returned - win.due[win.served()]) * 1e3
        lat = np.concatenate([lat, np.full(win.failed, np.inf)])
        out["p50_ms"] = float(np.percentile(lat, 50, method="higher"))
        out["p95_ms"] = float(np.percentile(lat, 95, method="higher"))
    return out
