"""The comparison that decides ``correct``: what the timed path served,
against the plain references, once the window has closed.

Three layers, each by the numbers in a cell's limits file:

- the fused lookup. A seeded sample of the window's requests, taken
  from the results the engine's own lookup returned in the window, is
  scanned again by the float64 reference over the installed allocation.
  ``lookup_mismatch`` counts the requests whose served (level, slot,
  payload) is not the reference's best, or is off it by more than the
  float32 error bound of the two costs (a near-tie). ``cost_err`` is the
  widest gap between a served cost and the reference's least cost.
- the repository. The logits of a seeded choice of the window's miss
  prefills are kept as the engine returns them, and those misses' prompts
  are run through the float32 reference model. ``logit_gap`` is the
  widest gap by which the token the engine served lies below the
  reference's best logit (a token outside the published vocabulary reads
  infinity); ``logit_err`` the widest gap between the engine's logits
  and the reference's.
- the allocation the lookup serves from, which the reference takes from
  the engine: ``allocation_errors`` counts slots that hold no catalog
  object or levels whose size is not the configuration's.
- the accounting. ``ServeStats`` over the window against the per-request
  results: ``requests_diff`` and ``hits_diff`` (counts, exact) and
  ``cost_sum_rel`` (the relative gap between the counted total cost and
  the sum of the served costs). ``unanswered`` counts misses that got no
  response.

A hit returns the response stored for its payload, which exists only if
that object once missed (the payload store fills on misses), so a hit's
payload id is what is compared, not its response.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import traffic
from reference import lookup as lookup_ref

LOOKUPS = 256                  # sampled requests the lookup check scans
MISSES = 256                   # sampled misses the model check runs


@dataclasses.dataclass
class Served:
    """Per-request results of the window, in served order."""
    index: np.ndarray          # schedule index of each served request
    level: np.ndarray
    slot: np.ndarray
    payload: np.ndarray
    cost: np.ndarray           # float32, as served
    hit: np.ndarray
    token: np.ndarray          # served token of a miss, −1 for a hit


def gather(win, results, sample: np.ndarray) -> Served:
    """Host copies of what the window's lookups returned. ``results`` is
    the recorded list, one per batch; level and slot are fetched only
    for the batches that hold a sampled request."""
    if len(results) != len(win.batches):
        raise RuntimeError(f"{len(results)} lookups for "
                           f"{len(win.batches)} served batches")
    want = np.zeros(len(win.batches), bool)
    starts = np.cumsum([0] + [b.n for b in win.batches])[:-1]
    if len(sample):
        want[np.unique(np.searchsorted(starts, sample, side="right") - 1)] \
            = True
    cols = {k: [] for k in ("level", "slot", "payload", "cost", "hit")}
    missing = {"level": -3, "slot": -3, "payload": -3, "cost": np.nan,
               "hit": False}
    tokens = []
    for b, res, w in zip(win.batches, results, want):
        n = b.n
        for k in cols:
            if k in ("level", "slot") and not w:
                cols[k].append(np.full(n, -2, np.int32))
                continue
            # a lookup that answered fewer rows than the batch sent
            # leaves the rest unanswered
            a = np.asarray(getattr(res, k))[:n]
            cols[k].append(np.concatenate(
                [a, np.full(n - len(a), missing[k], a.dtype)]))
        tokens.append(np.array(
            [-1 if o is None else int(np.asarray(o).ravel()[0])
             for o in b.out], np.int64))
    cat = {k: np.concatenate(v) if v else np.zeros(0)
           for k, v in cols.items()}
    return Served(index=win.served(), token=np.concatenate(tokens)
                  if tokens else np.zeros(0, np.int64), **cat)


def sample(n: int, k: int, seed: int, stream: str) -> np.ndarray:
    """A seeded sample of ``k`` of ``n`` positions, sorted."""
    if n == 0:
        return np.zeros(0, np.int64)
    g = traffic.rng(seed, stream)
    return np.sort(g.choice(n, size=min(k, n), replace=False))


def lookup_numbers(served: Served, rows: np.ndarray, queries: np.ndarray,
                   keys: lookup_ref.Keys) -> dict:
    """``lookup_mismatch`` and ``cost_err`` over the sampled rows."""
    best, best_cost = lookup_ref.scan_f64(queries, keys)
    lv, sl, pay = served.level[rows], served.slot[rows], served.payload[rows]
    repo = lv < 0
    lvc = np.clip(lv, 0, len(keys.offsets) - 1)
    sizes = np.diff(np.append(keys.offsets, keys.n))
    valid = repo | ((lv < len(keys.offsets)) & (sl >= 0)
                    & (sl < sizes[lvc]))
    cand = np.where(repo | ~valid, keys.n, keys.offsets[lvc] + sl)
    want_pay = np.where(repo, -1, keys.payload[np.minimum(cand,
                                                          keys.n - 1)])
    c_cost, c_err = lookup_ref.pair(queries, keys, cand)
    _, b_err = lookup_ref.pair(queries, keys, best)
    ok = valid & (pay == want_pay) & (c_cost - best_cost <= c_err + b_err)
    gap = np.abs(served.cost[rows].astype(np.float64) - best_cost)
    return {"lookup_mismatch": int((~ok).sum()),
            "cost_err": float(np.max(np.where(np.isnan(gap), np.inf, gap)))
            if len(rows) else 0.0}


def logit_gap(tokens: np.ndarray, logits: np.ndarray) -> float:
    """Widest gap of a served token's reference logit below the best."""
    logits = np.asarray(logits, np.float64)
    vocab = logits.shape[1]
    inside = (tokens >= 0) & (tokens < vocab)
    got = np.take_along_axis(logits, np.where(inside, tokens, 0)[:, None],
                             1)[:, 0]
    gap = np.where(inside, logits.max(1) - got, np.inf)
    return float(gap.max()) if len(gap) else 0.0


def recorded_misses(win, served: Served, recorded: dict):
    """(positions in served order, program logits) of the misses whose
    prefill logits were recorded. The k-th prefill call of the window
    belongs to the k-th batch with a miss, and its rows follow the order
    of that batch's misses."""
    idx, rows = [], []
    call, pos = 0, 0
    for b in win.batches:
        miss = np.nonzero(~served.hit[pos:pos + b.n])[0]
        if len(miss):
            if call in recorded:
                lg = np.asarray(recorded[call], np.float32)[:len(miss)]
                pad = np.full((len(miss) - len(lg), lg.shape[1]), np.nan,
                              np.float32)
                idx.append(pos + miss)
                rows.append(np.concatenate([lg, pad]))
            call += 1
        pos += b.n
    if not idx:
        return np.zeros(0, np.int64), np.zeros((0, 1), np.float32)
    return np.concatenate(idx), np.concatenate(rows)


def logit_err(program: np.ndarray, reference: np.ndarray) -> float:
    """Widest gap between the program's logits over the published
    vocabulary and the reference's."""
    v = reference.shape[1]
    if program.shape[1] < v:
        return float("inf")
    gap = np.abs(program[:, :v].astype(np.float64) - reference)
    return float(np.max(np.where(np.isnan(gap), np.inf, gap)))


def accounting(served: Served, stats_delta: dict, win) -> dict:
    n = len(served.index)
    hits = int(served.hit.sum())
    counted = 0.0
    for b, c in zip(win.batches, np.split(served.cost,
                                          np.cumsum([b.n for b in
                                                     win.batches])[:-1])):
        counted += float(np.sum(c))
    total = stats_delta["total_cost"]
    misses = ~served.hit
    return {
        "requests_diff": abs(stats_delta["n_requests"] - n),
        "hits_diff": abs(stats_delta["n_hits"] - hits),
        "cost_sum_rel": abs(total - counted) / max(abs(counted), 1e-30),
        "unanswered": int((served.token[misses] < 0).sum()),
    }


def allocation_errors(slots: np.ndarray, slot_cache: np.ndarray,
                      levels, n_objects: int) -> int:
    """Slots of the installed allocation that the configuration does not
    allow: an object id outside the catalog, or a level holding another
    number of slots than the configuration gives it."""
    bad = int(np.sum((slots < 0) | (slots >= n_objects)))
    sizes = np.bincount(slot_cache, minlength=len(levels))
    bad += int(np.abs(sizes[:len(levels)] - np.asarray(levels)).sum())
    return bad + int(sizes[len(levels):].sum())


def verdict(numbers: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [[name, value, limit], ...]) — every limit must hold,
    and a number that is missing or not finite fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok &= bool(good)
        rows.append([name, v if v is None or np.isfinite(v) else str(v),
                     limit])
    return ok, rows
