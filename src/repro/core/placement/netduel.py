"""NETDUEL — online, λ-unaware dynamic policy (paper §5).

Networked extension of DUEL [12]: each *real* cached object is paired
with a *virtual* competitor (metadata only, drawn from the arrival
process). Over an observation window we accumulate, per duel, the cost
saving each contender produces:

* real object in slot y:    saving_r = C(r, A \\ {y}) − C(r, A)
  (positive only for requests whose best approximizer is y; equals
  best2 − best1 for those requests);
* virtual object v at cache j(y): saving_r = max(0, C(r, A) − C_a(o, v)
  − h(i, j(y))) — the cost reduction v *would* have produced.

At the end of the window the virtual replaces the real iff its
accumulated saving exceeds the real's by a relative margin δ; otherwise
it is discarded and the slot is re-armed with a fresh virtual object
taken later from the arrival stream. The policy needs no knowledge of λ.

Two implementations with a shared bit-exact contract:

* :func:`netduel` — the host NumPy reference. All duel bookkeeping
  (savings, the δ-margin settle test, the armed-slot pick) is done in
  float32 with the *same elementary operations in the same order* as
  the device scan, and every random draw the policy consumes is taken
  up front (``_duel_draws``), so a trajectory is a pure function of
  (requests, draws) that replays bit-identically on the accelerator.
* :func:`device_netduel` — the device-resident rewrite: one jitted
  ``lax.scan`` over the whole request window. The carry is a
  :class:`DeviceDuelState` tuple (slots, best1/arg1/best2 serving
  tables, virtual ids, f32 savings, deadlines, promotion count) living
  entirely on the accelerator; per step the virtual contender is priced
  with the gain machinery of kernels/knn/gains.py
  (``duel_virtual_costs`` — the 1-row special case of the gain oracle's
  C_a tiling) and a promotion re-arms the serving tables via the same
  ``best_two`` kernel the offline control plane uses (mesh-sharded over
  the request axis when the DeviceInstance carries the data-plane
  axes). One launch prices a window of 10³–10⁵ requests; nothing
  returns to the host until the scan ends.

:class:`DuelPlane` packages the scan for the serving engine
(serve/engine.py, ``EngineConfig.netduel``): the duel carry persists
across serve() batches and each batch is observed in one scan launch,
optionally priced by the *same fused-lookup costs the data plane just
computed* (``b1_ext``) so a request is priced once for serving and
dueling.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracecount
from repro.core.objective import DeviceInstance, Instance
from repro.core.placement.localswap import SwapState, emulated_stream

F32_ZERO = np.float32(0.0)


def _duel_draws(rng: np.random.Generator, n: int):
    """All randomness NETDUEL consumes, drawn up front: per-request
    arming coin flips and armed-slot picks. Data-independent draw order
    is what lets the device scan replay the host policy bit-identically
    (the old implementation drew the slot choice lazily from the rng,
    coupling the stream position to the trajectory)."""
    return rng.random(n), rng.random(n)


@dataclasses.dataclass
class DuelState:
    sw: SwapState                       # reuse best1/arg1/best2 bookkeeping
    virt: np.ndarray                    # (K,) virtual object id or −1
    real_sav: np.ndarray                # (K,) f32 accumulated real savings
    virt_sav: np.ndarray                # (K,) f32
    deadline: np.ndarray                # (K,) request-count when duel ends
    n_promotions: int = 0
    served_cost: float = 0.0
    n_served: int = 0
    promotions: list = dataclasses.field(default_factory=list)
    # promotions: (t, slot, new_obj, real_sav, virt_sav) per event


def netduel(inst: Instance, n_iters: int = 200000, seed: int = 0,
            window: int = 2000, delta: float = 0.05, arm_prob: float = 0.25,
            slots0: np.ndarray | None = None,
            requests: tuple[np.ndarray, np.ndarray] | None = None,
            record_every: int = 0) -> DuelState:
    """Run NETDUEL over a request stream; returns final state.

    ``delta`` is the relative winning margin: promote iff
    virt_sav > (1+δ)·real_sav. ``window`` is the duel length in requests.

    Duel arithmetic is float32 end to end (savings accumulation, the
    settle comparison ``virt_sav > f32(1+δ)·real_sav``, the armed-slot
    pick ``⌊f32(u)·f32(n_free)⌋``): each operation mirrors the device
    scan of :func:`device_netduel` one-for-one, which is what the
    differential suite (tests/test_netduel_device.py) pins down.
    """
    rng, slots, objs, ings = emulated_stream(inst, n_iters, seed, slots0,
                                             requests)
    K = slots.shape[0]
    st = DuelState(
        sw=SwapState.init(inst, slots),
        virt=np.full(K, -1, dtype=np.int64),
        real_sav=np.zeros(K, dtype=np.float32),
        virt_sav=np.zeros(K, dtype=np.float32),
        deadline=np.zeros(K, dtype=np.int64))
    arm_draws, slot_draws = _duel_draws(rng, len(objs))

    H, ca = inst.net.H, inst.ca
    slot_cache = inst.slot_cache
    h_slots = H[:, slot_cache]                       # (I, K) f32, +inf off-path
    on_path = np.isfinite(h_slots)                   # (I, K)
    one_delta = np.float32(1.0 + delta)
    for t in range(len(objs)):
        o, i = int(objs[t]), int(ings[t])
        b1 = st.sw.best1[i, o]                       # np.float32 scalar
        a1 = int(st.sw.arg1[i, o])
        st.served_cost += float(b1)
        st.n_served += 1

        # -- real savings: only the best slot saves anything for r
        if a1 >= 0:
            st.real_sav[a1] += st.sw.best2[i, o] - b1

        # -- virtual savings for every armed duel on the path of i
        armed = st.virt >= 0
        vcost = ca[o, np.maximum(st.virt, 0)] + h_slots[i]
        st.virt_sav = np.where(
            armed, st.virt_sav + np.maximum(b1 - vcost, F32_ZERO),
            st.virt_sav)

        # -- settle expired duels
        expired = armed & (st.deadline <= t)
        if expired.any():
            promote = expired & (st.virt_sav > one_delta * st.real_sav) \
                & (st.virt_sav > 0.0)
            if promote.any():
                for y in np.nonzero(promote)[0]:
                    st.promotions.append(
                        (t, int(y), int(st.virt[y]),
                         float(st.real_sav[y]), float(st.virt_sav[y])))
                st.sw.slots[promote] = st.virt[promote]
                st.sw.refresh(inst)
                st.n_promotions += int(promote.sum())
            st.virt[expired] = -1
            st.real_sav[expired] = 0.0
            st.virt_sav[expired] = 0.0

        # -- arm a new duel: pair this request's object with a uniformly
        #    random free slot on the path of i
        if arm_draws[t] < arm_prob:
            free = (st.virt < 0) & on_path[i]
            n_free = int(free.sum())
            if n_free:
                m = min(int(np.float32(slot_draws[t]) * np.float32(n_free)),
                        n_free - 1)
                y = int(np.nonzero(free)[0][m])
                st.virt[y] = o
                st.deadline[y] = t + window
                st.real_sav[y] = st.virt_sav[y] = 0.0

        if record_every and t % record_every == 0:
            st.sw.cost_trace.append(st.sw.cost(inst))
    return st


# ==================================================================== device
@dataclasses.dataclass
class DeviceDuelState:
    """Final state of a device NETDUEL run (host-side mirror of the scan
    carry, plus the traces the scan emitted)."""
    slots: np.ndarray                   # (K,) final allocation
    virt: np.ndarray                    # (K,) armed virtual ids or −1
    real_sav: np.ndarray                # (K,) f32
    virt_sav: np.ndarray                # (K,) f32
    deadline: np.ndarray                # (K,)
    n_promotions: int
    served_cost: float
    n_served: int
    promotions: list                    # (t, slot, new_obj, real, virt)
    b1_trace: np.ndarray                # (T,) f32 per-request served cost
    cost_trace: list


# Static unroll width of the incremental re-arm: a settle step promoting
# more than this many slots at once falls back to the full rebuild.
PROMOTE_CAP = 8


def _duel_carry(dinst: DeviceInstance, slots: np.ndarray):
    """Initial scan carry from a host allocation vector. Carries the
    pre-fold best-two tables (b1p/a1p/b2p/a2p — the witnesses the
    incremental re-arm's dirty-row detection keys on) next to the folded
    serving tables."""
    from repro.core.objective import fold_best_two
    slots_d = jnp.asarray(slots, jnp.int32)
    b1p, a1p, b2p, a2p = dinst.best_two_tables(slots_d)
    b1, a1, b2 = fold_best_two(b1p, a1p, b2p, dinst.h_repo)
    K = slots_d.shape[0]
    return (slots_d, b1p, a1p, b2p, a2p, b1, a1, b2,
            jnp.full((K,), -1, jnp.int32),
            jnp.zeros((K,), jnp.float32),
            jnp.zeros((K,), jnp.float32),
            jnp.zeros((K,), jnp.int32),
            jnp.zeros((), jnp.int32))


@functools.partial(jax.jit, static_argnames=(
    "metric", "gamma", "has_ca", "record_events", "external_b1",
    "record_every", "mesh", "axes", "masked", "incremental"))
def _duel_scan(coords, ca, lam, H, h_repo, slot_cache, h_slots, on_path,
               carry, xs, one_delta, window,
               metric: str, gamma: float, has_ca: bool,
               record_events: bool, external_b1: bool, record_every: int,
               mesh, axes, masked: bool = False, incremental: bool = True):
    """One launch over a request window: lax.scan of the NETDUEL step.

    Per step: price the request against the serving tables (or take the
    externally supplied fused-lookup cost ``b1_ext`` — the engine path,
    where the data plane already priced the batch), accumulate real and
    virtual savings in f32, settle expired duels (a promotion re-arms
    the best1/arg1/best2 tables through ``DeviceInstance.best_two``'s
    kernel under ``lax.cond`` — mesh-sharded over the request axis when
    ``mesh`` is set), and arm a new duel from the precomputed draws.
    Emits the per-step served cost (always), promotion events and
    sub-sampled cost-trace points (statically gated).

    ``masked=True`` appends a per-step validity flag to ``xs`` (the
    bucketed engine path: batches padded to power-of-two buckets so the
    scan compiles once per bucket, not once per batch size). An invalid
    step is a complete no-op — no savings, no settle, no arming, no
    promotion count, zero emitted cost — so the carry after a padded
    window is bit-identical to the carry after the unpadded one.
    """
    from repro.core.objective import (_best_two_delta_jit,
                                      _fold_repo_rows, best_two_tables,
                                      default_delta_cap)
    from repro.kernels.knn.gains import duel_virtual_costs

    tracecount.bump("duel_scan")

    K = int(slot_cache.shape[0])
    n_obj = int(lam.shape[1])
    cap = min(default_delta_cap(n_obj), n_obj)

    def full_tables(slots):
        return best_two_tables(coords, ca, slots, slot_cache, H,
                               metric, gamma, has_ca, mesh, axes)

    def rearm(slots_new, promote, pre):
        """Pre-fold + folded tables after a settle wrote ``promote``."""
        if incremental:
            ys = jnp.nonzero(promote, size=PROMOTE_CAP,
                             fill_value=K)[0].astype(jnp.int32)
            n_p = jnp.sum(promote, dtype=jnp.int32)
            npre = jax.lax.cond(
                n_p > PROMOTE_CAP,
                lambda _: full_tables(slots_new),
                lambda _: _best_two_delta_jit(
                    coords, ca, *pre, slots_new, ys, slot_cache, H,
                    metric=metric, gamma=gamma, has_ca=has_ca, cap=cap,
                    n_slots=K, mesh=mesh, axes=axes),
                None)
        else:
            npre = full_tables(slots_new)
        return (*npre, *_fold_repo_rows(npre[0], npre[1], npre[2], h_repo))

    def step(c, x):
        (slots, b1p, a1p, b2p, a2p, best1, arg1, best2,
         virt, rs, vs, deadline, n_prom) = c
        if masked:
            *x, valid = x
        else:
            valid = jnp.bool_(True)
        if external_b1:
            o, i, t, armf, slotu, b1 = x
        else:
            o, i, t, armf, slotu = x
            b1 = best1[i, o]
        a1 = arg1[i, o]

        # real saving — scatter to the winning slot (no-op for repo hits)
        rs = rs.at[jnp.maximum(a1, 0)].add(
            jnp.where(valid & (a1 >= 0), best2[i, o] - b1, jnp.float32(0)))

        # virtual savings — the gain-machinery pricing tile
        armed = virt >= 0
        vcost = duel_virtual_costs(coords, ca, o, jnp.maximum(virt, 0),
                                   h_slots[i], metric, gamma, has_ca)
        vs = jnp.where(valid & armed,
                       vs + jnp.maximum(b1 - vcost, jnp.float32(0)), vs)

        # settle expired duels
        expired = valid & armed & (deadline <= t)
        promote = expired & (vs > one_delta * rs) & (vs > 0.0)
        any_p = jnp.any(promote)
        slots = jnp.where(promote, virt, slots)
        b1p, a1p, b2p, a2p, best1, arg1, best2 = jax.lax.cond(
            any_p, lambda _: rearm(slots, promote, (b1p, a1p, b2p, a2p)),
            lambda _: (b1p, a1p, b2p, a2p, best1, arg1, best2), None)
        n_prom = n_prom + jnp.sum(promote, dtype=jnp.int32)
        ev = (promote, virt, rs, vs) if record_events else ()
        virt = jnp.where(expired, -1, virt)
        rs = jnp.where(expired, jnp.float32(0), rs)
        vs = jnp.where(expired, jnp.float32(0), vs)

        # arm a new duel on a uniformly random free on-path slot
        free = (virt < 0) & on_path[i]
        n_free = jnp.sum(free, dtype=jnp.int32)
        arm = valid & armf & (n_free > 0)
        m = jnp.minimum((slotu * n_free.astype(jnp.float32))
                        .astype(jnp.int32), n_free - 1)
        y_arm = (jnp.cumsum(free) - 1 == m) & free & arm
        virt = jnp.where(y_arm, o, virt)
        deadline = jnp.where(y_arm, t + window, deadline)
        rs = jnp.where(y_arm, jnp.float32(0), rs)
        vs = jnp.where(y_arm, jnp.float32(0), vs)

        out = (jnp.where(valid, b1, jnp.float32(0)),)
        if record_every:
            out += (jax.lax.cond(
                t % record_every == 0,
                lambda b: jnp.sum(lam * b), lambda b: jnp.float32(-1.0),
                best1),)
        if record_events:
            out += ev
        return (slots, b1p, a1p, b2p, a2p, best1, arg1, best2,
                virt, rs, vs, deadline, n_prom), out

    return jax.lax.scan(step, carry, xs)


def _duel_xs(objs, ings, t0, arm_flags, slot_draws, b1_ext=None,
             valid=None):
    """Scan inputs. ``valid`` (bool mask) appends the bucketing validity
    flag; invalid rows reuse the last valid row's ``t`` so the duel
    timeline only advances with real requests (deadlines are measured in
    served requests, not in padded scan steps)."""
    n = len(objs)
    if valid is None:
        ts = np.arange(t0, t0 + n, dtype=np.int32)
    else:
        valid = np.asarray(valid, bool)
        ts = (t0 + np.maximum(np.cumsum(valid) - 1, 0)).astype(np.int32)
    xs = (jnp.asarray(objs, jnp.int32), jnp.asarray(ings, jnp.int32),
          jnp.asarray(ts),
          jnp.asarray(arm_flags), jnp.asarray(slot_draws, jnp.float32))
    if b1_ext is not None:
        xs += (jnp.asarray(b1_ext, jnp.float32),)
    if valid is not None:
        xs += (jnp.asarray(valid),)
    return xs


def _scan_args(dinst: DeviceInstance):
    ca = dinst.ca if dinst.ca is not None else jnp.zeros((0, 0), jnp.float32)
    h_slots = dinst.H[:, dinst.slot_cache]
    on_path = jnp.isfinite(h_slots)
    mesh = dinst.mesh if dinst.n_shards > 1 else None
    axes = dinst.axes if dinst.n_shards > 1 else ()
    return ca, h_slots, on_path, mesh, axes


def _events_from_trace(promote, virt, rs, vs, t0=0):
    """Host-side unpack of the recorded settle tensors into the same
    (t, slot, new_obj, real_sav, virt_sav) event list the host policy
    appends (slots in ascending order within a step)."""
    events = []
    for t in np.nonzero(promote.any(axis=1))[0]:
        for y in np.nonzero(promote[t])[0]:
            events.append((int(t) + t0, int(y), int(virt[t, y]),
                           float(rs[t, y]), float(vs[t, y])))
    return events


def device_netduel(dinst: DeviceInstance, n_iters: int = 200000,
                   seed: int = 0, window: int = 2000, delta: float = 0.05,
                   arm_prob: float = 0.25,
                   slots0: np.ndarray | None = None,
                   requests: tuple[np.ndarray, np.ndarray] | None = None,
                   record_every: int = 0,
                   record_events: bool = False,
                   incremental: bool = True) -> DeviceDuelState:
    """NETDUEL as one device launch: identical rng consumption to
    :func:`netduel` (same seed → same start slots, requests and draws)
    and bit-identical duel decisions on materialized-C_a instances
    (the f32 op-for-op contract of the module docstring).

    ``record_events=True`` additionally stacks the per-step settle
    state (promote mask, virtual ids, both savings — four (T, K)
    tensors) so the promotion-event list can be reconstructed; that is
    what the differential suite compares, but it costs ~13·T·K bytes of
    device memory, so it is opt-in (off, a run emits only the (T,)
    served-cost trace)."""
    rng, slots, objs, ings = emulated_stream(dinst.host, n_iters, seed,
                                             slots0, requests)
    arm_draws, slot_draws = _duel_draws(rng, len(objs))
    arm_flags = arm_draws < arm_prob                 # exact f64 compare

    ca, h_slots, on_path, mesh, axes = _scan_args(dinst)
    carry = _duel_carry(dinst, slots)
    xs = _duel_xs(objs, ings, 0, arm_flags, slot_draws)
    carry, out = _duel_scan(
        dinst.coords, ca, dinst.lam, dinst.H, dinst.h_repo,
        dinst.slot_cache, h_slots, on_path, carry, xs,
        jnp.float32(1.0 + delta), jnp.int32(window),
        dinst.metric, dinst.gamma, dinst.ca is not None,
        record_events, False, record_every, mesh, axes,
        incremental=incremental)

    b1_trace = np.asarray(out[0])
    cost_trace = []
    k = 1
    if record_every:
        costs = np.asarray(out[k]); k += 1
        cost_trace = [float(c) for t, c in enumerate(costs)
                      if t % record_every == 0]
    events = []
    if record_events:
        events = _events_from_trace(*(np.asarray(o) for o in out[k:k + 4]))
    (slots_d, _, _, _, _, _, _, _, virt, rs, vs, deadline, n_prom) = carry
    # cumsum accumulates sequentially in f64 — bit-identical to the
    # host's per-step ``served_cost += float(b1)``
    served = float(np.cumsum(b1_trace, dtype=np.float64)[-1]) \
        if b1_trace.size else 0.0
    return DeviceDuelState(
        slots=np.asarray(slots_d).astype(np.int64),
        virt=np.asarray(virt).astype(np.int64),
        real_sav=np.asarray(rs), virt_sav=np.asarray(vs),
        deadline=np.asarray(deadline).astype(np.int64),
        n_promotions=int(n_prom), served_cost=served,
        n_served=len(b1_trace), promotions=events, b1_trace=b1_trace,
        cost_trace=cost_trace)


class DuelPlane:
    """Persistent online control plane for the serving engine (§5 run
    *inside* the data plane): holds the duel carry on device across
    serve() batches, observing each batch in one scan launch.

    ``observe(objs, b1_ext=...)`` takes the batch's request object ids
    and (optionally) the costs the fused lookup already computed for
    them — the request is then priced once for serving and dueling.
    Returns True iff at least one promotion settled in the batch, i.e.
    the placement changed and the data-plane cache must be rebuilt.

    ``n_valid`` marks a *bucketed* batch (serve/engine.py): only the
    first ``n_valid`` rows are real requests, the tail is power-of-two
    padding. Randomness is drawn for the valid prefix only and the scan
    masks the padded steps into no-ops, so the duel trajectory is
    bit-identical to observing the unpadded batch — while the scan
    compiles once per bucket size instead of once per batch size.
    """

    def __init__(self, dinst: DeviceInstance, slots0: np.ndarray,
                 window: int = 512, delta: float = 0.05,
                 arm_prob: float = 0.25, seed: int = 0,
                 incremental: bool = True):
        self.dinst = dinst
        self.incremental = bool(incremental)
        self.window = int(window)
        self.one_delta = jnp.float32(1.0 + delta)
        self.arm_prob = float(arm_prob)
        self.rng = np.random.default_rng(seed)
        self.carry = _duel_carry(dinst, np.asarray(slots0))
        self.t = 0
        self.n_promotions = 0
        self.served_cost = 0.0
        self._args = _scan_args(dinst)

    @tracecount.spanned("DuelPlane.observe")
    def observe(self, objs: np.ndarray, ings: np.ndarray | None = None,
                b1_ext: np.ndarray | None = None,
                n_valid: int | None = None) -> bool:
        objs = np.asarray(objs)
        if ings is None:
            ings = np.zeros(objs.shape[0], np.int64)
        # masked whenever the caller buckets, even with zero padding rows:
        # one compiled scan per bucket size, not two (padded + exact-fit)
        masked = n_valid is not None
        n_real = objs.shape[0] if n_valid is None else int(n_valid)
        # draw only for real requests: the rng stream position after a
        # bucketed observe equals the unpadded one (bit-identical replay)
        arm_flags = np.zeros(objs.shape[0], bool)
        slot_draws = np.zeros(objs.shape[0], np.float64)
        arm_flags[:n_real] = self.rng.random(n_real) < self.arm_prob
        slot_draws[:n_real] = self.rng.random(n_real)
        valid = None
        if masked:
            valid = np.zeros(objs.shape[0], bool)
            valid[:n_real] = True
        ca, h_slots, on_path, mesh, axes = self._args
        xs = _duel_xs(objs, ings, self.t, arm_flags, slot_draws,
                      b1_ext=b1_ext, valid=valid)
        d = self.dinst
        self.carry, out = _duel_scan(
            d.coords, ca, d.lam, d.H, d.h_repo, d.slot_cache, h_slots,
            on_path, self.carry, xs, self.one_delta,
            jnp.int32(self.window), d.metric, d.gamma, d.ca is not None,
            False, b1_ext is not None, 0, mesh, axes, masked=masked,
            incremental=self.incremental)
        self.t += n_real
        self.served_cost += float(np.asarray(out[0], np.float64).sum())
        n_prom = int(self.carry[12])
        changed = n_prom > self.n_promotions
        self.n_promotions = n_prom
        return changed

    @property
    def slots_np(self) -> np.ndarray:
        return np.asarray(self.carry[0]).astype(np.int64)
