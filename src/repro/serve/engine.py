"""Serving engine with a similarity-cache front tier (the paper's system,
deployed): batched requests are embedded, looked up in the cache network,
and only misses run the model (the "repository"); responses are inserted
back according to the configured placement policy.

Hierarchy (DESIGN.md §2): level 0 = device-local shard (h=0), level 1 =
pod (ICI), level 2 = cross-pod (DCN); repository = the model itself. On
this container the levels are simulated with calibrated h costs. With
``EngineConfig.fused`` (default) a batch lookup is one fused
segmented-KNN pallas_call over all levels at once — jitted once per
placement, no per-level kernel launches or retraces. With
``EngineConfig.sharded`` and an engine ``mesh``, the segmented key
tensor is partitioned across the mesh axes picked by
``LookupShardPolicy`` and each device scans only its resident shard
(one fused kernel per shard + a tiny cross-shard reduction,
bit-identical results) — the catalog then scales with the mesh instead
of a single device's memory. ``EngineConfig.prune`` ("lsh" | "kmeans")
puts the candidate pre-filter of kernels/knn/lsh.py in front of the
scan (per shard when sharded) for catalogs ≫ 10⁵ keys;
``EngineConfig.verify`` keeps the exact scan as the verifier of last
resort, re-scanning any query past the pruning bound.

Batch bucketing (``EngineConfig.bucket``, default on): every served
batch is padded up to a power-of-two bucket (≥ ``min_bucket``) before
touching a jitted entry point — the fused lookup, the duel scan
(``DuelPlane.observe(n_valid=…)``), and the miss-prefill each compile
once per *bucket*, not once per distinct batch size. The miss prefill
pads less: its sub-batch runs as power-of-two pieces no smaller than
``PIECE_TOKENS`` tokens (``prefill_pieces``), each a bucket shape, so
~44 misses run as 32 + 16 rows rather than as 64. Padding rows are
masked everywhere: they never enter ``counts``, ``ServeStats``, the
duel trajectory (bit-identical to the unpadded one — the masked-scan
contract of core/placement/netduel.py), or the responses returned.
Without bucketing a mixed-batch-size request stream pays one XLA
compile per new size per entry point — the retrace pathology the
streaming driver (serve/stream.py) and benchmarks/serving_bench.py
quantify.

Double-buffered placement: the active data plane lives in a versioned
:class:`PlacementBuffer` (simcache + the allocation it serves).
``refresh_placement`` stays the synchronous path (solve, install, swap
— one call); the streaming path splits it: ``request_refresh`` snapshots
the observed demand and solves GREEDY/LOCALSWAP on the device control
plane *in a background thread while the old placement keeps serving*,
and ``poll_refresh`` installs a finished solve with one atomic swap
(rebuild the runtime network host-side, re-arm the duel plane, bump
``PlacementBuffer.version``). The swap — never the solve — is the only
serving-thread stall, timed into ``swap_stall_s``/``max_swap_stall_s``;
``refresh_in_flight`` and the version counter make the whole cycle
observable and race-free (the worker only writes the pending result
under a lock; the serving thread swaps it in between batches).

Cost-unit calibration: ``h`` values and C_a live in the same unit —
milliseconds of serving latency — via :meth:`calibrate`, which times one
model decode batch (the repository cost h_s) and scales the
dissimilarity metric so the paper's efficiency/accuracy trade-off is a
latency trade-off (γ keeps its role). Calibration *invalidates the
active placement buffer*: an already-built simcache indexes the old h
costs (and its memoized LSH tables / shard layouts index that stale
layout), so the runtime network is rebuilt from the held allocation
with the measured costs — the staleness this used to leave behind is
pinned by tests/test_serve_engine.py::test_calibrate_rebuilds_simcache.

Placement control plane: the engine records empirical demand; calling
``refresh_placement(algo)`` re-solves the offline problem (GREEDY /
LOCALSWAP / cascade) on the observed measure — the paper's offline
algorithms applied on a rolling window. With
``EngineConfig.device_placement`` (default) the solve runs on the
*device-resident* control plane (core/placement/device.py): the
observed instance becomes a ``DeviceInstance``, marginal gains come
from the batched gain oracle of kernels/knn/gains.py (sharded over the
same mesh axes as the data-plane keys when ``sharded``), and
GREEDY/LOCALSWAP loop over jitted incremental updates — so a rolling
re-placement no longer stalls the host exactly when the catalog grows.
``device_placement=False`` keeps the NumPy oracles (the control-plane
twin of ``fused=False``). The two paths are bit-identical on
well-separated instances (tests/test_device_placement.py), and on an
*observed* window the tail is no longer ambiguous: never-requested
objects keep an exact-zero rate (``observed_instance`` normalizes the
raw counts in f64 with no floor), so a candidate whose only value was
tail demand has a gain of exactly 0.0 on both the f32 device path and
the f64 host path, and once the real gains are exhausted both paths
stop at the same pick and leave the same slots unfilled — the old
``counts + 1e-9`` floor put sub-f32-resolution gains everywhere and
let the two paths fill the statistically-irrelevant tail in different
orders (regression pinned by tests/test_serve_engine.py::
test_observed_placement_tail_matches). Near-ties between *requested*
objects remain subject to the usual f32/f64 caveat of
core/placement/device.py.

``netduel=True`` additionally runs the §5 online policy *on device,
inside the serving loop*: a persistent ``DuelPlane``
(core/placement/netduel.py) keeps the duel state — real/virtual
savings, deadlines, serving tables — as device arrays sharded
alongside the data-plane keys (same ``LookupShardPolicy`` axes), and
each served batch is observed in one ``lax.scan`` launch priced by the
*same fused-lookup costs the data plane just computed* (a request is
priced once for serving and dueling). A settled promotion rebuilds the
runtime cache from the duel's slots (``placement_events`` counts these
churn events) — the λ-unaware complement of the offline
``refresh_placement`` solves. With ``refresh_on_promotion=True`` a
settled promotion additionally *triggers* a background offline rebuild
(``request_refresh``): the duel's churn is the signal that observed
demand drifted enough to justify re-solving — the rebuild trigger of
the streaming loop.

Control-plane/data-plane split: the data plane (lookups) and control
plane (placement solves) share the mesh and the shard axes picked by
``LookupShardPolicy``, but run disjoint kernels — a placement refresh
is a burst of gain-oracle launches between serving batches (or on the
background thread), never on the serving path itself.

Straggler mitigation: ``HedgedLookup`` (ft/straggler.py) wraps the
per-level lookups; a slow level is cut off and served by the next level
up — the cache hierarchy degrades gracefully by paying approximation
cost instead of waiting (a property unique to similarity caching; cost
quantified with the paper's own objective).
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracecount
from repro.configs.base import ArchConfig
from repro.core import demand as demand_api
from repro.core.analysis import surrogate_cost
from repro.core.catalog import Catalog
from repro.core.objective import DeviceInstance, Instance
from repro.core.placement import (DuelPlane, device_greedy,
                                  device_greedy_then_localswap,
                                  device_localswap, greedy,
                                  greedy_then_localswap, localswap,
                                  warmstart)
from repro.core.routing import StrategyPlane
from repro.core.simcache import SimCacheNetwork
from repro.core.topology import CacheNetwork, tpu_hierarchy
from repro.launch.sharding import LookupShardPolicy
from repro.models import model as model_api


def bucket_size(n: int, lo: int = 8) -> int:
    """Smallest power-of-two bucket ≥ max(n, lo) — the shape every jitted
    serving entry point actually sees under ``EngineConfig.bucket``."""
    m = max(int(lo), 1)
    while m < n:
        m <<= 1
    return m


# Tokens a prefill piece needs before one pass over the bf16 weights
# stops being bound by HBM on a TPU v5e: a token costs 2 FLOP per 2-byte
# weight, and the chip's ridge is 197e12 / 819e9 ≈ 240 FLOP per byte.
PIECE_TOKENS = 256


def piece_floor(seq: int, lo: int = 8) -> int:
    """Fewest rows of a prefill piece of ``seq``-token prompts: the
    smallest bucket (≥ ``lo``) that holds ``PIECE_TOKENS`` tokens."""
    return bucket_size(-(-PIECE_TOKENS // seq), lo)


def prefill_pieces(n: int, seq: int, lo: int = 8) -> list[int]:
    """Row counts, largest first, of the prefills that run a miss batch
    of ``n`` prompts of ``seq`` tokens. The rows are rounded up to a
    multiple of the piece floor and split into distinct powers of two;
    where that is not fewer rows than the batch's bucket, the bucket runs
    as one piece. So a plan never runs more rows than
    ``bucket_size(n, lo)``, and every piece is a bucket shape the prefill
    already compiles. Of several pieces the first is half the bucket."""
    b = bucket_size(n, lo)
    f = piece_floor(seq, lo)
    r = -(-n // f) * f
    if r >= b:
        return [b]
    return [1 << i for i in range(r.bit_length() - 1, -1, -1) if r >> i & 1]


def _first_piece(last, rows: int):
    """A (rows, vocab) array: a piece's last-position logits, then
    zeros."""
    return jnp.zeros((rows, last.shape[1]), last.dtype).at[
        :last.shape[0]].set(last)


def _next_piece(out, last, at):
    """``out`` with rows [at, at + len(last)) set to a piece's
    last-position logits."""
    return jax.lax.dynamic_update_slice_in_dim(out, last, at, axis=0)


def _pad_rows(x, m: int):
    """Pad axis 0 up to m rows by repeating row 0 (a always-valid filler:
    real coordinates / real tokens, so padded rows can never produce
    NaN/inf that a zero-filler might under exotic metrics). Results for
    padding rows are discarded by the caller — per-row kernel outputs
    are independent, so the first n rows are bitwise the unpadded run's."""
    n = x.shape[0]
    if m <= n:
        return x
    reps = jnp.repeat(x[:1], m - n, axis=0) if isinstance(x, jax.Array) \
        else np.repeat(x[:1], m - n, axis=0)
    cat = jnp.concatenate if isinstance(x, jax.Array) else np.concatenate
    return cat([x, reps], axis=0)


@dataclasses.dataclass
class EngineConfig:
    k_device: int = 64            # level-0 slots
    k_pod: int = 128
    k_global: int = 256
    h_ici: float = 0.1            # placeholder until calibrate()
    h_dcn: float = 1.0
    h_model: float = 10.0         # repository = run the model
    gamma: float = 1.0
    metric: str = "l2"
    algo: str = "cascade"         # greedy | localswap | cascade
    fused: bool = True            # single fused lookup kernel per batch
    sharded: bool = False         # mesh-sharded keys (needs engine mesh)
    prune: str | None = None      # "lsh" | "kmeans" candidate pre-filter
    verify: bool = False          # exact re-scan past the pruning bound
    quantize: bool = False        # int8 lower-bound first pass + exact
    #                               rescoring of the top-T candidates
    #                               (composes with prune/sharded; with
    #                               verify=True bit-identical to exact)
    device_placement: bool = True  # device-resident placement control plane
    swap_tol: float = 1e-3        # device LOCALSWAP accept margin (f32-safe
    #                               at calibrated-ms cost scales)
    netduel: bool = False         # §5 online duels on device, per batch
    duel_window: int = 512        # duel length in requests
    duel_delta: float = 0.05      # relative promotion margin δ
    duel_arm_prob: float = 0.25   # per-request arming probability
    duel_seed: int = 0            # arming-randomness seed
    bucket: bool = True           # power-of-two batch bucketing
    min_bucket: int = 8           # smallest bucket (tiny batches coalesce)
    refresh_on_promotion: bool = False  # duel churn → background re-solve
    refresh_min_gain: float = 0.0 # analytic refresh gate: request_refresh
    #                               prices the snapshotted demand with the
    #                               Che surrogate (core/analysis/hitrate)
    #                               and skips the device solve when the
    #                               predicted cost moved less than this
    #                               since the last installed solve (cost
    #                               units, i.e. calibrated ms; 0 = gate
    #                               off, every request solves)
    warm_start: bool = False      # §4 continuous-limit warm start: solve
    #                               the topology's continuous program,
    #                               band-map (Prop 4.2), polish — replaces
    #                               the O(O·J) discrete solve on every
    #                               refresh when the topology reduces
    warm_polish_iters: int = 512  # LOCALSWAP polish window after the
    #                               analytic warm start (O(1) in catalog
    #                               size; 0 = pure analytic placement)
    strategy: str | None = None   # on-path routing strategy (core/routing.py:
    #                               lce | lcd | probcache | sim-lru | rnd-lru)
    #                               instead of the offline-placement plane —
    #                               the λ-unaware baseline on any graph,
    #                               including multi-ingress nets the fused
    #                               simcache can't serve
    strategy_threshold: float | None = None  # C_a admission threshold θ
    strategy_seed: int = 0        # probcache / rnd-lru coin seed


# retained batch-latency window: percentiles are computed over the most
# recent LATENCY_WINDOW batches. An unbounded list was a slow leak on
# long driver runs (every batch appended forever); a deque(maxlen=…)
# ring keeps memory O(1) and the percentiles exact on the window.
LATENCY_WINDOW = 65536


@dataclasses.dataclass
class ServeStats:
    n_requests: int = 0
    n_hits: int = 0
    total_cost: float = 0.0
    total_approx_cost: float = 0.0
    model_calls: int = 0
    # refresh-gate outcomes (EngineConfig.refresh_min_gain): requests
    # skipped because the analytic surrogate saw too small a predicted
    # cost delta vs started because it saw enough (or the gate is off)
    refresh_skipped: int = 0
    refresh_triggered: int = 0
    # wall-clock per served batch (appended by SimCacheEngine.serve);
    # the latency percentiles the streaming driver/bench report —
    # bounded ring, newest LATENCY_WINDOW batches
    batch_latencies_ms: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=LATENCY_WINDOW))

    @property
    def hit_rate(self) -> float:
        return self.n_hits / max(self.n_requests, 1)

    @property
    def mean_cost(self) -> float:
        return self.total_cost / max(self.n_requests, 1)

    def latency_percentile(self, q: float) -> float:
        if not self.batch_latencies_ms:
            return 0.0
        return float(np.percentile(self.batch_latencies_ms, q))

    @property
    def p50_ms(self) -> float:
        return self.latency_percentile(50)

    @property
    def p95_ms(self) -> float:
        return self.latency_percentile(95)

    @property
    def p99_ms(self) -> float:
        return self.latency_percentile(99)


class PlacementBuffer:
    """The active data plane, versioned: the runtime cache network plus
    the allocation it was built from. The control plane never mutates a
    live buffer's network — it builds the next state and the engine
    swaps it in atomically (one pointer assignment + version bump on the
    serving thread), so a lookup always runs against a complete,
    internally consistent placement and ``version`` tells every observer
    exactly which one."""

    def __init__(self):
        self.simcache: SimCacheNetwork | None = None
        self.slots: np.ndarray | None = None
        self.slot_cache: np.ndarray | None = None
        self.version: int = 0

    def install(self, simcache: SimCacheNetwork, slots: np.ndarray,
                slot_cache: np.ndarray) -> None:
        self.simcache = simcache
        self.slots = slots
        self.slot_cache = slot_cache
        self.version += 1


class SimCacheEngine:
    """Batched serving for a decoder LM behind a similarity-cache network."""

    def __init__(self, cfg: ArchConfig, params, ecfg: EngineConfig,
                 catalog_coords: np.ndarray,
                 mesh: jax.sharding.Mesh | None = None,
                 net: CacheNetwork | None = None):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.coords = catalog_coords.astype(np.float32)   # request space
        # ``net`` overrides the built-in 3-level hierarchy with any
        # CacheNetwork (e.g. a core.scenarios general-graph scenario);
        # calibrate() only knows how to rescale the built-in one.
        self.custom_net = net is not None
        self.net = net if net is not None else tpu_hierarchy(
            ecfg.k_device, ecfg.k_pod, ecfg.k_global,
            ecfg.h_ici, ecfg.h_dcn, ecfg.h_model)
        # per-(ingress, object) empirical demand — multi-ingress nets see
        # the demand each ingress actually received (single-ingress
        # callers land everything in row 0)
        self.counts = np.zeros((self.net.n_ingress, self.coords.shape[0]),
                               dtype=np.float64)
        # on-path strategy plane: when configured it IS the serving
        # decision maker (per-request LRU walk over the ingress's path)
        # and the offline simcache is never built
        self.routing: StrategyPlane | None = None
        if ecfg.strategy is not None:
            self.routing = StrategyPlane(
                self.net, self.coords, metric=ecfg.metric,
                gamma=ecfg.gamma, strategy=ecfg.strategy,
                threshold=ecfg.strategy_threshold, seed=ecfg.strategy_seed)
        self.responses: dict[int, np.ndarray] = {}        # payload store
        self.stats = ServeStats()
        self.duel: DuelPlane | None = None                # online §5 plane
        self.placement_events = 0                         # duel churn count
        # the miss prefill, ``jit_prefill``: last-position logits and the
        # MoE layers' rows per held expert (None without experts)
        self._prefill = jax.jit(model_api.make_prefill_last(cfg))
        # the last prefill's per-piece row counts: kept here, not returned,
        # because ``serve`` calls the public ``prefill`` (which callers may
        # wrap to record its logits) and that keeps its (bucket, vocab) return
        self._expert_rows: list = []
        # (output bucket, prompt length) → the programs that lay the
        # pieces of a split prefill into the bucket's logits
        self._assembly: dict[tuple[int, int], tuple] = {}
        self.placement = PlacementBuffer()                # active data plane
        # background-refresh control: the worker thread solves, the
        # serving thread swaps; _pending crosses under _refresh_lock
        self._refresh_lock = threading.Lock()
        self._refresh_thread: threading.Thread | None = None
        self._pending: tuple | None = None
        self._in_flight = False
        self.refresh_count = 0            # completed installs (sync+async)
        self.swap_count = 0               # async atomic swaps
        self.swap_stall_s = 0.0           # total serving-thread swap time
        self.max_swap_stall_s = 0.0       # all-time max across swaps
        self.last_swap_stall_s = 0.0      # most recent swap only — what
        #                                   per-run windows (stream.py) max
        #                                   over, instead of the all-time
        #                                   value above
        self.last_predicted_cost: float | None = None
        # analytic-surrogate cost at the demand snapshot of the last
        # installed solve — the refresh gate's comparison point (None
        # until a gated solve has run, so the first request always goes
        # through)
        self._surrogate_baseline: float | None = None
        # key-axis shard policy for the sharded data plane: resolved once
        # from the mesh, reused on every placement refresh
        self.mesh = mesh
        self.lookup_shards = (LookupShardPolicy.create(mesh,
                                                       prune=ecfg.prune)
                              if mesh is not None else None)
        if ecfg.sharded and mesh is None:
            raise ValueError("EngineConfig.sharded requires a mesh")

    # -------------------------------------------------- data-plane state
    @property
    def simcache(self) -> SimCacheNetwork | None:
        """The active runtime network (the double buffer's live half)."""
        return self.placement.simcache

    @property
    def placement_version(self) -> int:
        return self.placement.version

    @property
    def refresh_in_flight(self) -> bool:
        """True from ``request_refresh`` until the swap lands (the
        observable refresh-in-flight flag of the streaming loop)."""
        return self._in_flight

    # -------------------------------------------------------- repository
    def prefill(self, tokens: jnp.ndarray) -> jax.Array:
        """Run the repository model on a (B, S) token batch; returns the
        logits of the last position.

        With ``EngineConfig.bucket``, B rows that are exactly a plan of
        several pieces (``prefill_pieces(B, S, min_bucket)``, as ``serve``
        pads a miss batch) run as those power-of-two prefills, back to
        back with no host sync, and come back as one (bucket_size(B),
        vocab) array whose first B rows are the pieces' rows in order.
        Any other batch is one prefill with (B, vocab) logits; at a
        bucket shape it also compiles, the first time, the programs that
        lay pieces into that bucket, so a split batch of host (NumPy)
        tokens compiles nothing; device tokens add an eager slice per
        piece shape. The program applies the final norm and head at the
        last position only; a MoE model's per-piece row counts of its
        held experts stay on the device for ``serve`` to fetch."""
        with tracecount.span("engine.prefill"):
            b, seq = tokens.shape
            lo = self.ecfg.min_bucket
            pieces = prefill_pieces(b, seq, lo) if self.ecfg.bucket else [b]
            if len(pieces) == 1 or sum(pieces) != b:
                last, rows = self._prefill(self.params, {"tokens": tokens})
                self._expert_rows = [rows]
                if self.ecfg.bucket and b == bucket_size(b, lo):
                    self._assemble(b, seq, last)
                return last
            out, at, self._expert_rows = None, 0, []
            for p in pieces:
                last, rows = self._prefill(self.params,
                                           {"tokens": tokens[at:at + p]})
                self._expert_rows.append(rows)
                first, rest = self._assemble(bucket_size(b, lo), seq, last)
                out = first(last) if out is None \
                    else rest[p](out, last, np.int32(at))
                at += p
            return out

    def _count_expert_rows(self, rows: list, tokens: int) -> None:
        """Counters of the MoE layers of one served prefill: the
        token-expert assignments the device routed (``tokens`` × top-k ×
        MoE layers), the rows the held experts computed, and per piece
        and layer the busiest held expert's rows, summed. ``rows``: one
        (MoE layers, held) array per piece."""
        tracecount.add("moe.assignments",
                       tokens * self.cfg.moe_topk * rows[0].shape[0])
        tracecount.add("moe.rows_held", int(sum(r.sum() for r in rows)))
        tracecount.add("moe.rows_busiest",
                       int(sum(r.max(axis=1).sum() for r in rows)))

    def _assemble(self, b: int, seq: int, last: jax.Array) -> tuple:
        """(first, {rows: next}) for output bucket ``b`` at prompt length
        ``seq``, given a prefill's (rows, vocab) last-position logits,
        compiled on first use: ``first`` lays the first piece, b/2 rows,
        into a new (b, vocab) array; ``next[p]`` lays a p-row piece at a
        traced offset, in place, for each p from the piece floor to b/4.
        Nothing where no plan splits a batch of bucket ``b``."""
        vocab = last.shape[1]
        key = (b, seq)
        if key not in self._assembly:
            f = piece_floor(seq, self.ecfg.min_bucket)
            first, rest = None, {}
            if b // 2 >= f:
                def piece(p):
                    return jax.ShapeDtypeStruct((p, vocab), last.dtype)
                first = jax.jit(_first_piece, static_argnums=1).lower(
                    piece(b // 2), b).compile()
                out = jax.ShapeDtypeStruct((b, vocab), last.dtype)
                at = jax.ShapeDtypeStruct((), jnp.int32)
                rest = {p: jax.jit(_next_piece, donate_argnums=0).lower(
                            out, piece(p), at).compile()
                        for p in (f << k for k in range(
                            (b // 4 // f).bit_length()))}
            self._assembly[key] = first, rest
        return self._assembly[key]

    # ------------------------------------------------------- calibration
    def calibrate(self, sample_prompt: jnp.ndarray, n: int = 3) -> float:
        """Measure the repository cost (one prefill batch) in ms and set
        h_model; ICI/DCN levels get fixed fractions (real deployments
        measure them the same way).

        Rebuilds the topology *and* the active placement buffer: a
        simcache built before calibration serves the old h costs (its
        per-key cost offsets, memoized LSH tables and shard layouts all
        bake the stale values in), so the held allocation is re-installed
        against the measured costs, and an armed duel plane — priced in
        the old cost units — is re-armed from the observed window.
        """
        if self.custom_net:
            raise ValueError(
                "calibrate() rescales the built-in tpu_hierarchy levels; "
                "a custom CacheNetwork carries its own cost unit — build "
                "it with calibrated delays instead")
        self._prefill(self.params, {"tokens": sample_prompt})
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(
                self._prefill(self.params, {"tokens": sample_prompt}))
        ms = (time.perf_counter() - t0) / n * 1e3
        self.ecfg.h_model = ms
        self.ecfg.h_ici = ms * 0.01
        self.ecfg.h_dcn = ms * 0.1
        self.net = tpu_hierarchy(self.ecfg.k_device, self.ecfg.k_pod,
                                 self.ecfg.k_global, self.ecfg.h_ici,
                                 self.ecfg.h_dcn, self.ecfg.h_model)
        if self.placement.slots is not None:
            # re-install the held allocation with the measured costs —
            # the stale-simcache regression fix (same slots, new h's)
            self._rebuild_simcache(self.placement.slots,
                                   self.placement.slot_cache)
            if self.duel is not None:
                self._arm_duel(self.observed_instance(),
                               self.placement.slots)
        return ms

    # ----------------------------------------------------- control plane
    def observed_instance(self) -> Instance:
        """Empirical demand window as a placement instance.

        Counts are normalized in f64 with *no* floor: never-requested
        objects keep an exact-zero rate, so every candidate gain they
        would contribute is exactly 0.0 in f32 and f64 alike and the
        host/device solvers agree bit-for-bit on the (unplaced) tail —
        the old ``counts + 1e-9`` floor drowned the tail below f32
        resolution instead. A cold engine (no requests yet) falls back
        to uniform demand.

        Counts are per-(ingress, object): a multi-ingress net's solve
        sees the demand each ingress actually received, not a collapsed
        single-row copy (the old ``lam[None, :]`` hardcoding).
        """
        total = self.counts.sum()
        if total <= 0.0:
            lam = np.full_like(self.counts, 1.0 / self.counts.size)
        else:
            lam = self.counts / total
        dem = demand_api.Demand(lam=lam)
        cat = Catalog(coords=self.coords, metric=self.ecfg.metric,
                      gamma=self.ecfg.gamma)
        return Instance(net=self.net, cat=cat, dem=dem)

    def _control_shard_args(self):
        """(mesh, axes) for the control plane, or None — the single
        resolution point shared by the solver, the duel plane, and the
        background refresh (LookupShardPolicy.control_plane_args)."""
        if self.lookup_shards is None:
            return None
        return self.lookup_shards.control_plane_args(self.ecfg.sharded)

    @tracecount.spanned("engine.solve")
    def _solve(self, inst: Instance, algo: str, device: bool,
               shard: bool = True) -> tuple[np.ndarray, float]:
        """Run the offline solver on one observed instance; returns the
        (clamped) allocation and the predicted C(A). Pure function of
        its inputs — safe to run on the background refresh thread.

        ``shard=False`` solves on a single device even when the engine
        is mesh-sharded. The background refresh thread must use it: two
        threads enqueueing *collective* programs concurrently (the
        sharded control-plane solve racing the serving thread's sharded
        lookups) have no cross-program per-device launch-order
        guarantee, so their device executions can interleave and
        deadlock the client's collective rendezvous. The control-plane
        oracles are bit-identical at any shard count (locked by
        tests/test_device_placement.py), so the unsharded background
        solve returns the same allocation the sharded one would — the
        atomic-swap differentials in tests/test_streaming.py assert
        exactly that against a sharded synchronous solve.

        With ``EngineConfig.warm_start`` on and a topology that reduces
        to a §4 continuous program (the engine's tpu_hierarchy chain
        always does), the discrete solver is replaced by the
        continuous-limit pipeline of placement/warmstart.py: solve the
        program analytically, band-map per Prop 4.2, polish with a
        bounded LOCALSWAP window — deterministic, so background
        refreshes stay replayable. Irreducible topologies fall back to
        ``algo`` untouched."""
        warm_red = warmstart.classify_topology(inst.net,
                                               gamma=inst.cat.gamma) \
            if self.ecfg.warm_start else None
        if device:
            sh = self._control_shard_args() if shard else None
            dinst = DeviceInstance.from_instance(
                inst, mesh=sh[0] if sh else None,
                axes=sh[1] if sh else (), materialize_ca=False)
        if warm_red is not None:
            slots = warmstart.warm_start(
                inst, reduction=warm_red, device=device,
                dinst=dinst if device else None,
                polish_iters=self.ecfg.warm_polish_iters,
                tol=self.ecfg.swap_tol).slots
        elif device:
            if algo == "greedy":
                slots = device_greedy(dinst)
            elif algo == "localswap":
                slots = device_localswap(dinst, n_iters=4000,
                                         tol=self.ecfg.swap_tol).slots_np
            else:
                slots = device_greedy_then_localswap(
                    dinst, max_passes=8, tol=self.ecfg.swap_tol).slots_np
        elif algo == "greedy":
            slots = greedy(inst)
        elif algo == "localswap":
            slots = localswap(inst, n_iters=4000).slots
        else:
            slots = greedy_then_localswap(inst, max_passes=8).slots
        slots = np.where(slots < 0, 0, slots)
        if device:
            # device evaluator — the only C(A) path that exists past
            # objective.CA_MATERIALIZE_MAX catalogs
            pred = dinst.total_cost(slots)
        else:
            pred = inst.total_cost(slots)
        return slots, pred

    def _arm_duel(self, inst: Instance, slots: np.ndarray) -> None:
        """(Re-)arm the online §5 plane: duel state lives on device,
        sharded along the same axes as the data-plane keys, and persists
        across serve() batches (reset on every offline install)."""
        sh = self._control_shard_args()
        duel_dinst = DeviceInstance.from_instance(
            inst, mesh=sh[0] if sh else None,
            axes=sh[1] if sh else (), materialize_ca=False)
        self.duel = DuelPlane(
            duel_dinst, slots, window=self.ecfg.duel_window,
            delta=self.ecfg.duel_delta,
            arm_prob=self.ecfg.duel_arm_prob, seed=self.ecfg.duel_seed)

    def _install(self, slots: np.ndarray, inst: Instance) -> None:
        """Install a solved allocation into the active buffer: rebuild
        the runtime network, re-arm the duel plane, bump the version.
        Runs on the serving thread — this *is* the atomic swap."""
        self._rebuild_simcache(slots, inst.slot_cache)
        if self.ecfg.netduel:
            self._arm_duel(inst, slots)
        self.refresh_count += 1

    def refresh_placement(self, algo: str | None = None,
                          device: bool | None = None) -> float:
        """Re-solve offline placement on the observed demand window;
        rebuild the runtime cache. Returns the predicted C(A).

        ``device=None`` follows ``EngineConfig.device_placement``: the
        default device path solves on a DeviceInstance via the batched
        gain oracle (mesh-sharded alongside the data-plane keys when
        ``sharded``); ``device=False`` forces the NumPy oracles.

        This is the *synchronous* path (solve + install in one call,
        serving blocked throughout) — the streaming loop uses
        :meth:`request_refresh` / :meth:`poll_refresh` instead.
        """
        algo = algo or self.ecfg.algo
        if device is None:
            device = self.ecfg.device_placement
        inst = self.observed_instance()
        slots, pred = self._solve(inst, algo, device)
        self._install(slots, inst)
        self.last_predicted_cost = pred
        if self.ecfg.refresh_min_gain > 0.0:
            self._surrogate_baseline = surrogate_cost(
                inst.net, np.asarray(inst.dem.lam, np.float64))
        return pred

    # ------------------------------------------- double-buffered refresh
    def request_refresh(self, algo: str | None = None,
                        device: bool | None = None) -> bool:
        """Start a background placement re-solve against a snapshot of
        the observed demand; the active buffer keeps serving throughout.
        Returns False (and does nothing) if a refresh is already in
        flight. The finished solve is *not* installed here — call
        :meth:`poll_refresh` from the serving loop to swap it in.

        With ``EngineConfig.refresh_min_gain > 0`` the snapshot is first
        priced by the analytic Che surrogate
        (``core.analysis.surrogate_cost``, milliseconds even at 10⁶
        objects): if the predicted per-request cost moved less than the
        gate since the demand snapshot of the last installed solve, the
        device solve is skipped (returns False,
        ``ServeStats.refresh_skipped`` += 1) — stationary demand stops
        paying for rebuilds it doesn't need, while drift still triggers
        (``refresh_triggered``)."""
        if self._in_flight:
            return False
        algo = algo or self.ecfg.algo
        if device is None:
            device = self.ecfg.device_placement
        inst = self.observed_instance()       # snapshot: lam is a copy
        surrogate_now: float | None = None
        if self.ecfg.refresh_min_gain > 0.0:
            surrogate_now = surrogate_cost(
                inst.net, np.asarray(inst.dem.lam, np.float64))
            base = self._surrogate_baseline
            if base is not None and \
                    abs(surrogate_now - base) < self.ecfg.refresh_min_gain:
                self.stats.refresh_skipped += 1
                return False
            self.stats.refresh_triggered += 1
        self._in_flight = True

        def work():
            try:
                # unsharded: a collective solve here would race the
                # serving thread's collectives (see _solve's docstring)
                slots, pred = self._solve(inst, algo, device, shard=False)
                with self._refresh_lock:
                    self._pending = (slots, inst, pred, surrogate_now)
            except BaseException:
                self._in_flight = False       # never wedge the flag
                raise

        self._refresh_thread = threading.Thread(
            target=work, name="placement-refresh", daemon=True)
        self._refresh_thread.start()
        return True

    def wait_refresh(self, timeout: float | None = None) -> bool:
        """Block until the in-flight solve finishes (the *solve*, not the
        swap — call :meth:`poll_refresh` after). True if nothing is
        running or the thread completed within ``timeout``."""
        t = self._refresh_thread
        if t is None or not t.is_alive():
            return True
        t.join(timeout)
        return not t.is_alive()

    def poll_refresh(self) -> bool:
        """Install a finished background solve, if any: the atomic swap.
        The serving thread stalls only for the host-side rebuild + duel
        re-arm (timed into ``swap_stall_s``/``max_swap_stall_s``), never
        for the solve. Returns True iff a swap happened."""
        with self._refresh_lock:
            pend, self._pending = self._pending, None
        if pend is None:
            return False
        slots, inst, pred, surrogate_now = pend
        with tracecount.span("engine.swap") as sp:
            self._install(slots, inst)
        stall = sp.ns * 1e-9
        self.swap_stall_s += stall
        self.max_swap_stall_s = max(self.max_swap_stall_s, stall)
        self.last_swap_stall_s = stall
        self.swap_count += 1
        self.last_predicted_cost = pred
        if surrogate_now is not None:
            # the installed solve's snapshot becomes the gate baseline
            self._surrogate_baseline = surrogate_now
        self._in_flight = False
        return True

    @tracecount.spanned("engine.install")
    def _rebuild_simcache(self, slots: np.ndarray,
                          slot_cache: np.ndarray | None = None) -> None:
        """(Re)build the runtime lookup network from an allocation and
        install it into the placement buffer (version += 1) — shared by
        the offline install, the online duel's promotion churn, and the
        calibration rebuild."""
        if self.net.n_ingress > 1:
            raise ValueError(
                "the fused simcache serves one ingress row of H; a "
                "multi-ingress CacheNetwork needs the on-path strategy "
                "plane (EngineConfig.strategy) instead")
        if slot_cache is None:
            slot_cache = self.net.slot_layout()
        if self.custom_net:
            # a custom single-ingress net (core/scenarios.py) carries its
            # own per-cache reach costs in its H row
            hs = [float(h) for h in np.asarray(self.net.H[0], np.float64)]
            h_repo = float(self.net.h_repo[0])
        else:
            # built-in hierarchy: use the exact f64 config values (the
            # net stores H in f32 — going through it would round them)
            hs = [0.0, self.ecfg.h_ici, self.ecfg.h_dcn]
            h_repo = self.ecfg.h_model
        simcache = SimCacheNetwork.from_placement(
            self.coords, slots, slot_cache, hs, h_repo,
            metric=self.ecfg.metric, gamma=self.ecfg.gamma,
            fused=self.ecfg.fused, sharded=self.ecfg.sharded,
            mesh=self.mesh,
            shard_axes=(self.lookup_shards.axes
                        if self.lookup_shards else None),
            candidate_policy=(self.lookup_shards.candidate_policy()
                              if self.lookup_shards else None))
        self.placement.install(simcache, np.asarray(slots), slot_cache)

    # --------------------------------------------------------- data plane
    def serve(self, request_ids: np.ndarray, prompts: jnp.ndarray,
              ingress_ids: np.ndarray | None = None
              ) -> tuple[list, ServeStats]:
        """Serve a batch. request_ids index the catalog (their embeddings
        are the lookup keys); prompts are the token batch for misses.
        ``ingress_ids`` says where each request entered the network
        (None → ingress 0, the single-ingress hierarchy's only row).

        With ``EngineConfig.bucket`` the lookup and the duel observation
        run at the batch's power-of-two bucket shape (padding masked out
        of every stat and the duel trajectory), and the miss prefill as
        the power-of-two pieces of ``prefill_pieces``, so a stream of
        mixed batch sizes compiles each entry point once per bucket
        instead of once per size.

        The call is the ``engine.serve`` span (stats: ``batch``, the
        process's served-batch index, and ``n``); its phases are spans
        nested inside it (see ``repro.tracecount``), and its duration is
        the batch's entry in ``ServeStats.batch_latencies_ms``.
        """
        request_ids = np.asarray(request_ids)
        n = len(request_ids)
        with tracecount.span("engine.serve",
                             batch=tracecount.get("serve.batches"),
                             n=n) as sp:
            out = self._serve(request_ids, prompts, ingress_ids)
        self.stats.batch_latencies_ms.append(sp.ns * 1e-6)
        return out, self.stats

    def _serve(self, request_ids: np.ndarray, prompts: jnp.ndarray,
               ingress_ids: np.ndarray | None) -> list:
        n = len(request_ids)
        with tracecount.span("serve.demand"):
            if ingress_ids is None:
                ingress_ids = np.zeros(n, dtype=np.int64)
            else:
                ingress_ids = np.asarray(ingress_ids, dtype=np.int64)
            # np.add.at, not fancy-indexed +=: a batch with the same
            # object twice must count twice (the += form collapses
            # duplicates and undercounts exactly the hot objects of a
            # skewed trace)
            np.add.at(self.counts, (ingress_ids, request_ids), 1.0)
            self.stats.n_requests += n
            tracecount.add("serve.batches")
            tracecount.add("serve.requests", n)
        out: list = [None] * n
        bucket = self.ecfg.bucket

        route_dec = None
        if self.routing is not None:
            # on-path strategy plane: per-request LRU walk over the
            # ingress's forwarding path decides server and insertions —
            # no offline simcache, no duel (λ-unaware by design)
            route_dec = self.routing.serve(request_ids, ingress_ids)
            self.stats.total_cost += float(route_dec.cost.sum())
            self.stats.total_approx_cost += float(
                route_dec.approx_cost.sum())
            self.stats.n_hits += int(route_dec.hit.sum())
            miss_idx = np.nonzero(~route_dec.hit)[0]
        elif self.simcache is None:
            miss_idx = np.arange(n)
        else:
            with tracecount.span("serve.queries"):
                q = jnp.asarray(self.coords[request_ids])
                if bucket:
                    q = _pad_rows(q, bucket_size(n, self.ecfg.min_bucket))
            tracecount.add("lookup.rows", q.shape[0])
            tracecount.add("lookup.rows_valid", n)
            res = self.simcache.lookup(q, prune=self.ecfg.prune,
                                       verify=self.ecfg.verify,
                                       quantize=self.ecfg.quantize)
            with tracecount.span("serve.fetch_lookup"):
                hit_b = np.asarray(res.hit)
                payload_b = np.asarray(res.payload)
                full_cost = np.asarray(res.cost)      # bucket shape
                approx_b = np.asarray(res.approx_cost)
            tracecount.add("serve.copies", 4)
            tracecount.add("serve.copy_bytes", hit_b.nbytes
                           + payload_b.nbytes + full_cost.nbytes
                           + approx_b.nbytes)
            with tracecount.span("serve.respond_hits"):
                # slice the valid prefix before any accounting: padded
                # rows never touch stats, responses, or the demand window
                hits, payloads = hit_b[:n], payload_b[:n]
                self.stats.total_cost += float(np.sum(full_cost[:n]))
                self.stats.total_approx_cost += float(np.sum(approx_b[:n]))
                for i in np.nonzero(hits)[0]:
                    out[i] = self.responses.get(int(payloads[i]))
                self.stats.n_hits += int(hits.sum())
                miss_idx = np.nonzero(~hits)[0]
            if self.duel is not None:
                # online control plane: observe the batch in one scan
                # launch, priced by the costs the lookup just computed —
                # at the bucket shape, padded steps masked to no-ops
                ids_b = _pad_rows(request_ids, full_cost.shape[0])
                if self.duel.observe(ids_b, b1_ext=full_cost,
                                     n_valid=n if bucket else None):
                    self._rebuild_simcache(self.duel.slots_np)
                    self.placement_events += 1
                    if self.ecfg.refresh_on_promotion:
                        # duel churn = demand drifted: trigger the
                        # background offline re-solve (no-op if one is
                        # already in flight)
                        self.request_refresh()

        if len(miss_idx):
            # repository: run the model on the miss sub-batch, padded to
            # its piece plan: power-of-two prefills that compile per
            # bucket, with no more rows than the sub-batch's own bucket
            with tracecount.span("serve.miss_gather"):
                sel = prompts[jnp.asarray(miss_idx)]
                pieces = [len(miss_idx)]
                if bucket:
                    pieces = prefill_pieces(len(miss_idx), sel.shape[1],
                                            self.ecfg.min_bucket)
                    sel = _pad_rows(sel, sum(pieces))
            tracecount.add("prefill.rows", sel.shape[0])
            tracecount.add("prefill.rows_valid", len(miss_idx))
            tracecount.add("prefill.batches")
            tracecount.add("prefill.pieces", len(pieces))
            logits = self.prefill(sel)
            with tracecount.span("serve.fetch_prefill"):
                resp, rows = jax.device_get(
                    (jnp.argmax(logits, axis=-1), self._expert_rows))
            tracecount.add("serve.copies")
            tracecount.add("serve.copy_bytes", resp.nbytes)
            if rows[0] is not None:
                tracecount.add("serve.copy_bytes",
                               sum(r.nbytes for r in rows))
                self._count_expert_rows(rows, sel.shape[0] * sel.shape[1])
            self.stats.model_calls += 1
            if self.routing is None and self.simcache is None:
                # cold engine without a strategy plane: repository cost
                # per miss (the routing plane already counted dec.cost)
                self.stats.total_cost += self.ecfg.h_model * len(miss_idx)
            with tracecount.span("serve.respond_misses"):
                for j, i in enumerate(miss_idx):
                    rid = int(request_ids[i])
                    self.responses[rid] = resp[j:j + 1]
                    out[i] = resp[j:j + 1]
        if route_dec is not None:
            # fill hits AFTER the miss prefill: a request can hit a key
            # an earlier miss of this very batch just inserted, whose
            # response only exists once the model ran
            for i in np.nonzero(route_dec.hit)[0]:
                out[i] = self.responses.get(int(route_dec.payload[i]))
        return out
