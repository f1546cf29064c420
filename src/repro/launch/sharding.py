"""Mesh-aware sharding: logical axes → PartitionSpecs with divisibility
fallbacks (the resolver of DESIGN.md §4).

Strategy summary (per arch × mode, computed in ``MeshShardPolicy``):

  params    — TP: heads/ff/vocab/experts → "model" (when the dim
              divides); FSDP: embed → "data". Optimizer moments inherit
              parameter specs (fully sharded ZeRO-style state).
  train     — batch → (pod, data); MLP/MoE TP over "model";
              attention "heads" strategy when n_heads % model == 0
              (with KV-head repetition to the TP degree for GQA),
              otherwise "batch" strategy: attention activations shard
              batch over (pod, data, model) inside the sublayer.
  prefill   — same as train (+ optional sequence sharding knob).
  decode    — KV caches shard their sequence axis over "model"
              (distributed flash-decode); batch over (pod, data).

Every rule is a *candidate list*; ``_resolve`` keeps the longest prefix
of axes that divides the dim and never reuses a mesh axis across dims,
so any (arch × shape × mesh) combination lowers without manual edits —
the property the 40-cell dry-run matrix exercises.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.kernels.knn import mesh_axes_size
from repro.models.schema import ParamSpec
from repro.models.sharding_api import ShardPolicy


def _resolve(shape: tuple, axes: tuple, rules: dict, mesh: Mesh) -> P:
    """Map logical axis names to mesh axes honoring divisibility."""
    used: set = set()
    out = []
    for dim, name in zip(shape, axes):
        chosen: list = []
        rem = int(dim)
        for ax in rules.get(name, ()):
            if ax in mesh.shape and ax not in used and \
                    rem % mesh.shape[ax] == 0:
                chosen.append(ax)
                used.add(ax)
                rem //= mesh.shape[ax]
        out.append(tuple(chosen) if chosen else None)
    return P(*out)


def attn_strategy_for(cfg: ArchConfig, mesh: Mesh, mode: str) -> str:
    model = mesh.shape.get("model", 1)
    if mode == "decode":
        return "kv_seq"
    if cfg.n_heads % model == 0:
        return "heads"
    return "batch"


def kv_repeat_for(cfg: ArchConfig, mesh: Mesh, strategy: str) -> int:
    """Repeat KV heads to the TP degree under heads-TP (GQA)."""
    model = mesh.shape.get("model", 1)
    if strategy != "heads" or cfg.n_kv_heads >= model:
        return 1
    if model % cfg.n_kv_heads == 0:
        return model // cfg.n_kv_heads
    return 1


@dataclasses.dataclass(frozen=True)
class MeshShardPolicy(ShardPolicy):
    """ShardPolicy backed by a real mesh (models call this).

    Perf knobs (EXPERIMENTS.md §Perf — defaults are the baseline):
      * ffn_mode="dp": no tensor parallelism; activations sequence-shard
        over the model axis (ZeRO-DP + sequence parallelism — the small-
        model recipe; removes all Megatron-style activation all-reduces);
      * attn_override="seq": attention runs with its sequence axis over
        the model axis (context parallelism) instead of the batch
        round-trip, for archs whose head count doesn't divide the TP
        degree;
      * serve_fsdp=False: serving params replicate over the data axis
        (no per-layer weight all-gathers on the decode path).
    """
    cfg: ArchConfig = None
    mesh: Mesh = None
    mode: str = "train"
    seq_shard: bool = False          # prefill sequence parallelism knob
    ffn_mode: str = "tp"             # tp | dp
    serve_fsdp: bool = True

    @classmethod
    def create(cls, cfg: ArchConfig, mesh: Mesh, mode: str,
               seq_shard: bool = False, ffn_mode: str = "tp",
               attn_override: str | None = None,
               serve_fsdp: bool = True) -> "MeshShardPolicy":
        strategy = attn_override or attn_strategy_for(cfg, mesh, mode)
        if ffn_mode == "dp" and mode != "decode":
            strategy = "seq"
        if ffn_mode == "dp_batch" and mode != "decode":
            strategy = "batch"
        return cls(attn_strategy=strategy,
                   kv_repeat=kv_repeat_for(cfg, mesh, strategy),
                   cfg=cfg, mesh=mesh, mode=mode, seq_shard=seq_shard,
                   ffn_mode=ffn_mode, serve_fsdp=serve_fsdp)

    # ------------------------------------------------- activation rules
    def act_rules(self) -> dict:
        dp = self.ffn_mode in ("dp", "dp_batch")
        # dp_batch: pure data parallelism over every axis incl. model —
        # token-local routing (MoE cumsum never crosses shards)
        batch = ("pod", "data", "model") if self.ffn_mode == "dp_batch" \
            else ("pod", "data")
        rules = {
            "batch": batch,
            "attn_batch": batch + (("model",) if self.attn_strategy == "batch"
                                   else ()),
            "seq": ("model",) if (self.seq_shard or self.ffn_mode == "dp")
            else (),
            "attn_seq": ("model",) if self.attn_strategy == "seq" else (),
            "kv_seq": ("model",),
            "heads": ("model",) if self.attn_strategy == "heads" else (),
            "rep_kv_heads": ("model",) if self.attn_strategy == "heads"
            else (),
            "kv_heads": (),
            "head_dim": (),
            "embed": (),
            "ff": () if dp else ("model",),
            "vocab": () if dp else ("model",),
            "experts": () if dp else ("model",),
            # MoE dispatch groups follow the token sharding
            "moe_group": batch + (("model",) if self.ffn_mode == "dp"
                                  else ()),
            "layers": (),
            "state": (),
        }
        return rules

    def spec_for(self, shape: tuple, axes: tuple) -> P:
        return _resolve(shape, axes, self.act_rules(), self.mesh)

    def __call__(self, x, axes):
        spec = self.spec_for(x.shape, axes)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    # ------------------------------------------------------ param rules
    def param_rules(self) -> dict:
        dp = self.ffn_mode in ("dp", "dp_batch")
        heads_tp = ("model",) if not dp \
            and self.attn_strategy in ("heads", "kv_seq") \
            and self.cfg.n_heads % self.mesh.shape.get("model", 1) == 0 \
            else ()
        # serving without FSDP: replicate over data (no per-layer weight
        # all-gathers on the decode path). FSDP stays data-axis-only even
        # in dp modes: 16-way ZeRO-3 fits every config's state and keeps
        # the per-layer gather group narrow (§Perf iteration log).
        fsdp = ("data",) if (self.mode == "train" or self.serve_fsdp) else ()
        # decode with head-indivisible archs (56H/40H/28H ∤ 16): shard
        # attention weights on head_dim instead — q·k contracts the
        # sharded dim into a tiny per-token all-reduce, and the 4×-larger
        # attention param block stops being replicated (§Perf cell C′)
        head_dim_tp = ("model",) if (self.mode == "decode" and not dp
                                     and not heads_tp) else ()
        return {
            "heads": heads_tp,
            "kv_heads": heads_tp,        # divisibility usually drops this
            "head_dim": head_dim_tp,
            "embed": fsdp,
            "ff": () if dp else ("model",),
            "vocab": () if dp else ("model",),
            "experts": () if dp else ("model",),
            "layers": (),
            None: (),
        }

    def param_spec(self, ps: ParamSpec) -> P:
        return _resolve(ps.shape, ps.axes, self.param_rules(), self.mesh)

    def param_sharding_tree(self, schema_tree: Any) -> Any:
        """Nested dict of NamedShardings mirroring param_schema(cfg)."""
        def walk(node):
            if isinstance(node, ParamSpec):
                return NamedSharding(self.mesh, self.param_spec(node))
            return {k: walk(v) for k, v in node.items()}
        return walk(schema_tree)

    def moment_sharding_tree(self, schema_tree: Any, moment_dtype: str
                             ) -> Any:
        """Optimizer-moment shardings: inherit the param spec; int8
        moments carry a per-row scale whose last dim is unsharded."""
        def walk(node):
            if isinstance(node, ParamSpec):
                spec = self.param_spec(node)
                if moment_dtype != "int8":
                    return NamedSharding(self.mesh, spec)
                parts = list(spec) + [None] * (len(node.shape) - len(spec))
                sspec = P(*(parts[:-1] + [None]))
                return {"q": NamedSharding(self.mesh, spec),
                        "s": NamedSharding(self.mesh, sspec)}
            return {k: walk(v) for k, v in node.items()}
        return walk(schema_tree)

    # ------------------------------------------------------ cache rules
    def cache_spec(self, key: str, shape: tuple) -> P:
        batch = ("pod", "data")
        by_key = {
            "k": (None, batch, ("model",), None, None),
            "v": (None, batch, ("model",), None, None),
            "xk": (None, batch, ("model",), None, None),
            "xv": (None, batch, ("model",), None, None),
            "k_s": (None, batch, ("model",), None, None),
            "v_s": (None, batch, ("model",), None, None),
            "h": (None, batch, ("model",), None),          # mamba (Di)
            "conv": (None, batch, None, ("model",)),       # mamba conv buf
            "C": (None, batch, None, ("model",), None),    # mlstm
            "n": (None, batch, None, ("model",)),
            "c": (None, batch, None, ("model",)),          # slstm
        }
        cands = by_key.get(key, (None,) * len(shape))
        used: set = set()
        parts = []
        for dim, cand in zip(shape, cands):
            if cand is None:
                parts.append(None)
                continue
            cand = (cand,) if isinstance(cand, str) else cand
            chosen = []
            rem = int(dim)
            for ax in cand:
                if ax in self.mesh.shape and ax not in used and \
                        rem % self.mesh.shape[ax] == 0:
                    chosen.append(ax)
                    used.add(ax)
                    rem //= self.mesh.shape[ax]
            parts.append(tuple(chosen) if chosen else None)
        return P(*parts)

    def cache_sharding_tree(self, cache_shapes: Any) -> Any:
        def walk(node):
            return {k: (walk(v) if isinstance(v, dict) else
                        NamedSharding(self.mesh, self.cache_spec(k, v.shape)))
                    for k, v in node.items()}
        return walk(cache_shapes)

    # ------------------------------------------------------ batch rules
    def batch_sharding_tree(self, batch_shapes: dict) -> dict:
        out = {}
        for k, v in batch_shapes.items():
            if k == "mrope_positions":              # (3, B, S)
                spec = _resolve(v.shape, (None, "batch", "seq"),
                                self.act_rules(), self.mesh)
            elif v.ndim >= 2:
                axes = ("batch", "seq") + (None,) * (v.ndim - 2)
                spec = _resolve(v.shape, axes, self.act_rules(), self.mesh)
            else:
                spec = P()
            out[k] = NamedSharding(self.mesh, spec)
        return out


@dataclasses.dataclass(frozen=True)
class LookupShardPolicy:
    """Key-axis sharding policy for the similarity-cache fused lookup.

    The SimCacheNetwork data plane shards the segmented key tensor
    (keys, h_key, meta) over mesh axes; this policy decides *which*
    axes, reusing :func:`_resolve`'s divisibility-fallback logic: the
    longest prefix of ``candidates`` present in the mesh is kept (the
    key axis is always padded to a multiple of the resulting shard
    count, so divisibility is guaranteed by construction — we resolve
    against the full candidate product). Preference order puts "model"
    first: lookup shards and tensor-parallel shards then live on the
    same devices, so cache keys sit next to the KV-prefix payloads they
    index.

    ``prune`` additionally selects the per-shard candidate-pruning
    tables (kernels.knn.lsh): each shard of the balanced contiguous key
    layout builds its *own* SimHash / k-means tables over its resident
    chunk, seeded from ``table_seed`` (shard s draws from
    ``policy.for_shard(s)``, so hyperplanes/centroids are independent
    across shards while the whole fleet stays reproducible).

    The *control plane* rides the same axes: the placement gain oracle
    (kernels/knn/gains.py) shard_maps its candidate-object axis over
    ``axes`` (see :meth:`gain_shard_args`), so candidate shards are
    co-resident with the data-plane key shards they would populate —
    one placement decision's gains and its eventual cache keys live on
    the same devices. The *online* control plane (NETDUEL's DuelPlane
    and the scanned LOCALSWAP window, core/placement/netduel.py /
    device.py) rides them too: a DeviceInstance built from
    :meth:`gain_shard_args` routes its serving-table refreshes through
    ``objective.sharded_best_two``, which shard_maps the request axis
    over the same ``axes`` — the duel state of a key shard's content
    is refreshed where the keys live.
    """
    mesh: Mesh
    axes: tuple[str, ...]
    prune: str | None = None
    table_seed: int = 0

    @classmethod
    def create(cls, mesh: Mesh,
               candidates: tuple[str, ...] = ("model", "data", "pod"),
               prune: str | None = None,
               table_seed: int = 0) -> "LookupShardPolicy":
        present = tuple(ax for ax in candidates if ax in mesh.shape)
        if not present:                  # unrecognised axes: use them all
            present = tuple(mesh.axis_names)
        total = mesh_axes_size(mesh, present)
        spec = _resolve((total,), ("keys",), {"keys": present}, mesh)
        # PartitionSpec stores a one-axis entry as the bare axis name
        axes = spec[0] if spec[0] is not None else ()
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        return cls(mesh=mesh, axes=axes, prune=prune,
                   table_seed=table_seed)

    @property
    def n_shards(self) -> int:
        return mesh_axes_size(self.mesh, self.axes)

    def candidate_policy(self):
        """The base CandidatePolicy for this deployment (None when
        pruning is off); SimCacheNetwork derives per-shard tables from
        it via ``for_shard``."""
        if self.prune is None:
            return None
        from repro.kernels.knn.lsh import default_policy
        return default_policy(self.prune, seed=self.table_seed)

    def gain_shard_args(self) -> tuple[Mesh, tuple[str, ...]] | None:
        """(mesh, axes) for sharding the placement control plane — the
        gain oracle's candidate axis and the online plane's
        serving-table request axis (``sharded_best_two``). None when
        the policy resolves to a single shard (everything then runs
        unsharded, and the shard_maps would only add overhead). Values
        are bit-identical either way (per-candidate/per-request sums
        are shard-count-independent by construction)."""
        if self.n_shards <= 1:
            return None
        return (self.mesh, self.axes)

    def control_plane_args(self, enabled: bool = True
                           ) -> tuple[Mesh, tuple[str, ...]] | None:
        """Single resolution point for every control-plane consumer in
        the serving engine (offline solver, duel plane, background
        refresh): :meth:`gain_shard_args` when the engine's data plane
        is actually sharded (``enabled``), else None — so a policy held
        for pruning-table seeds alone never turns on shard_maps."""
        if not enabled:
            return None
        return self.gain_shard_args()


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def count_devices(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
