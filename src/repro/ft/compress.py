"""Cross-pod gradient compression (distributed-optimization trick).

The 'pod' mesh axis crosses DCN (~25× less bandwidth than ICI). Gradients
are reduced hierarchically: full-precision psum *within* each pod over
ICI, then an int8-quantized exchange *across* pods — 4× fewer DCN bytes
than an f32 psum leg at a quantization error that vanishes into the Adam
noise floor (per-row scales keep relative error < 1/127 per block).

Implemented with shard_map so the two legs are explicit (a plain pjit
all-reduce would fuse them into one f32 ring over both axes).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# The int8 quantizer lives in kernels/quant.py now — one implementation
# shared with the compressed first-pass distance path of the lookup and
# gain kernels (and with an explicit all-zero-row guard: scale 0.0, not
# the historic denormal 1e-20 floor). Re-exported here so existing
# gradient-exchange callers and tests keep their import site.
from repro.kernels.quant import dequantize_int8, quantize_int8

__all__ = ["quantize_int8", "dequantize_int8", "compressed_crosspod_mean"]


def _crosspod_leaf(g: jax.Array, pod_axis: str) -> jax.Array:
    """Mean over the pod axis with int8 exchange (inside shard_map)."""
    q, s = quantize_int8(g)
    # all_gather the quantized payload + scales (int8 over DCN), then
    # dequantize-and-mean locally
    qs = jax.lax.all_gather(q, pod_axis)            # (n_pods, ...) int8
    ss = jax.lax.all_gather(s, pod_axis)
    deq = dequantize_int8(qs, ss)
    out = jnp.mean(deq, axis=0).reshape(g.shape if g.ndim else (1,))
    return out.reshape(g.shape) if g.ndim else out[0]


def compressed_crosspod_mean(grads: Any, mesh, pod_axis: str = "pod",
                             data_axis: str = "data") -> Any:
    """Hierarchical gradient mean: f32 psum over data (ICI), int8
    exchange over pods (DCN). Leaves must be replicated over the model
    axis or sharded consistently; the shard_map below runs per (pod,
    data) shard and leaves other dims alone."""
    def per_shard(g):
        g = jax.lax.pmean(g, data_axis)             # ICI leg, f32
        return _crosspod_leaf(g, pod_axis)          # DCN leg, int8

    spec = P()        # gradients replicated within the mapped axes

    def apply(leaf):
        fn = jax.shard_map(per_shard, mesh=mesh,
                       in_specs=spec, out_specs=spec,
                       check_vma=False)
        return fn(leaf)
    return jax.tree.map(apply, grads)
