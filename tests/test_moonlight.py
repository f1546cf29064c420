"""Moonlight-16B-A3B's block (DeepSeek-V3: latent attention, sigmoid-routed
experts with a correction bias, shared experts, a dense first layer) at a
tiny size on the CPU, against the benchmark's plain reference
(``bench/reference/moonlight.py``) on seeded weights: the engine's
prefill, the expert layer's shares, dropless serving, and the routing
equations."""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, get_smoke_config
from repro.models import model as model_api
from repro.models import layers, moe, transformer
from repro.models.sharding_api import NO_SHARD
from repro.serve import EngineConfig, SimCacheEngine

_REF = Path(__file__).resolve().parents[1] / "bench/reference/moonlight.py"
_spec = importlib.util.spec_from_file_location("moonlight_reference", _REF)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

SMOKE = get_smoke_config("moonlight-16b-a3b")
SEQ = 12


def _cfg(held=()):
    return dataclasses.replace(SMOKE, experts_held=held)


def _ref_cfg(cfg):
    """The reference's configuration keys for an ArchConfig."""
    return {"vocab_size": cfg.vocab, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "qk_nope_head_dim": cfg.head_dim,
            "qk_rope_head_dim": cfg.qk_rope_dim,
            "v_head_dim": cfg.head_dim,
            "num_experts_per_tok": cfg.moe_topk, "norm_topk_prob": True,
            "routed_scaling_factor": cfg.moe_routed_scale,
            "program": {"arch": {"experts_held": list(cfg.held)}}}


def _weights(cfg, seed):
    """Seeded weights as the benchmark draws them: norms one, every other
    leaf N(0, 0.02²), but the correction bias at 0.1 so that it moves
    choices at this size."""
    shapes = jax.eval_shape(lambda: model_api.init_params(cfg, 0))
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for (path, s), k in zip(leaves, keys):
        name = path[-1].key
        scale = 0.1 if name == "router_bias" else 0.02
        out.append(jnp.ones(s.shape, s.dtype) if name.endswith("norm")
                   else scale * jax.random.normal(k, s.shape, s.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def _share(params, first, n):
    """The weights a chip holding experts [first, first + n) keeps."""
    p = jax.tree.map(lambda a: a, params)
    blk = dict(p["blocks"]["b0_attn_moe"])
    for k in ("we_gate", "we_up", "we_down"):
        blk[k] = blk[k][:, first:first + n]
    p["blocks"] = {"b0_attn_moe": blk}
    return p


def _tokens(cfg, n, seed):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, (n, SEQ)).astype(np.int32)


def test_full_config_is_the_published_block():
    cfg = get_config("moonlight-16b-a3b")
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.d_model, cfg.n_heads,
            cfg.head_dim, cfg.qk_rope_dim, cfg.kv_lora_rank) == (
        27, 1, 2048, 16, 128, 64, 512)
    assert (cfg.d_ff, cfg.moe_ff, cfg.shared_ff, cfg.moe_experts,
            cfg.moe_topk, cfg.moe_routed_scale, cfg.vocab) == (
        11264, 1408, 2816, 64, 6, 2.446, 163840)
    assert cfg.held == (0, 64) and not cfg.tie_embeddings


@pytest.mark.parametrize("held", [(), (2, 3)])
def test_engine_prefill_matches_the_reference(held):
    """(a) The engine's miss prefill, one chip's share of the experts or
    all of them, gives the reference's last-position logits."""
    cfg = _cfg(held)
    params = _weights(cfg, seed=1)
    cat = np.random.default_rng(0).standard_normal((64, 4)).astype(
        np.float32)
    eng = SimCacheEngine(cfg, params, EngineConfig(), cat)
    toks = _tokens(cfg, 8, seed=2)
    got = np.asarray(eng.prefill(toks))
    want = np.asarray(ref.last_logits(params, toks, _ref_cfg(cfg)))
    assert got.shape == want.shape == (8, cfg.vocab)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-4 * np.abs(want).max())
    rows = np.asarray(eng._expert_rows[0])
    assert rows.shape == (cfg.n_layers - 1, cfg.held[1])


def test_expert_shares_add_up_to_the_uncut_layer():
    """(b) Over the four shares of 2 experts, the held experts' parts
    summed, with the shared experts counted once, are the uncut layer of
    the reference, and every assignment is computed by one share."""
    cfg = _cfg()
    params = _weights(cfg, seed=3)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["b0_attn_moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, cfg.d_model))
    h = layers.rms_norm(x, p["moe_norm"], cfg.norm_eps)
    shared = layers.swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"])
    total, rows = -3 * shared, 0
    for first in range(0, 8, 2):
        part = dict(p, **{k: p[k][first:first + 2]
                          for k in ("we_gate", "we_up", "we_down")})
        y, _, r = transformer._moe(_cfg((first, 2)), part, x, NO_SHARD,
                                   "prefill")
        total, rows = total + y, rows + int(r.sum())
    want = ref._experts(dict(ref._items(_ref_cfg(cfg))), None, h, p)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=0, atol=1e-5)
    assert rows == 2 * SEQ * cfg.moe_topk


@pytest.mark.parametrize("held", [(), (4, 4)])
def test_a_prompt_prefills_alike_alone_and_beside_other_misses(held):
    """(c) Serving drops no token: a prompt's logits do not depend on the
    misses that share its prefill, though its batch routes 16× its own
    assignments over the same experts."""
    cfg = _cfg(held)
    params = _weights(cfg, seed=5)
    toks = _tokens(cfg, 16, seed=6)
    fwd = jax.jit(model_api.make_prefill_last(cfg))
    alone, rows1 = fwd(params, {"tokens": toks[3:4]})
    batch, rows16 = fwd(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(batch[3]), np.asarray(alone[0]),
                               rtol=0, atol=1e-5)
    assert int(rows16.sum()) > int(rows1.sum())


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sigmoid_routing_chooses_by_biased_scores_and_weighs_by_scores(seed):
    """(d) The choice is the top-k of sigmoid + bias, the weights the
    chosen unbiased sigmoid scores normalised to one, × the scale; the
    reference's gates agree."""
    g = np.random.default_rng(seed)
    x = g.standard_normal((32, 16)).astype(np.float32)
    router = g.standard_normal((16, 8)).astype(np.float32)
    bias = (0.5 * g.standard_normal(8)).astype(np.float32)
    w, idx, _ = moe.route(jnp.asarray(x), jnp.asarray(router), 3,
                          rule="sigmoid", bias=jnp.asarray(bias), scale=2.5)
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ router)))
    pick = np.argsort(-(s + bias), axis=1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), 1),
                                  np.sort(pick, 1))
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(np.asarray(w),
                               2.5 * chosen / chosen.sum(1, keepdims=True),
                               rtol=1e-5)
    # the bias moves the choice and not the weights
    assert not np.array_equal(np.sort(pick, 1),
                              np.sort(np.argsort(-s, 1)[:, :3], 1))
    dense = np.zeros((32, 8))
    np.put_along_axis(dense, np.asarray(idx), np.asarray(w), 1)
    got = ref.gates(jnp.asarray(x), jnp.asarray(router), jnp.asarray(bias),
                    {"num_experts_per_tok": 3, "norm_topk_prob": True,
                     "routed_scaling_factor": 2.5})
    np.testing.assert_allclose(np.asarray(got), dense, rtol=1e-5, atol=1e-7)
