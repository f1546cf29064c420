"""Per-architecture smoke tests (deliverable f): every assigned arch is
instantiated at a reduced config of the same family and runs one forward
AND one backward (train) step plus a prefill→decode parity check on CPU,
asserting output shapes and no NaNs. Full configs are checked for
parameter-count fidelity against the published sizes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, get_smoke_config, list_archs
from repro.models import model, schema, transformer

ARCHS = list_archs()

PUBLISHED_SIZES = {           # ±5% unless noted
    "jamba-1.5-large-398b": 398e9,
    "deepseek-67b": 67e9,
    "granite-3-2b": 2.5e9,
    "deepseek-coder-33b": 33e9,
    "phi3-medium-14b": 14e9,
    "granite-moe-3b-a800m": 3.3e9,
    "dbrx-132b": 132e9,
    "xlstm-350m": 0.35e9,     # ±40%: block internals are ours (DESIGN.md)
    "whisper-small": 0.244e9,  # ±20%: conv frontend stubbed
    "qwen2-vl-7b": 7.6e9,     # backbone only (vision tower stubbed)
    "moonlight-16b-a3b": 16e9,
}

ACTIVE_SIZES = {
    "jamba-1.5-large-398b": 94e9,
    "granite-moe-3b-a800m": 0.8e9,
    "dbrx-132b": 36e9,
    "moonlight-16b-a3b": 3e9,
}


def make_batch(cfg, rng, B=2, S=24):
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)))}
    if cfg.is_encdec:
        batch["audio_embeds"] = jnp.asarray(
            rng.standard_normal((B, 64, 128)), jnp.float32)
    if cfg.mrope:
        S_img = 8
        batch["image_embeds"] = jnp.asarray(
            rng.standard_normal((B, S_img, 1280)), jnp.float32)
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(S + S_img)[None, None, :], (3, B, S + S_img)
        ).astype(jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finiteness(arch, rng):
    cfg = get_smoke_config(arch)
    params = model.init_params(cfg, 0)
    batch = make_batch(cfg, rng)
    loss, metrics = jax.jit(model.make_train_forward(cfg))(params, batch)
    assert np.isfinite(float(loss)), arch
    assert float(metrics["ce"]) == pytest.approx(np.log(cfg.padded_vocab),
                                                 rel=0.15)


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_gradient_step(arch, rng):
    """Backward pass produces finite grads for every leaf; loss drops
    after one SGD step on the same batch."""
    cfg = get_smoke_config(arch)
    params = model.init_params(cfg, 0)
    batch = make_batch(cfg, rng)
    fwd = model.make_train_forward(cfg)
    (loss0, _), grads = jax.jit(
        jax.value_and_grad(fwd, has_aux=True))(params, batch)
    flat = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in flat), arch
    params2 = jax.tree.map(lambda p, g: p - 0.3 * g, params, grads)
    loss1, _ = jax.jit(fwd)(params2, batch)
    assert float(loss1) < float(loss0), (arch, float(loss0), float(loss1))


@pytest.mark.parametrize("arch", [a for a in ARCHS if a != "whisper-small"])
def test_prefill_decode_parity(arch, rng):
    cfg = get_smoke_config(arch)
    params = model.init_params(cfg, 0)
    B, S = 2, 24
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)))
    full, _, _ = transformer.forward(cfg, params, {"tokens": toks},
                                     mode="train")
    Sp = S - 4
    logits, caches = jax.jit(model.make_prefill(cfg))(
        params, {"tokens": toks[:, :Sp]})
    caches = model._pad_caches(cfg, caches, S)
    step = jax.jit(model.make_serve_step(cfg))
    errs = [float(jnp.max(jnp.abs(logits - full[:, :Sp])))]
    for t in range(4):
        lg, caches = step(params, toks[:, Sp + t:Sp + t + 1], caches, Sp + t)
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - full[:, Sp + t]))))
    assert max(errs) < 2e-3, (arch, errs)


def test_whisper_parity(rng):
    cfg = get_smoke_config("whisper-small")
    params = model.init_params(cfg, 0)
    from repro.models import encdec
    B, S = 2, 20
    audio = jnp.asarray(rng.standard_normal((B, 64, 128)), jnp.float32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)))
    full, _, _ = encdec.encdec_forward(
        cfg, params, {"tokens": toks, "audio_embeds": audio}, mode="train")
    Sp = S - 4
    lp, caches = jax.jit(model.make_prefill(cfg))(
        params, {"tokens": toks[:, :Sp], "audio_embeds": audio})
    caches = model._pad_caches(cfg, caches, S)
    step = jax.jit(model.make_serve_step(cfg))
    errs = [float(jnp.max(jnp.abs(lp - full[:, :Sp])))]
    for t in range(4):
        lg, caches = step(params, toks[:, Sp + t:Sp + t + 1], caches, Sp + t)
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - full[:, Sp + t]))))
    assert max(errs) < 2e-3, errs


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_matches_published(arch):
    cfg = get_config(arch)
    n = schema.param_count(cfg)
    target = PUBLISHED_SIZES[arch]
    tol = {"xlstm-350m": 0.4, "whisper-small": 0.2}.get(arch, 0.05)
    assert abs(n - target) / target < tol, (arch, n, target)


@pytest.mark.parametrize("arch", sorted(ACTIVE_SIZES))
def test_active_param_count(arch):
    cfg = get_config(arch)
    n = schema.active_param_count(cfg)
    assert abs(n - ACTIVE_SIZES[arch]) / ACTIVE_SIZES[arch] < 0.15, n


@pytest.mark.parametrize("arch", ["xlstm-350m", "jamba-1.5-large-398b"])
def test_subquadratic_flag(arch):
    assert get_config(arch).subquadratic     # long_500k eligibility


def test_full_attention_archs_marked():
    for a in ARCHS:
        cfg = get_config(a)
        if a not in ("xlstm-350m", "jamba-1.5-large-398b"):
            assert not cfg.subquadratic
