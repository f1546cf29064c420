"""Compile the main-path kernels and the repository model for a TPU v5e
that is described, not attached.

Interpret mode cannot see misaligned tiles, VMEM overuse, layouts Mosaic
refuses or a program that does not fit the chip's memory; the TPU
compiler can, without a chip. Every program here is compiled for one
chip of a ``v5e:2x2`` topology at the size the serving path runs it:

* the fused multi-level lookup at Q 256 × K 2²⁰ × D 128 (the segment
  fold and the shard-local ``fold_repo=False`` entry), and the l1
  metric, whose per-feature loop must compile in seconds;
* the placement gain kernel at R = O = 8192;
* flash attention at granite-3-2b's head widths;
* the granite-3-2b prefill at its published widths with bf16
  parameters, which must fit the chip's 16 GB;
* the Moonlight-16B-A3B miss prefill (``jit_prefill``: latent
  attention, the held experts' grouped matmuls, the head at the last
  position) at its published widths with 8 of 64 experts held, at the
  row counts its 1,024-token pieces run;
* the programs that lay a split miss prefill's pieces into its
  bucket's logits, the later pieces in place.

The topology is described inside a module fixture, never while the
module is imported: only one process may load the TPU library, and the
test workers each import every test file.
"""
import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.flash_attention.flash import flash_pallas
from repro.kernels.knn.gains import _gains_pallas
from repro.kernels.knn.knn import fused_lookup_pallas
from repro.models import model as model_api

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_lookup(one_chip, metric, K, fold_repo=True):
    f = functools.partial(fused_lookup_pallas, metric=metric, h_repo=1.0,
                          interpret=False, fold_repo=fold_repo,
                          n_feat=128)
    s = functools.partial(_spec, one_chip)
    return jax.jit(f).lower(s((256, 128)), s((K, 128)), s((1, K)),
                            s((4, K), jnp.int32)).compile()


@pytest.mark.parametrize("fold_repo", [True, False])
def test_fused_lookup_compiles_at_2e20_keys(one_chip, fold_repo):
    compiled = _compile_lookup(one_chip, "l2", 1 << 20, fold_repo)
    assert "tpu_custom_call" in compiled.as_text()


def test_l1_fused_lookup_compiles_in_seconds(one_chip):
    t0 = time.perf_counter()
    compiled = _compile_lookup(one_chip, "l1", 4096)
    assert time.perf_counter() - t0 < 30.0
    assert "tpu_custom_call" in compiled.as_text()


def test_gain_kernel_compiles_at_8192(one_chip):
    s = functools.partial(_spec, one_chip)
    f = functools.partial(_gains_pallas, metric="l2", gamma=1.0, br=256,
                          bo=256, interpret=False)
    compiled = jax.jit(f).lower(s((8192, 128)), s((8192, 128)),
                                s((1, 8192)), s((1, 8192)),
                                s((1, 3))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_granite_widths(one_chip):
    cfg = get_config("granite-3-2b")
    groups = cfg.n_heads // cfg.n_kv_heads
    s = functools.partial(_spec, one_chip)
    f = functools.partial(flash_pallas, n_groups=groups,
                          scale=cfg.head_dim ** -0.5, causal=True,
                          kv_len=2048, interpret=False)
    compiled = jax.jit(f).lower(
        s((cfg.n_heads, 2048, cfg.head_dim), jnp.bfloat16),
        s((cfg.n_kv_heads, 2048, cfg.head_dim), jnp.bfloat16),
        s((cfg.n_kv_heads, 2048, cfg.head_dim), jnp.bfloat16)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_granite_prefill_fits_one_chip_with_bf16_params(one_chip):
    """The serving path's miss prefill, 64 prompts × 16 tokens, at the
    published widths: arguments plus temporaries stay under the chip's
    HBM. (With f32 parameters every call also makes a bf16 copy of each
    weight, about 15 GB before a cache key is placed.)"""
    cfg = dataclasses.replace(get_config("granite-3-2b"),
                              param_dtype="bfloat16")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (40, 2048, 49155)
    params = jax.eval_shape(lambda: model_api.init_params(cfg, 0))
    params = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), params)
    tokens = _spec(one_chip, (64, 16), jnp.int32)
    compiled = jax.jit(model_api.make_prefill(cfg)).lower(
        params, {"tokens": tokens}).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, used


@pytest.mark.parametrize("rows", [1, 2, 4, 8, 16, 32])
def test_moonlight_prefill_fits_one_chip(one_chip, rows):
    """The serving path's miss prefill of Moonlight-16B-A3B at its
    published widths, 8 of its 64 routed experts held, bf16, at each row
    count of its 1,024-token pieces: arguments (6.7 GB of weights) plus
    temporaries stay under the chip's HBM, the held experts run as
    grouped-matmul kernels, and the program keeps the name the
    benchmark reads."""
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"),
                              param_dtype="bfloat16", experts_held=(0, 8))
    params = jax.eval_shape(lambda: model_api.init_params(cfg, 0))
    params = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), params)
    tokens = _spec(one_chip, (rows, 1024), jnp.int32)
    compiled = jax.jit(model_api.make_prefill_last(cfg)).lower(
        params, {"tokens": tokens}).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, used
    text = compiled.as_text()
    assert text.startswith("HloModule jit_prefill")
    assert "ragged-dot" in text
    assert compiled.out_info[0].shape == (rows, cfg.vocab)


@pytest.mark.parametrize("rows,piece", [(64, 32), (64, 8), (1024, 16),
                                        (8, 2)])
def test_prefill_pieces_assemble_in_place(one_chip, rows, piece):
    """The programs that lay a split prefill's pieces into its bucket's
    logits, at granite-3-2b's vocabulary in bf16: a first piece's
    last-position logits (half the bucket, or fewer rows, as 3 misses
    run as 2 + 1 in a bucket of 8) into a new (rows, vocab) array, a
    later one at a traced offset into the donated array, which the
    output then aliases."""
    from repro.serve.engine import _first_piece, _next_piece
    vocab = 49155
    logits = _spec(one_chip, (piece, vocab), jnp.bfloat16)
    out = _spec(one_chip, (rows, vocab), jnp.bfloat16)
    for lead in sorted({rows // 2, piece}):
        first = jax.jit(_first_piece, static_argnums=1).lower(
            _spec(one_chip, (lead, vocab), jnp.bfloat16), rows).compile()
        assert first.out_info.shape == (rows, vocab)
    nxt = jax.jit(_next_piece, donate_argnums=0).lower(
        out, logits, _spec(one_chip, (), jnp.int32)).compile()
    # the aliased buffer is the whole output, padded to the chip's tiles
    assert nxt.memory_analysis().alias_size_in_bytes >= rows * vocab * 2
