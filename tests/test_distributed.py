"""Distribution tests that need >1 device: run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest
process must keep seeing 1 device for the smoke tests)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        assert jax.device_count() == 8
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """pjit train step on a 4×2 mesh must produce the same loss as the
    unsharded step (SPMD is semantics-preserving)."""
    run_in_subprocess("""
        import dataclasses
        from repro.configs.registry import get_smoke_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.sharding import MeshShardPolicy
        from repro.models import model, schema
        from repro.models.sharding_api import NO_SHARD

        cfg = get_smoke_config("granite-3-2b")
        mesh = make_debug_mesh(4, 2)
        policy = MeshShardPolicy.create(cfg, mesh, "train")
        params = model.init_params(cfg, 0)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32))),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)))}
        l_ref, _ = jax.jit(model.make_train_forward(cfg, NO_SHARD))(params, batch)
        with mesh:
            shard_tree = policy.param_sharding_tree(schema.param_schema(cfg))
            p_sh = jax.device_put(params, shard_tree)
            b_sh = jax.device_put(batch, policy.batch_sharding_tree(
                {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in batch.items()}))
            l_sh, _ = jax.jit(model.make_train_forward(cfg, policy))(p_sh, b_sh)
        err = abs(float(l_ref) - float(l_sh))
        assert err < 2e-3, (float(l_ref), float(l_sh))
        print("sharded == unsharded:", float(l_ref), float(l_sh))
    """)


def test_moe_expert_parallel_matches():
    run_in_subprocess("""
        from repro.configs.registry import get_smoke_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.sharding import MeshShardPolicy
        from repro.models import model, schema
        from repro.models.sharding_api import NO_SHARD

        cfg = get_smoke_config("dbrx-132b")   # 4 experts, EP over model=2
        mesh = make_debug_mesh(4, 2)
        policy = MeshShardPolicy.create(cfg, mesh, "train")
        params = model.init_params(cfg, 0)
        rng = np.random.default_rng(1)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32))),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)))}
        l_ref, _ = jax.jit(model.make_train_forward(cfg, NO_SHARD))(params, batch)
        with mesh:
            p_sh = jax.device_put(
                params, policy.param_sharding_tree(schema.param_schema(cfg)))
            l_sh, _ = jax.jit(model.make_train_forward(cfg, policy))(p_sh, batch)
        assert abs(float(l_ref) - float(l_sh)) < 2e-3
        print("EP ok", float(l_ref), float(l_sh))
    """)


def test_compressed_crosspod_mean():
    run_in_subprocess("""
        from repro.ft.compress import compressed_crosspod_mean
        mesh = make_mesh((2, 4), ("pod", "data"))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal((16, 64)).astype(np.float32))
        with mesh:
            out = compressed_crosspod_mean({"g": g}, mesh)["g"]
        # replicated input → mean is the identity, up to int8 error
        rel = np.max(np.abs(np.asarray(out) - np.asarray(g))) / \
            np.max(np.abs(np.asarray(g)))
        assert rel < 0.02, rel
        print("compressed mean rel err", rel)
    """)


def test_elastic_remesh_restore():
    """Checkpoint on a 4×2 mesh, restore onto 2×4 and 8×1 — losses agree."""
    run_in_subprocess("""
        import tempfile
        from repro.checkpoint import save, restore_for_mesh
        from repro.configs.registry import get_smoke_config
        from repro.ft.elastic import plan_mesh, reshard_plan
        from repro.launch.sharding import MeshShardPolicy
        from repro.models import model, schema

        cfg = get_smoke_config("granite-3-2b")
        params = model.init_params(cfg, 0)
        d = tempfile.mkdtemp()
        save(d, 5, {"params": params})
        rng = np.random.default_rng(2)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16))),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)))}
        losses = []
        for (nd, nm) in ((4, 2), (2, 4), (8, 1)):
            mesh = make_mesh((nd, nm), ("data", "model"))
            policy = MeshShardPolicy.create(cfg, mesh, "train")
            tree = {"params": policy.param_sharding_tree(
                schema.param_schema(cfg))}
            step, state = restore_for_mesh(d, tree)
            assert step == 5
            with mesh:
                loss, _ = jax.jit(model.make_train_forward(cfg, policy))(
                    state["params"], batch)
            losses.append(float(loss))
        assert max(losses) - min(losses) < 2e-3, losses
        print("elastic restore ok", losses)
    """)


def test_decode_kv_seq_sharding():
    """Decode with the KV-cache sequence axis sharded over model must
    match unsharded decode (distributed flash-decode semantics)."""
    run_in_subprocess("""
        from repro.configs.registry import get_smoke_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.sharding import MeshShardPolicy
        from repro.models import model, schema, transformer
        from repro.models.sharding_api import NO_SHARD

        cfg = get_smoke_config("granite-3-2b")
        params = model.init_params(cfg, 0)
        rng = np.random.default_rng(3)
        B, S = 4, 16
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)))
        logits, caches = jax.jit(model.make_prefill(cfg))(
            params, {"tokens": toks[:, :S-1]})
        caches = model._pad_caches(cfg, caches, S)
        l_ref, _ = jax.jit(model.make_serve_step(cfg))(
            params, toks[:, S-1:], caches, S-1)

        mesh = make_debug_mesh(4, 2)
        policy = MeshShardPolicy.create(cfg, mesh, "decode")
        with mesh:
            p_sh = jax.device_put(
                params, policy.param_sharding_tree(schema.param_schema(cfg)))
            c_sh = jax.device_put(caches, policy.cache_sharding_tree(
                jax.eval_shape(lambda: caches)))
            l_sh, _ = jax.jit(model.make_serve_step(cfg, policy))(
                p_sh, toks[:, S-1:], c_sh, S-1)
        err = float(jnp.max(jnp.abs(l_ref - l_sh)))
        assert err < 2e-3, err
        print("kv_seq decode ok", err)
    """)
