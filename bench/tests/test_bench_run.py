"""A whole run of a tiny cell on the CPU, past the look for a chip: it
comes out correct, and with the timed path broken underneath it does
not. Also: without a TPU, or with only the benchmark's own files, the
command exits non-zero and prints no result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench_fixtures import BENCH, REPO, tiny_root  # noqa: F401
import harness  # noqa: E402
import run  # noqa: E402

PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
         "hbm_bytes_per_s": 1e11}
SEED = 2 ** 31 + 77


def _run(root: Path, cell: str, seconds: float = 0.6) -> dict:
    return run.execute(harness.load_cell(cell, root), SEED, seconds, False,
                       jax.devices(), PEAKS)


@pytest.mark.parametrize("cell", ["tiny.tiny-sat", "tiny.tiny-poisson"])
def test_a_sound_run_is_correct(tiny_root, cell):
    res = _run(tiny_root, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    names = set(res["metrics"])
    assert names == ({"req_per_s", "setup_s"} if cell.endswith("sat")
                     else {"req_per_s", "setup_s", "p50_ms"})
    assert all(m["value"] > 0 for m in res["metrics"].values())


def _unchanged_state(monkeypatch):
    from repro.serve.engine import SimCacheEngine
    serve = SimCacheEngine.serve

    def broken(self, ids, prompts, ingress_ids=None):
        import dataclasses
        before = dataclasses.replace(self.stats)
        out, _ = serve(self, ids, prompts, ingress_ids)
        self.stats = before
        return out, self.stats
    monkeypatch.setattr(SimCacheEngine, "serve", broken)


def _half_batch(monkeypatch):
    from repro.serve.engine import SimCacheEngine
    serve = SimCacheEngine.serve

    def broken(self, ids, prompts, ingress_ids=None):
        h = max(len(ids) // 2, 1)
        out, st = serve(self, ids[:h], prompts[:h])
        return out + [None] * (len(ids) - h), st
    monkeypatch.setattr(SimCacheEngine, "serve", broken)


def _token_altered(monkeypatch):
    from repro.serve.engine import SimCacheEngine
    prefill = SimCacheEngine.prefill
    monkeypatch.setattr(SimCacheEngine, "prefill",
                        lambda self, t: -prefill(self, t))


def _answer_altered(monkeypatch):
    from repro.core.simcache import SimCacheNetwork
    lookup = SimCacheNetwork._lookup_fused

    def broken(self, q):
        res = lookup(self, q)
        res.payload = res.payload + (res.payload >= 0)
        return res
    monkeypatch.setattr(SimCacheNetwork, "_lookup_fused", broken)


# the cell runs on one chip, so the fault of an exchange between chips
# left out cannot occur in it
FAULTS = {"state_unchanged": (_unchanged_state, "requests_diff"),
          "half_batch": (_half_batch, "requests_diff"),
          "token_altered": (_token_altered, "logit_gap"),
          "answer_altered": (_answer_altered, "lookup_mismatch")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    res = _run(tiny_root, "tiny.tiny-sat")
    assert not res["correct"]
    c = res["checks"][number]
    assert not (isinstance(c["value"], (int, float))
                and c["value"] <= c["limit"]), res["checks"]


def _cli(cwd: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resp-1m.mix",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _cli(REPO, {})
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_benchmark_files_alone_are_not_enough(tmp_path):
    """Past the look for a chip, a checkout without the program fails
    before any result."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    code = ("import sys, jax; sys.argv = ['bench/run.py']; "
            "sys.path.insert(0, 'bench'); import run, harness; "
            "cell = harness.load_cell('resp-1m.mix'); "
            "print(run.execute(cell, 1, 1.0, False, jax.devices(), {}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_control_is_not_correct_at_test_size(tiny_root):
    """The control (the references one precision step down) read on the
    tiny cell fails one of its limits."""
    import control
    cell = harness.load_cell("tiny.tiny-sat", tiny_root)
    r = control.readings(cell, SEED, 0.5, log=lambda m: None)
    ok, _ = harness.check.verdict(
        {k: v for k, v in r["control"].items() if v is not None},
        {k: cell.limits[k] for k in r["control"]})
    assert not ok, r
