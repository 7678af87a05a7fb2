"""Data pipeline, checkpointing, sharding rules — the distributed substrate."""
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.data import DataConfig, SyntheticLM
from repro.checkpoint import Checkpointer
from repro.parallel.sharding import (DEFAULT_RULES, FSDP_RULES, spec_for,
                                     batch_axes)
from jax.sharding import PartitionSpec as P


class TestData:
    def test_deterministic(self):
        d = SyntheticLM(DataConfig(seed=3))
        b1, b2 = d.batch(7), d.batch(7)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_steps_differ(self):
        d = SyntheticLM(DataConfig(seed=3))
        assert not np.array_equal(d.batch(1)["tokens"], d.batch(2)["tokens"])

    def test_sharding_partitions_batch(self):
        cfg = DataConfig(global_batch=8)
        d = SyntheticLM(cfg)
        shards = [d.batch(0, s, 4) for s in range(4)]
        assert all(s["tokens"].shape[0] == 2 for s in shards)
        # different shards get different data
        assert not np.array_equal(shards[0]["tokens"], shards[1]["tokens"])

    def test_labels_shift(self):
        d = SyntheticLM(DataConfig())
        b = d.batch(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_learnable_structure(self):
        cfg = DataConfig(determinism=0.9)
        d = SyntheticLM(cfg)
        b = d.batch(0)
        nxt = (d.a * b["tokens"] + d.b) % cfg.vocab_size
        frac = (nxt == b["labels"]).mean()
        assert 0.8 < frac < 1.0
        assert 0 < d.entropy_floor() < np.log(cfg.vocab_size)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path, rng):
        ck = Checkpointer(str(tmp_path))
        tree = {"a": jnp.asarray(rng.standard_normal((4, 4)), jnp.float32),
                "b": {"c": jnp.arange(5)}}
        ck.save(10, tree, blocking=True)
        step, got = ck.restore_latest(tree)
        assert step == 10
        np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))

    def test_latest_and_gc(self, tmp_path, rng):
        ck = Checkpointer(str(tmp_path), keep=2)
        tree = {"a": jnp.zeros(3)}
        for s in (1, 2, 3, 4):
            ck.save(s, tree, blocking=True)
        assert ck.latest_step() == 4
        assert ck.all_steps() == [3, 4]          # old ones GC'd

    def test_integrity_check_fails_on_corruption(self, tmp_path, rng):
        ck = Checkpointer(str(tmp_path))
        tree = {"a": jnp.asarray(rng.standard_normal(16), jnp.float32)}
        ck.save(1, tree, blocking=True)
        # corrupt a leaf crc in the manifest
        man = os.path.join(str(tmp_path), "step_0000000001", "manifest.json")
        m = json.load(open(man))
        m["leaves"][0]["crc32"] ^= 0xDEAD
        json.dump(m, open(man, "w"))
        with pytest.raises(IOError, match="integrity"):
            ck.restore(1, tree)

    def test_async_save(self, tmp_path, rng):
        ck = Checkpointer(str(tmp_path))
        tree = {"a": jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)}
        ck.save(5, tree, blocking=False)
        ck.wait()
        assert ck.latest_step() == 5


class TestShardingRules:
    def _mesh(self):
        # 1-device "production-shaped" mesh: rule logic is shape-independent
        from repro.launch.mesh import make_mesh
        return make_mesh((1, 1), ("data", "model"))

    def test_divisibility_fallback(self):
        mesh = self._mesh()
        # 1 divides everything on the 1-dev mesh; use a fake axis size via
        # direct rule evaluation instead:
        spec = spec_for((7, 64), ("vocab", "embed"), mesh, DEFAULT_RULES)
        assert isinstance(spec, P)

    def test_priority_kv_over_seq(self):
        mesh = self._mesh()
        # kv divisible -> takes "model"; seq then can't reuse it
        spec = spec_for((2, 128, 16, 64),
                        ("cache_batch", "cache_seq", "cache_kv", None),
                        mesh, DEFAULT_RULES)
        assert spec[2] == "model" or spec[2] is None
        # a mesh axis may appear at most once
        used = [s for s in spec if s is not None]
        flat = []
        for u in used:
            flat.extend(u if isinstance(u, tuple) else (u,))
        assert len(flat) == len(set(flat))

    def test_fsdp_rules_shard_embed(self):
        assert FSDP_RULES.table["embed"] == [("data",)]
        assert DEFAULT_RULES.table["embed"] == []

    def test_batch_axes(self):
        mesh = self._mesh()
        assert batch_axes(mesh) == ("data",)


class TestHloStats:
    def test_collective_parse(self):
        from repro.analysis import collective_stats
        hlo = """
  %ar = f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} %x), replica_groups={{0,1,2,3}}
  %ag = bf16[16,256]{1,0} all-gather(bf16[4,256]{1,0} %y), replica_groups=[4,8]<=[32]
  %cp = f32[4]{0} collective-permute(f32[4]{0} %z), source_target_pairs={{0,1}}
"""
        s = collective_stats(hlo)
        assert s["all-reduce"]["count"] == 1
        np.testing.assert_allclose(s["all-reduce"]["bytes"],
                                   2 * 0.75 * 8 * 128 * 4)
        np.testing.assert_allclose(s["all-gather"]["bytes"],
                                   (7 / 8) * 16 * 256 * 2)
        assert s["collective-permute"]["bytes"] == 16.0
        assert s["total_bytes"] > 0


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
d = enable_compile_cache()
if d != REPO_CACHE_DIR:
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
print(d)
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "repo"])
def test_compile_cache_location(tmp_path, env_dir):
    """$JAX_COMPILATION_CACHE_DIR wins and receives the entries; without
    it the cache is the fixed <repo>/.jax_cache (this test writes
    nothing there)."""
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(root, "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got, configured = out.stdout.split()
    want = (str(tmp_path) if env_dir
            else os.path.abspath(os.path.join(root, ".jax_cache")))
    assert got == configured == want
    if env_dir:
        assert os.listdir(tmp_path), "no cache entry written"
