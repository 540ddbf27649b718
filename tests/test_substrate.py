"""Integration tests: data pipeline, checkpointing, DES, sharding rules."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import AsyncCheckpointer, restore_checkpoint, save_checkpoint
from repro.core import metrics as M
from repro.core.backend import NexusBackend
from repro.core.storage import ObjectStore, RemoteStorage
from repro.data import DataPipeline, SyntheticCorpus
from repro.data.pipeline import CorpusSpec


def make_backend(transport="tcp"):
    store = ObjectStore()
    acct = M.CycleAccount()
    remote = RemoteStorage(store, transport, acct)
    return store, NexusBackend(remote, acct, transport_name=transport)


class TestDataPipeline:
    def test_batches_deterministic_and_complete(self):
        store, be = make_backend()
        spec = CorpusSpec("corpus", vocab_size=1000, shard_tokens=4096,
                          num_shards=4, seed=7)
        corpus = SyntheticCorpus(store, spec)
        corpus.materialize()
        pipe = DataPipeline(corpus, be, batch=4, seq_len=128)
        b1 = pipe.next_batch()
        assert b1["tokens"].shape == (4, 128)
        assert b1["targets"].shape == (4, 128)
        # next-token alignment
        np.testing.assert_array_equal(b1["tokens"][:, 1:],
                                      b1["targets"][:, :-1])
        assert b1["tokens"].max() < 1000

    def test_prefetch_overlap_hides_io(self):
        """With compute between batches, the pipeline never blocks."""
        store, be = make_backend()
        spec = CorpusSpec("corpus", vocab_size=100, shard_tokens=2080,
                          num_shards=8, seed=1)
        corpus = SyntheticCorpus(store, spec)
        corpus.materialize()
        pipe = DataPipeline(corpus, be, batch=4, seq_len=64,
                            prefetch_depth=3)
        time.sleep(0.08)                 # step-0 compile hides the prime
        for _ in range(12):
            pipe.next_batch()
            time.sleep(0.01)             # "compute" hides the fetches
        assert pipe.blocking_waits <= 1  # scheduler jitter headroom
        assert pipe.overlap_efficiency() >= 0.8


class TestCheckpoint:
    def _tiny_state(self):
        return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                "b": jnp.ones((4,), jnp.bfloat16),
                "step": jnp.asarray(3, jnp.int32)}

    def test_sync_roundtrip(self):
        store = ObjectStore()
        state = self._tiny_state()
        save_checkpoint(store, "ck", 3, state)
        step, flat = restore_checkpoint(store, "ck")
        assert step == 3
        np.testing.assert_array_equal(flat["w"], np.asarray(state["w"]))
        assert flat["b"].dtype == np.asarray(state["b"]).dtype

    def test_async_commit_is_atomic(self):
        store, be = make_backend()
        ck = AsyncCheckpointer(be, bucket="ck")
        state = self._tiny_state()
        ck.save(5, state)
        ck.wait()
        step, flat = restore_checkpoint(store, "ck")
        assert step == 5
        np.testing.assert_array_equal(flat["w"], np.asarray(state["w"]))

    def test_restore_via_backend_prefetch(self):
        store, be = make_backend()
        state = self._tiny_state()
        save_checkpoint(store, "ck", 9, state)
        step, flat = restore_checkpoint(store, "ck", backend=be)
        assert step == 9
        assert be.stats["prefetches"] == len(flat)

    def test_latest_pointer_tracks_newest(self):
        store = ObjectStore()
        save_checkpoint(store, "ck", 1, self._tiny_state())
        save_checkpoint(store, "ck", 2, self._tiny_state())
        step, _ = restore_checkpoint(store, "ck")
        assert step == 2


class TestDensitySimulator:
    def test_nexus_beats_baseline_density(self):
        from repro.core.des import DensitySimulator
        results = {}
        for system in ("baseline", "nexus"):
            r = DensitySimulator(system, 320, seed=1, duration_s=40,
                                 warmup_s=8).run()
            results[system] = r
        assert results["nexus"].geomean_slowdown() \
            < results["baseline"].geomean_slowdown()
        assert results["nexus"].cpu_util < results["baseline"].cpu_util
        assert results["nexus"].mem_util < results["baseline"].mem_util

    def test_slo_definition(self):
        from repro.core.des import SimResult
        r = SimResult("x", 1, {"f": [1.0] * 100}, {"f": 0.25}, 0, 0, 0,
                      100, 0)
        assert r.slowdowns()["f"] == pytest.approx(4.0)
        assert r.meets_slo(5.0)
        assert not r.meets_slo(3.0)


class TestShardingRules:
    def test_param_specs_divisible(self):
        """Every leaf of every full config gets a spec whose axes divide
        the dims — the invariant the 40-cell dry-run rests on."""
        from repro.configs import ARCH_IDS, registry
        from repro.launch import sharding as SH
        from repro.models import get_model

        mesh = jax.make_mesh((1, 1), ("data", "model"))

        class FakeMesh:
            shape = {"pod": 2, "data": 16, "model": 16}
            axis_names = ("pod", "data", "model")

        fake = FakeMesh()
        for arch in ARCH_IDS:
            cfg = registry.get(arch)
            shapes = get_model(cfg).param_shapes()
            flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
            for path, leaf in flat:
                spec = SH.param_spec(SH._path_str(path), leaf.shape, fake)
                for dim, axes in zip(leaf.shape, spec):
                    if axes is None:
                        continue
                    axes = (axes,) if isinstance(axes, str) else axes
                    size = 1
                    for a in axes:
                        size *= fake.shape[a]
                    assert dim % size == 0, (arch, SH._path_str(path),
                                             leaf.shape, spec)

    def test_fsdp_actually_shards_big_leaves(self):
        """The embed and attention weights must NOT be replicated."""
        from repro.configs import registry
        from repro.launch import sharding as SH

        class FakeMesh:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")

        fake = FakeMesh()
        cfg = registry.get("llama3-8b")
        spec = SH.param_spec("embed", (cfg.vocab_size, cfg.d_model), fake)
        assert spec != ()
        spec = SH.param_spec("layers/attn/wq",
                             (cfg.num_layers, cfg.d_model,
                              cfg.num_heads * cfg.head_dim), fake)
        from jax.sharding import PartitionSpec as P
        assert spec == P(None, "data", "model")
