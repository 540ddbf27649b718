"""SharedCache (ISSUE 10): the host-side tiered payload cache.

Four layers of evidence:

* `CacheState` unit behavior — deterministic LRU/clock/seeded-random
  eviction, hint-gated admission, content-key refcounting/dedup,
  write-allocation, staleness invalidation through the single
  `lookup(valid=...)` code path;
* `SharedCache` tier behavior — arena parking with plain-bytes
  fallback (counters independent of allocator luck), etag
  revalidation against the live store, immutable hit payloads;
* the PlanVerify overlay checker — `des.cache_overlay` output verifies
  clean and seeded corruptions map to the right `V-CACHE-*` codes;
* the cross-executor count-parity contract — the DES's hit/miss/
  eviction counters are a replay-verified prediction of the threaded
  `WorkerNode`'s on the same serial trace, in both the no-eviction and
  the eviction-pressure regime, and the ml_suite KV/weights chains
  become hits after the first invocation on a node in BOTH executors.
"""
import os
import sys
import threading

import pytest

from repro.core import workloads as W
from repro.core.arena import ArenaError, Slot, TenantArena
from repro.core.cache import CacheSpec, CacheState, SharedCache
from repro.core.des import DensitySimulator, _build_bundle, cache_overlay
from repro.core.runtime import WorkerNode
from repro.core.storage import ObjectStore
from repro.core.workloads import (ComputeSegment, Get, IOProfile, Put,
                                  Workload, _single_io_handler, _digest_n)

MB = 1024 * 1024


# ----------------------------------------------------------------- spec

class TestCacheSpec:
    def test_defaults_validate(self):
        s = CacheSpec()
        assert s.capacity_bytes == 64 * MB

    @pytest.mark.parametrize("kw", [
        dict(policy="mru"), dict(admit="never"),
        dict(capacity_mb=0.0), dict(hit_gbps=0.0),
    ])
    def test_rejects_bad_policy(self, kw):
        with pytest.raises(ValueError):
            CacheSpec(**kw)

    def test_hit_duration_scales_with_size(self):
        s = CacheSpec(hit_base_s=1e-6, hit_gbps=80.0)
        assert s.hit_duration_s(0) == 1e-6
        assert s.hit_duration_s(10 * MB) > s.hit_duration_s(MB)


# ---------------------------------------------------------- CacheState

def _spec(**kw):
    kw.setdefault("capacity_mb", 1.0)
    return CacheSpec(**kw)


class TestCacheState:
    def test_miss_then_fill_then_hit(self):
        st = CacheState(_spec())
        assert st.lookup("a") is None
        assert st.fill("a", "ck-a", 1000)
        assert st.lookup("a") == "ck-a"
        snap = st.snapshot()
        assert (snap["lookups"], snap["hits"], snap["misses"]) == (2, 1, 1)
        assert snap["used_bytes"] == 1000

    def test_admission_hinted_rejects_unhinted(self):
        st = CacheState(_spec(admit="hinted"))
        assert not st.fill("a", "ck", 100, hinted=False)
        assert st.lookup("a") is None
        st2 = CacheState(_spec(admit="all"))
        assert st2.fill("a", "ck", 100, hinted=False)
        assert st2.lookup("a") == "ck"

    def test_oversized_object_rejected(self):
        st = CacheState(_spec(capacity_mb=1.0))
        assert not st.fill("big", "ck", 2 * MB)
        assert st.snapshot()["admitted"] == 0

    def test_lru_evicts_least_recently_used(self):
        st = CacheState(_spec(capacity_mb=1.0))
        third = MB // 3
        for k in ("a", "b", "c"):
            st.fill(k, f"ck-{k}", third)
        st.lookup("a")                       # a is now MRU
        st.fill("d", "ck-d", third)          # must evict b, not a
        assert st.lookup("a") is not None
        assert st.lookup("b") is None
        assert st.snapshot()["evictions"] == 1

    def test_clock_second_chance(self):
        st = CacheState(_spec(capacity_mb=1.0, policy="clock"))
        third = MB // 3
        for k in ("a", "b", "c"):
            st.fill(k, f"ck-{k}", third)
        st.lookup("a")                       # reference bit protects a
        st.fill("d", "ck-d", third)          # hand skips a, evicts b
        assert st.lookup("a") is not None
        assert st.lookup("b") is None

    def test_random_policy_is_seeded(self):
        def run(seed):
            st = CacheState(_spec(capacity_mb=1.0, policy="random",
                                  seed=seed))
            for i in range(8):
                st.fill(f"k{i}", f"ck{i}", MB // 3)
            return sorted(lk for lk in ("k%d" % i for i in range(8))
                          if st.lookup(lk) is not None)

        assert run(1) == run(1)              # same seed: same victims
        # the hit counters the contract pins stay deterministic too
        a = CacheState(_spec(capacity_mb=1.0, policy="random", seed=5))
        b = CacheState(_spec(capacity_mb=1.0, policy="random", seed=5))
        for st in (a, b):
            for i in range(8):
                st.fill(f"k{i}", f"ck{i}", MB // 3)
                st.lookup(f"k{i % 3}")
        assert a.snapshot() == b.snapshot()

    def test_content_dedup_refcounts(self):
        freed = []
        st = CacheState(_spec(), on_free=freed.append)
        st.fill("t1/w", "shard", 1000)
        st.fill("t2/w", "shard", 1000)       # same content: no new bytes
        snap = st.snapshot()
        assert snap["used_bytes"] == 1000
        assert snap["dedup_bytes"] == 1000
        assert snap["unique_content"] == 1
        st.invalidate("t1/w")
        assert freed == []                   # t2 still references it
        st.invalidate("t2/w")
        assert freed == ["shard"]
        assert st.snapshot()["used_bytes"] == 0

    def test_write_allocate_switch(self):
        st = CacheState(_spec(write_allocate=False))
        assert not st.write("out", "ck", 100)
        assert st.snapshot() ["writes"] == 1
        assert st.lookup("out") is None
        st2 = CacheState(_spec())
        assert st2.write("out", "ck", 100)
        assert st2.lookup("out") == "ck"

    def test_write_invalidates_even_without_allocation(self):
        """A durable PUT is authoritative staleness evidence: with
        write-allocation off, the overwrite must still evict the
        resident old-content entry instead of leaving correctness to
        etag revalidation."""
        st = CacheState(_spec(write_allocate=False))
        assert st.fill("k", "ck-v1", 100)
        assert not st.write("k", "ck-v2", 100)
        assert st.lookup("k") is None
        assert st.snapshot()["used_bytes"] == 0

    def test_racing_fill_reports_no_insert(self):
        """The second of two racing fills must learn it lost — its
        bytes/etag may belong to a different object version and must
        not be bound to the winner's entry."""
        st = CacheState(_spec())
        assert st.fill("k", "ck-v1", 100)
        assert not st.fill("k", "ck-v2", 100)
        assert st.lookup("k") == "ck-v1"
        assert st.snapshot()["admitted"] == 1

    def test_write_overwrites_existing_entry(self):
        st = CacheState(_spec())
        st.write("out", "ck-v1", 100)
        st.write("out", "ck-v2", 200)
        assert st.lookup("out") == "ck-v2"
        assert st.snapshot()["used_bytes"] == 200

    def test_stale_valid_callback_invalidates(self):
        st = CacheState(_spec())
        st.fill("a", "ck", 100)
        assert st.lookup("a", valid=lambda lk, ck: False) is None
        snap = st.snapshot()
        assert snap["stale_invalidations"] == 1
        assert snap["misses"] == 1 and snap["entries"] == 0

    def test_replay_determinism(self):
        """Same op sequence in, same counters out — the property the
        whole cross-executor contract rests on."""
        def drive(st):
            for i in range(40):
                lk = f"k{i % 7}"
                if st.lookup(lk) is None:
                    st.fill(lk, f"ck{i % 5}", (i % 5 + 1) * 100_000,
                            hinted=(i % 3 != 0))
                if i % 4 == 0:
                    st.write(f"out{i}", f"cko{i % 2}", 150_000)
            return st.snapshot()

        a = drive(CacheState(_spec(capacity_mb=1.0)))
        b = drive(CacheState(_spec(capacity_mb=1.0)))
        assert a == b


# --------------------------------------------------------- SharedCache

class TestSharedCache:
    def _store(self):
        store = ObjectStore()
        store.put("in", "k", b"x" * 4096)
        return store

    def test_fill_then_hit_returns_payload(self):
        store = self._store()
        cache = SharedCache(CacheSpec(capacity_mb=1.0))
        etag = store.head("in", "k").etag
        assert cache.get("t", "in", "k", store) is None
        cache.fill("t", "in", "k", store.get("in", "k"), 4096,
                   hinted=True, etag=etag)
        data = cache.get("t", "in", "k", store)
        assert data == b"x" * 4096
        snap = cache.snapshot()
        assert snap["hits"] == 1 and snap["misses"] == 1

    def test_stale_etag_never_served(self):
        """A re-driven PUT bumps the object's etag; the next cache GET
        must revalidate and miss, never serve the old bytes."""
        store = self._store()
        cache = SharedCache(CacheSpec(capacity_mb=1.0))
        cache.fill("t", "in", "k", store.get("in", "k"), 4096,
                   hinted=True, etag=store.head("in", "k").etag)
        store.put("in", "k", b"y" * 4096)    # new version lands
        assert cache.get("t", "in", "k", store) is None
        assert cache.snapshot()["stale_invalidations"] == 1
        # the refreshed fill serves the new version
        cache.fill("t", "in", "k", store.get("in", "k"), 4096,
                   hinted=True, etag=store.head("in", "k").etag)
        assert cache.get("t", "in", "k", store) == b"y" * 4096

    def test_deleted_object_invalidates(self):
        store = self._store()
        cache = SharedCache(CacheSpec(capacity_mb=1.0))
        cache.fill("t", "in", "k", store.get("in", "k"), 4096,
                   hinted=True, etag=store.head("in", "k").etag)
        store.delete("in", "k")
        assert cache.get("t", "in", "k", store) is None

    def test_arena_fallback_keeps_counters_identical(self):
        """Arena exhaustion must degrade the *tier*, never the
        *counters*: a 0-slack arena and a roomy one produce identical
        CacheState snapshots over the same trace."""
        def drive(arena_mb):
            store = ObjectStore()
            cache = SharedCache(CacheSpec(capacity_mb=4.0),
                                arena_mb=arena_mb)
            for i in range(6):
                key = f"k{i}"
                store.put("in", key, bytes([i]) * (512 * 1024))
                cache.get("t", "in", key, store)
                cache.fill("t", "in", key, store.get("in", key),
                           512 * 1024, hinted=True,
                           etag=store.head("in", key).etag)
                cache.get("t", "in", key, store)
            return cache

        small, big = drive(0.25), drive(16.0)
        assert small.arena_fallbacks > 0
        assert big.arena_fallbacks == 0
        a, b = small.state.snapshot(), big.state.snapshot()
        assert a == b

    def test_hits_hand_out_immutable_copies(self):
        """Mutating a hit's bytes must never corrupt the cached copy
        (arena slots are shared memory — hits are copies)."""
        store = self._store()
        cache = SharedCache(CacheSpec(capacity_mb=1.0))
        cache.fill("t", "in", "k", store.get("in", "k"), 4096,
                   hinted=True, etag=store.head("in", "k").etag)
        first = bytearray(cache.get("t", "in", "k", store))
        first[:4] = b"zzzz"
        assert cache.get("t", "in", "k", store) == b"x" * 4096

    def test_losing_racer_never_rebinds_etag_or_leaks_payload(self):
        """Two concurrent misses straddling a PUT: racer A fills the
        old version first; racer B (holding the new bytes + new etag)
        loses the fill race. B's etag must NOT be stamped onto A's
        entry (that hit would serve v1 while revalidating as v2), and
        B's payload must not be parked under an unreferenced content
        key (arena slot leak)."""
        store = self._store()
        cache = SharedCache(CacheSpec(capacity_mb=1.0))
        v1, e1 = store.get_with_meta("in", "k")
        assert cache.fill("t", "in", "k", v1, 4096,
                          hinted=True, etag=e1.etag)
        store.put("in", "k", b"y" * 4096)        # PUT between the racers
        v2, e2 = store.get_with_meta("in", "k")
        assert not cache.fill("t", "in", "k", v2, 4096,
                              hinted=True, etag=e2.etag)
        # entry still binds v1 to v1's etag: revalidation must miss,
        # never serve the old bytes under the new version's etag
        assert cache.get("t", "in", "k", store) is None
        assert cache.snapshot()["stale_invalidations"] == 1
        # no orphan payload parked for the losing racer's content key
        assert len(cache._payload) == 0          # invalidation freed v1's
        assert cache._etag == {}

    def test_put_without_allocation_invalidates_stale_entry(self):
        """write_allocate=False: the write-through declines the new
        bytes but must still drop the resident old-content entry (and
        its parked payload + captured etag)."""
        store = self._store()
        cache = SharedCache(CacheSpec(capacity_mb=1.0,
                                      write_allocate=False))
        v1, m1 = store.get_with_meta("in", "k")
        cache.fill("t", "in", "k", v1, 4096, hinted=True, etag=m1.etag)
        m2 = store.put("in", "k", b"y" * 4096)
        assert not cache.put("t", "in", "k", b"y" * 4096, 4096, m2.etag)
        snap = cache.snapshot()
        assert snap["entries"] == 0 and snap["used_bytes"] == 0
        assert cache._payload == {} and cache._etag == {}

    def test_cross_tenant_dedup_switch(self):
        store = self._store()
        shared = SharedCache(CacheSpec(capacity_mb=1.0))
        private = SharedCache(CacheSpec(capacity_mb=1.0,
                                        cross_tenant=False))
        for cache in (shared, private):
            data = store.get("in", "k")
            etag = store.head("in", "k").etag
            cache.fill("t1", "in", "k", data, 4096, hinted=True,
                       etag=etag)
            cache.fill("t2", "b2", "k", data, 4096, hinted=True,
                       etag=etag)
        assert shared.snapshot()["unique_content"] == 1
        assert shared.snapshot()["dedup_bytes"] == 4096
        assert private.snapshot()["unique_content"] == 2
        assert private.snapshot()["dedup_bytes"] == 0


# ---------------------------------------------- SharedCache.get_into

class TestSharedCacheGetInto:
    """The hit verb that copies the parked payload once, straight into
    the caller's arena slot, pinning the content key for the copy."""

    SIZE = 4096

    def _filled(self, arena_mb=None, capacity_mb=1.0, nominal=SIZE,
                data=b"x" * SIZE):
        store = ObjectStore()
        store.put("in", "k", data)
        cache = SharedCache(CacheSpec(capacity_mb=capacity_mb),
                            arena_mb=arena_mb)
        v, meta = store.get_with_meta("in", "k")
        assert cache.fill("t", "in", "k", v, nominal, hinted=True,
                          etag=meta.etag)
        return store, cache

    @staticmethod
    def _cache_arena(cache):
        return cache._arenas.get("__cache__")

    @pytest.mark.parametrize("arena_mb,kind", [(1.0, Slot),
                                               (0.001, bytes)])
    def test_hit_lands_the_same_bytes_as_get(self, arena_mb, kind):
        store, cache = self._filled(arena_mb=arena_mb)
        (parked,) = cache._payload.values()
        assert isinstance(parked, kind)
        tenant = TenantArena("t", capacity_mb=1.0)
        slot = cache.get_into("t", "in", "k", store, tenant.alloc)
        assert slot.arena is tenant and slot.used == self.SIZE
        assert bytes(slot.view()) == cache.get("t", "in", "k", store)
        snap = cache.snapshot()
        assert (snap["direct_hits"], snap["copied_hits"]) == (1, 1)
        assert cache._pins == {}

    def test_writing_the_slot_never_changes_a_later_hit(self):
        store, cache = self._filled()
        tenant = TenantArena("t", capacity_mb=1.0)
        slot = cache.get_into("t", "in", "k", store, tenant.alloc)
        slot.write(b"z" * self.SIZE)
        again = cache.get_into("t", "in", "k", store, tenant.alloc)
        assert bytes(again.view()) == b"x" * self.SIZE
        assert cache.get("t", "in", "k", store) == b"x" * self.SIZE

    @pytest.mark.parametrize("arena_mb", [0.25, 16.0])
    def test_state_counters_match_get(self, arena_mb):
        """Hits, misses, a stale invalidation and evictions drive
        `CacheState` identically through either hit verb."""
        def drive(verb):
            store = ObjectStore()
            cache = SharedCache(CacheSpec(capacity_mb=2.0),
                                arena_mb=arena_mb)
            tenant = TenantArena("t", capacity_mb=4.0)

            def hit(key):
                if verb == "get":
                    return cache.get("t", "in", key, store)
                slot = cache.get_into("t", "in", key, store, tenant.alloc)
                if slot is None:
                    return None
                data = bytes(slot.view())
                slot.release()
                return data

            for i in range(3):
                store.put("in", f"k{i}", bytes([i]) * (256 * 1024))
            for key in ("k0", "k1", "k0", "k1", "!k0", "k0", "k2", "k1",
                        "k0"):
                if key.startswith("!"):          # a new version lands
                    store.put("in", key[1:], b"new" * 1024)
                    continue
                if hit(key) is None:
                    data, meta = store.get_with_meta("in", key)
                    cache.fill("t", "in", key, data, 768 * 1024,
                               hinted=True, etag=meta.etag)
                    assert hit(key) == data
            assert tenant.allocated == 0
            return cache.state.snapshot()

        got, into = drive("get"), drive("get_into")
        assert got == into
        assert got["evictions"] > 0 and got["stale_invalidations"] == 1

    def test_miss_returns_none_and_allocates_nothing(self):
        store, cache = self._filled()
        store.put("in", "other", b"o" * 64)
        calls = []
        assert cache.get_into("t", "in", "other", store,
                              calls.append) is None
        assert calls == []
        assert cache.snapshot()["direct_hits"] == 0
        assert cache._pins == {}

    @pytest.mark.parametrize("how", ["evict", "invalidate", "rewrite"])
    def test_entry_freed_while_pinned(self, how, monkeypatch):
        """Freed mid-copy: never served again, its parked slot goes
        back exactly once, at the unpin, and the copy reads the old
        bytes whole."""
        store, cache = self._filled(nominal=600 * 1024)
        arena = self._cache_arena(cache)
        (parked,) = cache._payload.values()
        frees = []
        real_free = arena._free
        monkeypatch.setattr(arena, "_free",
                            lambda s: (frees.append(s), real_free(s)))
        tenant = TenantArena("t", capacity_mb=1.0)

        def alloc(n):
            if how == "evict":
                store.put("in", "k2", b"y" * 1024)
                cache.fill("t", "in", "k2", store.get("in", "k2"),
                           600 * 1024, hinted=True,
                           etag=store.head("in", "k2").etag)
                assert cache.snapshot()["evictions"] == 1
            elif how == "invalidate":
                store.put("in", "k", b"y" * self.SIZE)
            else:
                meta = store.put("in", "k", b"y" * self.SIZE)
                assert cache.put("t", "in", "k", b"y" * self.SIZE,
                                 600 * 1024, meta.etag)
            # no lookup serves the freed payload, and its slot is held
            seen = cache.get("t", "in", "k", store)
            assert seen in (None, b"y" * self.SIZE)
            assert frees == [] and not parked.released
            return tenant.alloc(n)

        slot = cache.get_into("t", "in", "k", store, alloc)
        assert bytes(slot.view()) == b"x" * self.SIZE
        assert frees == [parked] and parked.released
        assert cache._pins == {} and cache._doomed == {}
        assert arena.allocated == sum(
            p.size for p in cache._payload.values() if isinstance(p, Slot))

    def test_refill_while_pinned_takes_the_parked_slot_back(self):
        """The content key names the bytes: a payload freed and filled
        again during a copy is published again, not parked twice."""
        store, cache = self._filled()
        arena = self._cache_arena(cache)
        (parked,) = cache._payload.values()
        before = arena.allocated
        tenant = TenantArena("t", capacity_mb=1.0)
        data = store.get("in", "k")

        def alloc(n):
            store.put("in", "k", data)           # same bytes, new etag
            assert cache.get("t", "in", "k", store) is None
            assert cache.fill("t", "in", "k", data, self.SIZE, hinted=True,
                              etag=store.head("in", "k").etag)
            return tenant.alloc(n)

        slot = cache.get_into("t", "in", "k", store, alloc)
        assert bytes(slot.view()) == data
        assert list(cache._payload.values()) == [parked]
        assert not parked.released and arena.allocated == before
        assert cache.get("t", "in", "k", store) == data

    @pytest.mark.parametrize("fault", ["alloc", "copy"])
    def test_failed_hit_leaves_no_pin_and_no_slot(self, fault):
        """An exhausted arena (or a slot the payload overflows) fails
        the hit; the pin and any tenant slot go back."""
        store, cache = self._filled()
        tenant = TenantArena("t", capacity_mb=1.0)
        if fault == "alloc":
            tenant.alloc(tenant.capacity)        # exhausted

            def alloc(n):
                return tenant.alloc(n)
        else:
            def alloc(n):
                return tenant.alloc(n // 2)       # too small: write fails
        before = tenant.allocated
        with pytest.raises(ArenaError):
            cache.get_into("t", "in", "k", store, alloc)
        assert cache._pins == {}
        assert tenant.allocated == before
        assert cache.get("t", "in", "k", store) == b"x" * self.SIZE

    def test_concurrent_hits_and_frees_keep_the_arena_exact(self):
        """More threads than cores hit, refill, evict and invalidate
        three keys at once: every hit reads its own key's bytes whole,
        and afterwards no pin, deferred free or leaked slot is left."""
        store = ObjectStore()
        payloads = {f"k{i}": bytes([i + 1]) * (64 * self.SIZE)
                    for i in range(3)}
        for key, data in payloads.items():
            store.put("in", key, data)
        cache = SharedCache(CacheSpec(capacity_mb=2.0))   # two of three fit
        tenant = TenantArena("t", capacity_mb=8.0)
        errors = []

        def worker(w):
            try:
                for i in range(150):
                    key = f"k{(w + i) % 3}"
                    if i % 17 == w % 17:         # same bytes, new etag
                        store.put("in", key, payloads[key])
                    slot = cache.get_into("t", "in", key, store,
                                          tenant.alloc)
                    if slot is None:
                        data, meta = store.get_with_meta("in", key)
                        cache.fill("t", "in", key, data, 700 * 1024,
                                   hinted=True, etag=meta.etag)
                        continue
                    if bytes(slot.view()) != payloads[key]:
                        errors.append(key)
                    slot.release()
            except BaseException as e:            # noqa: BLE001 — reported
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range((os.cpu_count() or 4) + 4)]
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert errors == []
        snap = cache.snapshot()
        assert snap["direct_hits"] > 0 and snap["evictions"] > 0
        assert cache._pins == {} and cache._doomed == {}
        assert tenant.allocated == 0
        assert self._cache_arena(cache).allocated == sum(
            p.size for p in cache._payload.values() if isinstance(p, Slot))


# ------------------------------------------------- PlanVerify overlay

class TestVerifyCacheOverlay:
    def _bundle(self, system="nexus", wname="WEB", cold=False):
        from repro.core.plan import SYSTEMS
        from repro.core.transport import TRANSPORTS
        spec = SYSTEMS[system]
        w = W.SUITE.get(wname) or W.SCENARIOS[wname]
        kb = TRANSPORTS[spec.transport].kernel_bypass
        prog, tmpl = _build_bundle(spec, w, cold, kb)
        return w, prog, tmpl

    @pytest.mark.parametrize("system", ["nexus", "baseline", "wasm",
                                        "nexus-async"])
    @pytest.mark.parametrize("wname", ["WEB", "SG", "PIPE"])
    @pytest.mark.parametrize("cold", [False, True])
    def test_real_overlays_verify_clean(self, system, wname, cold):
        from repro.core.analysis.verify import verify_cache_overlay
        w, prog, tmpl = self._bundle(system, wname, cold)
        cops, cops2, acc = cache_overlay(prog, tmpl[4], tmpl[5],
                                         w.profile)
        verify_cache_overlay(prog, tmpl[4], tmpl[5], cops, cops2, acc,
                             w.profile, subject=f"{system}/{wname}")

    def test_patch_outside_fetch_net_is_rejected(self):
        from repro.core.analysis.diag import PlanCheckError
        from repro.core.analysis.verify import verify_cache_overlay
        from repro.core.des import _OP_CACHE
        w, prog, tmpl = self._bundle()
        cops, cops2, acc = cache_overlay(prog, tmpl[4], tmpl[5],
                                         w.profile)
        bad = list(cops)
        i = prog.names.index("compute[0]")
        bad[i] = _OP_CACHE
        with pytest.raises(PlanCheckError) as e:
            verify_cache_overlay(prog, tmpl[4], tmpl[5], tuple(bad),
                                 cops2, acc, w.profile)
        assert e.value.code == "V-CACHE-WIRE"

    def test_unpatched_coverage_is_rejected(self):
        from repro.core.analysis.diag import PlanCheckError
        from repro.core.analysis.verify import verify_cache_overlay
        w, prog, tmpl = self._bundle()
        _, cops2, acc = cache_overlay(prog, tmpl[4], tmpl[5], w.profile)
        with pytest.raises(PlanCheckError) as e:
            # hand the base array back as the "patched" one
            verify_cache_overlay(prog, tmpl[4], tmpl[5], tmpl[4], cops2,
                                 acc, w.profile)
        assert e.value.code == "V-CACHE-COVER"

    def test_access_list_drift_is_rejected(self):
        from repro.core.analysis.diag import PlanCheckError
        from repro.core.analysis.verify import verify_cache_overlay
        w, prog, tmpl = self._bundle()
        cops, cops2, acc = cache_overlay(prog, tmpl[4], tmpl[5],
                                         w.profile)
        with pytest.raises(PlanCheckError) as e:
            verify_cache_overlay(prog, tmpl[4], tmpl[5], cops, cops2,
                                 acc[:-1], w.profile)
        assert e.value.code == "V-CACHE-OP"

    def test_noncacheable_get_is_fully_transparent(self):
        """cacheable=False: no opcode patch, no access entry — the
        overlay equals the base arrays for an all-opted-out profile."""
        from repro.core.plan import SYSTEMS, compile_program
        from repro.core.transport import TRANSPORTS
        prof = IOProfile((Get(2 * MB, cacheable=False),
                          ComputeSegment(10.0), Put(MB)))
        w = Workload("OPTOUT", prof, 30.0, _single_io_handler(
            lambda v: _digest_n(v, 1.0)))
        spec = SYSTEMS["nexus"]
        kb = TRANSPORTS[spec.transport].kernel_bypass
        prog, tmpl = _build_bundle(spec, w, False, kb)
        cops, cops2, acc = cache_overlay(prog, tmpl[4], tmpl[5], prof)
        assert cops == tmpl[4] and cops2 == tmpl[5]
        assert [a for a in acc if a[0] == "g"] == []


# ------------------------------------------------------- DES behavior

class TestDESCache:
    def _sim(self, **kw):
        kw.setdefault("cache", CacheSpec())
        return DensitySimulator("nexus", 24, seed=3, duration_s=15.0,
                                warmup_s=3.0, **kw)

    def test_same_seed_same_result(self):
        a, b = self._sim().run(), self._sim().run()
        assert a.latencies == b.latencies
        assert a.cache_stats == b.cache_stats
        assert a.cache_stats["hits"] > 0

    def test_disabled_cache_reports_none(self):
        assert self._sim(cache=None).run().cache_stats is None

    def test_hits_shorten_latencies(self):
        flat = lambda r: sorted(x for v in r.latencies.values()
                                for x in v)
        cached = flat(self._sim().run())
        plain = flat(self._sim(cache=None).run())
        assert sum(cached) < sum(plain)

    def test_cache_disabled_templates_stay_pristine(self):
        """A cache-enabled run must not leak `_OP_CACHE` into the
        process-wide bundle table: an uncached run AFTER a cached one
        reproduces the uncached result bit-for-bit."""
        before = self._sim(cache=None).run()
        self._sim().run()
        after = self._sim(cache=None).run()
        assert after.latencies == before.latencies


# ------------------------------------------- cross-executor parity

def _digest_out(mb):
    return lambda v: _digest_n(v, mb)


def _parity_suite():
    """Three cacheable single-I/O workloads with pairwise-distinct
    whole-MB sizes (so both executors see the same content-identity
    classes under eviction pressure) plus one fully opted out."""
    mk = lambda name, in_mb, out_mb: Workload(
        name, IOProfile.single(in_mb, out_mb, 1.0), 30.0,
        _single_io_handler(_digest_out(out_mb)))
    optout = Workload(
        "CD", IOProfile((Get(5 * MB, cacheable=False),
                         ComputeSegment(1.0), Put(MB))), 30.0,
        _single_io_handler(_digest_out(1.0)))
    return {w.name: w for w in (mk("CA", 2.0, 1.0), mk("CB", 3.0, 2.0),
                                mk("CC", 4.0, 3.0), optout)}


PARITY_KEYS = ("lookups", "hits", "misses", "evictions", "admitted",
               "writes")


def _des_counts(spec, order, rounds):
    suite = _parity_suite()
    sim = DensitySimulator("nexus", len(suite), seed=0, duration_s=300.0,
                           warmup_s=0.0, suite=suite, cache=spec)
    # pin the exact serial trace: one arrival every 5 virtual seconds,
    # cycling the same function order the threaded node will replay
    names = {f.split("#")[0]: f for f in sim.functions}
    arrivals = {f: [] for f in sim.functions}
    t = 1.0
    for _ in range(rounds):
        for base in order:
            arrivals[names[base]].append(t)
            t += 5.0
    sim.arrivals = arrivals
    r = sim.run()
    assert r.completed == rounds * len(order)
    return {k: r.cache_stats[k] for k in PARITY_KEYS}


def _threaded_counts(spec, order, rounds):
    suite = _parity_suite()
    node = WorkerNode("nexus", byte_scale=1.0, cache=spec)
    try:
        for w in suite.values():
            node.deploy(w)
            node.seed_input(w.name)
        for _ in range(rounds):
            for base in order:
                node.invoke(base).result(timeout=120)
        node.drain(timeout_s=60.0)
        snap = node.cache_stats()
        return {k: snap[k] for k in PARITY_KEYS}
    finally:
        node.shutdown()


class TestCountParity:
    """DES counters == threaded counters on the same serial trace —
    the tentpole's replay-verified-prediction contract."""

    ORDER = ("CA", "CB", "CC", "CD")

    def test_no_eviction_regime(self):
        spec = CacheSpec(capacity_mb=64.0)
        des = _des_counts(spec, self.ORDER, rounds=3)
        thr = _threaded_counts(spec, self.ORDER, rounds=3)
        assert des == thr
        # the opted-out CD never consults: 3 cacheable fns x 3 rounds
        assert des["lookups"] == 9
        assert des["hits"] == 6                 # all hits after round 1

    @pytest.mark.parametrize("policy", ["lru", "clock", "random"])
    def test_eviction_pressure_regime(self, policy):
        # unique content is 15 MB (9 MB of inputs + 6 MB of outputs):
        # a 12 MB cache evicts every round, and the eviction SEQUENCE
        # must agree across executors for the counters to match
        spec = CacheSpec(capacity_mb=12.0, policy=policy, seed=11)
        des = _des_counts(spec, self.ORDER, rounds=4)
        thr = _threaded_counts(spec, self.ORDER, rounds=4)
        assert des == thr
        assert des["evictions"] > 0


# ------------------------------------------------------ ml_suite hits

class TestMLSecondInvocationHits:
    def _node(self, suite, name, spec=None):
        from repro.models import serving
        node = WorkerNode("nexus", byte_scale=1.0,
                          cache=spec or CacheSpec(capacity_mb=64.0))
        node.deploy(suite[name])
        node.seed_input(name, payloads=serving.seed_payloads(name))
        return node

    def test_llm_decode_kv_chain_hits_after_first_invocation(self):
        suite = W.ml_suite("tiny")
        node = self._node(suite, "LLM-DECODE")
        try:
            node.invoke("LLM-DECODE").result(timeout=120)
            node.invoke("LLM-DECODE").result(timeout=120)
            node.drain(timeout_s=60.0)
            snap = node.cache_stats()
            # params + kv GET per step: both hit on the second step
            assert snap["lookups"] == 4
            assert snap["hits"] == 2
            assert snap["misses"] == 2
            assert node.backend.stats["cache_hits"] == 2
        finally:
            node.shutdown()

    def test_llm_cold_weight_shards_hit_after_first_invocation(self):
        suite = W.ml_suite("tiny")
        node = self._node(suite, "LLM-COLD")
        try:
            n_gets = len(suite["LLM-COLD"].profile.gets)
            node.invoke("LLM-COLD").result(timeout=120)
            node.invoke("LLM-COLD").result(timeout=120)
            node.drain(timeout_s=60.0)
            snap = node.cache_stats()
            assert snap["lookups"] == 2 * n_gets
            assert snap["hits"] == n_gets       # every shard + prompt
        finally:
            node.shutdown()

    def test_des_ml_suite_predicts_hits(self):
        """The DES over the full-scale ml mix: stable logical keys
        (params / kv / shards) turn into hits after each function's
        first invocation — no wall clock anywhere."""
        sim = DensitySimulator(
            "nexus", 10, seed=1, duration_s=40.0, warmup_s=5.0,
            mean_rate=0.25, suite=W.ml_suite("full"),
            # capacity is pure accounting in the DES — size it over the
            # whole ML working set so no eviction breaks the bound below
            cache=CacheSpec(capacity_mb=65536.0))
        r = sim.run()
        assert r.cache_stats["hits"] > 0
        # every function's stable GETs miss at most once each
        per_fn_gets = {f: len(sim.workload[f].profile.gets)
                       for f in sim.functions}
        assert r.cache_stats["misses"] <= sum(per_fn_gets.values())


# ------------------------------------------------ per-op admission

class TestPerOrdinalAdmission:
    """The threaded client's SharedCache admission flags are per GET
    *ordinal*, like the DES overlay's — a profile declaring two GETs
    on one (bucket, key) with differing prefetchable/cacheable bits
    must not collapse them into one decision."""

    def _client(self, admission):
        from repro.core import metrics as M
        from repro.core.frontend import GuestContext, NexusClient
        ctx = GuestContext(tenant="t", cred_handle="c",
                           admission=admission)
        return NexusClient(ctx, lambda: None, M.CycleAccount())

    def test_duplicate_key_gets_keep_their_own_flags(self):
        client = self._client({("b", "k"): [(True, True),
                                            (False, False)]})
        assert client._admission("b", "k") == (True, True)
        assert client._admission("b", "k") == (False, False)
        # the final entry sticks for calls past the declared count
        assert client._admission("b", "k") == (False, False)

    def test_undeclared_pair_is_unhinted_but_cacheable(self):
        client = self._client({})
        assert client._admission("b", "k") == (False, True)


# -------------------------------------------------------- cluster

class TestClusterCache:
    def test_per_node_caches_are_independent(self):
        from repro.core.cluster import (ClusterSimulator, ClusterSpec,
                                        NodeSpec)
        spec = ClusterSpec(
            nodes=(NodeSpec("nexus", cache=CacheSpec()),
                   NodeSpec("nexus")),
            n_functions=24, policy="round_robin",
            duration_s=15.0, warmup_s=3.0)
        res = ClusterSimulator(spec, seed=3).run()
        cached, plain = res.node_results
        assert cached.cache_stats is not None
        assert cached.cache_stats["lookups"] > 0
        assert plain.cache_stats is None
