"""Behavioural tests for the Nexus core (paper §4-§5 invariants)."""
import threading
import time

import pytest

from repro.core import fabric as F
from repro.core import metrics as M
from repro.core.arena import ArenaError, ArenaRegistry, IsolationError, TenantArena
from repro.core.backend import BackendCrashed, NexusBackend
from repro.core.cache import CacheSpec, SharedCache
from repro.core.credentials import CredentialError, TokenManager
from repro.core.frontend import GuestContext, NexusClient
from repro.core.hints import (InputHint, OutputHint, extract_hints,
                              make_event)
from repro.core.planes import ControlMessage, ControlPlane
from repro.core.ratelimit import TokenBucket
from repro.core.runtime import SYSTEMS, WorkerNode
from repro.core.storage import FaultPlan, ObjectStore, RemoteStorage
from repro.core.streaming import CircularBuffer
from repro.core.supervisor import Supervisor

MB = 1024 * 1024

def make_backend(transport="tcp", **kw):
    store = ObjectStore()
    acct = M.CycleAccount()
    remote = RemoteStorage(store, transport, acct, **kw)
    return store, acct, NexusBackend(remote, acct, transport_name=transport)


# ------------------------------------------------------------------ arena

class TestArena:
    def test_zero_copy_views(self):
        arena = TenantArena("t", capacity_mb=1)
        slot = arena.alloc(1024)
        slot.write(b"x" * 1024)
        view = slot.view()
        assert isinstance(view, memoryview)
        # the view aliases arena memory: no copy happened
        assert view.obj is arena._buf

    def test_exact_size_alloc_and_reuse(self):
        arena = TenantArena("t", capacity_mb=1)
        a = arena.alloc(512 * 1024)
        b = arena.alloc(512 * 1024)
        with pytest.raises(ArenaError):
            arena.alloc(1)
        a.release()
        b.release()
        c = arena.alloc(1024 * 1024)       # coalesced back to full size
        assert c.size == 1024 * 1024

    def test_cross_tenant_isolation(self):
        reg = ArenaRegistry()
        a = reg.get("alice")
        reg.get("bob")
        slot = a.alloc(64)
        with pytest.raises(IsolationError):
            reg.resolve("bob", slot)

    def test_oversized_write_rejected(self):
        arena = TenantArena("t", capacity_mb=1)
        slot = arena.alloc(16)
        with pytest.raises(ArenaError):
            slot.write(b"y" * 17)

    def test_write_releases_the_gil(self):
        """A thread spinning while `Slot.write` copies 128 MB sees the
        destination half written: it ran during the copy, which a copy
        holding the GIL never lets happen."""
        n, step = 128 * MB, 4 * MB
        arena = TenantArena("t", capacity_mb=n / MB)
        slot = arena.alloc(n)
        dst = slot.arena._buf_view[::step]
        done = threading.Event()
        seen = []

        def spin():
            while not done.is_set():
                if len(set(bytes(dst))) > 1:     # some samples old, some new
                    seen.append(1)

        t = threading.Thread(target=spin)
        t.start()
        try:
            for v in range(1, 5):
                src = bytes([v]) * n
                slot.write(src)
                assert bytes(dst) == bytes([v]) * (n // step)
                if seen:
                    break
        finally:
            done.set()
            t.join()
        assert seen


# ------------------------------------------------------------- control plane

class TestControlPlane:
    def test_bulk_payloads_rejected(self):
        plane = ControlPlane(M.CycleAccount())
        with pytest.raises(ValueError):
            plane.send(ControlMessage("put", "t", {"data": "z" * 8192}))

    def test_crossing_accounting(self):
        acct = M.CycleAccount()
        plane = ControlPlane(acct)
        for _ in range(5):
            plane.send(ControlMessage("get", "t", {"key": "k"}))
        snap = acct.snapshot()
        assert snap["crossings"]["ctrl_msg"] == 5
        assert snap["crossings"]["vm_exit"] == 5 * F.VSOCK_EXITS_PER_MSG


# ---------------------------------------------------------------- streaming

class TestStreaming:
    def test_bounded_roundtrip(self):
        buf = CircularBuffer(capacity=1024)        # smaller than payload
        payload = bytes(range(256)) * 40           # 10 KB through 1 KB ring

        def produce():
            buf.write(payload)
            buf.close()

        t = threading.Thread(target=produce)
        t.start()
        out = buf.read_all(chunk=300)
        t.join()
        assert out == payload
        assert buf.total_in == len(payload)


# -------------------------------------------------------------- credentials

class TestCredentials:
    def test_scope_enforced(self):
        tm = TokenManager()
        h = tm.provision("fn", {"data"}, {"get"})
        tm.authorize(h, "data", "get")
        with pytest.raises(CredentialError):
            tm.authorize(h, "data", "put")
        with pytest.raises(CredentialError):
            tm.authorize(h, "secrets", "get")

    def test_expiry(self):
        tm = TokenManager(ttl_s=-1.0)
        h = tm.provision("fn", {"data"})
        with pytest.raises(CredentialError):
            tm.authorize(h, "data", "get")

    def test_no_raw_keys_in_guest(self):
        store, acct, be = make_backend()
        cred = be.register_function("fn", {"in"})
        ctx = GuestContext(tenant="fn", cred_handle=cred,
                           invocation_id="inv-1")
        TokenManager.assert_guest_clean(
            {"tenant": ctx.tenant, "invocation_id": ctx.invocation_id,
             "cred_handle": ctx.cred_handle})


# ---------------------------------------------------------------- ratelimit

class TestRateLimit:
    def test_token_bucket_delay(self):
        clock = [0.0]
        b = TokenBucket(rate_bps=1000.0, burst_bytes=100.0,
                        clock=lambda: clock[0])
        assert b.reserve(100) == 0.0            # burst absorbs
        d = b.reserve(500)                      # 500 B over a drained bucket
        assert d == pytest.approx(0.5)
        clock[0] += 1.0                         # refill 1000 B (cap 100)
        assert b.reserve(50) == pytest.approx(0.0, abs=1e-9)


# ------------------------------------------------------------------- hints

class TestHints:
    def test_s3_event_promotion(self):
        event = {"Records": [{"s3": {"bucket": {"name": "b"},
                                     "object": {"key": "k", "size": 123}}}]}
        (inp,), _ = extract_hints(event)
        assert inp == InputHint("b", "k", 123)
        assert inp.prefetchable

    def test_opaque_event(self):
        inputs, outputs = extract_hints("not json at all")
        assert inputs == () and outputs == ()

    def test_sizeless_hint_not_prefetchable(self):
        (inp,), _ = extract_hints(make_event([("b", "k")], [("o", "ok")]))
        assert not inp.prefetchable

    def test_multi_input_events_keep_order(self):
        """Scatter-gather events promote every data dependency, in the
        handler's program order."""
        event = make_event([("in", f"shard-{i}", 64) for i in range(4)],
                           [("out", "a"), ("out", "b")])
        inputs, outputs = extract_hints(event)
        assert [h.key for h in inputs] == [f"shard-{i}" for i in range(4)]
        assert all(h.prefetchable for h in inputs)
        assert [o.key for o in outputs] == ["a", "b"]

    def test_legacy_single_input_shape_still_promotes(self):
        event = {"input": {"bucket": "b", "key": "k", "size": 9},
                 "output": {"bucket": "o", "key": "x"}}
        inputs, outputs = extract_hints(event)
        assert inputs == (InputHint("b", "k", 9),)
        assert outputs == (OutputHint("o", "x"),)


# ------------------------------------------------------------------ backend

class TestBackend:
    def test_prefetch_exact_slot(self):
        store, acct, be = make_backend()
        store.put("in", "obj", b"q" * 4096)
        cred = be.register_function("fn", {"in"})
        h = be.prefetch("fn", cred, InputHint("in", "obj", 4096))
        slot = h.wait()
        assert slot.used == 4096
        assert bytes(slot.view()) == b"q" * 4096

    def test_put_idempotent_by_invocation(self):
        from repro.core.hints import OutputHint
        store, acct, be = make_backend()
        cred = be.register_function("fn", {"out"})
        arena = be.arenas.get("fn")
        s1 = arena.alloc(16); s1.write(b"a" * 16)
        t1 = be.submit_put("fn", cred, OutputHint("out", "k"), s1, "inv-1")
        e1 = t1.future.result(timeout=5)
        s2 = arena.alloc(16); s2.write(b"a" * 16)
        t2 = be.submit_put("fn", cred, OutputHint("out", "k"), s2, "inv-1")
        e2 = t2.future.result(timeout=5)
        assert e1 == e2                      # deduped: same etag, one write
        assert store.head("out", "k").etag == e1

    def test_streaming_fallback(self):
        store, acct, be = make_backend()
        payload = bytes(range(256)) * 256    # 64 KB
        store.put("in", "blob", payload)
        cred = be.register_function("fn", {"in"})
        buf = CircularBuffer(capacity=4096)
        be.fetch_stream("fn", cred, "in", "blob", buf, chunk=1024)
        assert buf.read_all() == payload

    def test_streaming_fallback_charges_streamed_bytes(self):
        """Regression: the stub used to bill the streaming path with
        nbytes=0, silently dropping the SDK's per-MB cycles. The charge
        must reflect the full streamed size once the ring closes."""
        n = 3 * (1 << 20)

        class _FakeBackend:
            class remote:
                cost_scale = 1.0

            @staticmethod
            def fetch_stream(tenant, cred, bucket, key, buf, chunk):
                def _pump():
                    buf.write(b"x" * n)
                    buf.close()
                threading.Thread(target=_pump, daemon=True).start()

        acct = M.CycleAccount()
        ctx = GuestContext(tenant="fn", cred_handle="h")
        client = NexusClient(ctx, lambda: _FakeBackend, acct)
        buf = client.get_object_streaming(Bucket="in", Key="blob")
        assert len(buf.read_all()) == n
        charged = acct.snapshot()["total"]
        assert charged == pytest.approx(
            F.remoted_op_cost("aws", n).total(), rel=1e-9)
        # strictly above what the old nbytes=0 bug billed
        assert charged > F.remoted_op_cost("aws", 0).total()

    def test_prefetch_hit_is_one_direct_copy(self):
        store, acct, be = make_backend()
        be.cache = SharedCache(CacheSpec(capacity_mb=1.0))
        store.put("in", "obj", b"q" * 4096)
        cred = be.register_function("fn", {"in"})
        first = be.prefetch("fn", cred, InputHint("in", "obj", 4096)).wait()
        assert be.stats["cache_hits"] == 0
        slot = be.prefetch("fn", cred, InputHint("in", "obj", 4096)).wait()
        assert slot is not first and slot.arena is be.arenas.get("fn")
        assert bytes(slot.view()) == b"q" * 4096
        assert be.stats["cache_hits"] == 1
        snap = be.cache.snapshot()
        assert (snap["hits"], snap["direct_hits"],
                snap["copied_hits"]) == (1, 1, 0)

    def test_unauthorized_bucket_denied(self):
        store, acct, be = make_backend()
        store.put("secrets", "x", b"nope")
        cred = be.register_function("fn", {"in"})
        h = be.prefetch("fn", cred, InputHint("secrets", "x", 4))
        with pytest.raises(CredentialError):
            h.wait()


# ------------------------------------------------- crash-only + supervisor

class TestCrashRecovery:
    def test_supervisor_restarts_backend(self):
        store = ObjectStore()
        acct = M.CycleAccount()
        remote = RemoteStorage(store, "tcp", acct)
        sup = Supervisor(lambda: NexusBackend(remote, acct))
        sup.start()
        try:
            old = sup.backend
            sup.kill_backend()
            deadline = time.monotonic() + 2.0
            while sup.backend is old and time.monotonic() < deadline:
                time.sleep(0.005)
            assert sup.backend is not old
            assert sup.restarts == 1
            assert sup.backend.alive
        finally:
            sup.stop()

    def test_frontend_retries_across_crash(self):
        store = ObjectStore()
        acct = M.CycleAccount()
        remote = RemoteStorage(store, "tcp", acct)
        from repro.core.arena import ArenaRegistry
        from repro.core.credentials import TokenManager
        arenas, tokens = ArenaRegistry(), TokenManager()
        sup = Supervisor(lambda: NexusBackend(remote, acct, arenas=arenas,
                                              tokens=tokens))
        sup.start()
        try:
            store.put("in", "obj", b"p" * 1024)
            cred = sup.backend.register_function("fn", {"in", "out"})
            ctx = GuestContext(tenant="fn", cred_handle=cred,
                               invocation_id="inv-9")
            client = NexusClient(ctx, lambda: sup.backend, acct)
            sup.kill_backend()                    # crash BEFORE the request
            obj = client.get_object(Bucket="in", Key="obj")
            assert bytes(obj["Body"]) == b"p" * 1024
            assert sup.restarts >= 1
        finally:
            sup.stop()


    def test_crash_between_hit_lookup_and_copy_leaves_no_pin(self):
        """The backend dies after the hit pinned its payload and before
        the copy (in the tenant slot's allocation): the prefetch fails,
        the pin goes, no slot leaks, and the restarted backend serves
        the same hit."""
        store = ObjectStore()
        acct = M.CycleAccount()
        remote = RemoteStorage(store, "tcp", acct)
        from repro.core.arena import ArenaRegistry
        from repro.core.credentials import TokenManager
        arenas, tokens = ArenaRegistry(), TokenManager()
        cache = SharedCache(CacheSpec(capacity_mb=1.0))
        sup = Supervisor(lambda: NexusBackend(remote, acct, arenas=arenas,
                                              tokens=tokens, cache=cache))
        sup.start()
        try:
            store.put("in", "obj", b"p" * 4096)
            cred = sup.backend.register_function("fn", {"in"})
            hint = InputHint("in", "obj", 4096)
            sup.backend.prefetch("fn", cred, hint).wait().release()
            arena = arenas.get("fn")
            real_alloc_wait = arena.alloc_wait

            def dying_alloc_wait(size, timeout_s=10.0):
                assert cache._pins                # the hit is pinned
                sup.kill_backend()
                raise BackendCrashed("nexus backend is down")

            arena.alloc_wait = dying_alloc_wait
            with pytest.raises(BackendCrashed):
                sup.backend.prefetch("fn", cred, hint).wait()
            assert cache._pins == {} and arena.allocated == 0
            arena.alloc_wait = real_alloc_wait
            deadline = time.monotonic() + 2.0
            while not sup.backend.alive and time.monotonic() < deadline:
                time.sleep(0.005)
            slot = sup.backend.prefetch("fn", cred, hint).wait()
            assert bytes(slot.view()) == b"p" * 4096
            assert cache.snapshot()["direct_hits"] == 2
        finally:
            sup.stop()


def test_first_get_waits_for_prefetch_as_long_as_the_node_says():
    """The guest's wait on the ingress prefetch is the node's connect
    deadline, not a fixed one: a multi-GB weights fetch outlasts 30 s."""
    from repro.core.backend import PrefetchHandle
    handle = PrefetchHandle(InputHint("in", "weights", 1 << 30))
    ctx = GuestContext(tenant="fn", cred_handle="h", invocation_id="i",
                       prefetch=handle)
    client = NexusClient(ctx, lambda: None, M.CycleAccount(),
                         connect_timeout_s=0.2)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        client.get_object(Bucket="in", Key="weights")
    assert time.monotonic() - t0 < 5.0


# ------------------------------------------------------- end-to-end runtime

class TestWorkerNode:
    @pytest.mark.parametrize("system", list(SYSTEMS))
    def test_invocation_completes_durably(self, system):
        node = WorkerNode(system)
        try:
            node.deploy("AES")
            node.seed_input("AES")
            res = node.invoke("AES").result(timeout=60)
            assert res.output_etag is not None
            # at-least-once: the output object really is in storage
            assert node.store.head("out", f"{res.invocation_id}-out").size > 0
        finally:
            node.shutdown()

    def test_arena_sized_from_the_deployed_profile(self):
        """One invocation holds its 48 MB input slot while it writes a
        24 MB output: more than the registry's 64 MB default. Deploy
        sizes the tenant arena from the profile, so it still runs."""
        from repro.core.workloads import (MB, IOProfile, Workload,
                                          _single_io_handler)
        w = Workload("BIG", IOProfile.single(48.0, 24.0, 1.0), 10.0,
                     _single_io_handler(lambda v: bytes(v[:24 * MB])))
        node = WorkerNode("nexus", byte_scale=1.0)
        try:
            node.deploy(w)
            assert node._arenas.get("BIG").capacity >= 72 * MB
            node.seed_input("BIG")
            res = node.invoke("BIG").result(timeout=60)
            assert node.store.head("out", f"{res.invocation_id}-out").size \
                == 24 * MB
        finally:
            node.shutdown()

    def test_prefetch_overlaps_restore(self):
        """Cold-start latency: async (prefetch) < tcp (serialized)."""
        lat = {}
        for system in ("nexus-tcp", "nexus-async"):
            node = WorkerNode(system)
            try:
                node.deploy("ST-R")
                node.seed_input("ST-R")
                res = node.invoke("ST-R").result(timeout=60)
                assert res.cold
                lat[system] = res.latency_s
            finally:
                node.shutdown()
        assert lat["nexus-async"] < lat["nexus-tcp"]

    def test_streaming_for_opaque_inputs(self):
        node = WorkerNode("nexus")
        try:
            node.deploy("WEB")
            node.seed_input("WEB")
            res = node.invoke("WEB", opaque=True).result(timeout=60)
            assert res.output_etag is not None
            assert node.backend.stats["stream_gets"] >= 1
            assert node.backend.stats["prefetches"] == 0
        finally:
            node.shutdown()

    def test_cycle_savings_vs_baseline(self):
        """Fabric offload must cut total cycles and guest-user share."""
        snaps = {}
        for system in ("baseline", "nexus"):
            node = WorkerNode(system)
            try:
                node.deploy("LR-S")
                node.seed_input("LR-S")
                for _ in range(3):
                    node.invoke("LR-S").result(timeout=60)
                snaps[system] = node.acct.snapshot()
            finally:
                node.shutdown()
        base, nex = snaps["baseline"], snaps["nexus"]
        assert nex["total"] < base["total"] * 0.75
        assert (nex["cycles"]["guest_user"]
                < base["cycles"]["guest_user"] * 0.5)
        assert (nex["crossings"]["vm_exit"]
                < base["crossings"]["vm_exit"])

    def test_hedged_reads_bound_stragglers(self):
        store = ObjectStore()
        acct = M.CycleAccount()
        slow = RemoteStorage(store, "tcp", acct,
                             faults=FaultPlan(slow_every=2, slow_factor=50))
        hedged = RemoteStorage(store, "tcp", acct, hedge_after_s=0.005,
                               faults=FaultPlan(slow_every=2, slow_factor=50))
        store.put("in", "k", b"d" * (4 << 20))

        def timed(rs):
            t0 = time.monotonic()
            rs.get("in", "k")
            rs.get("in", "k")
            return time.monotonic() - t0

        assert timed(hedged) < timed(slow)
        assert hedged.hedges_fired >= 1
