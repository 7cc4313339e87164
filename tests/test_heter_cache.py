"""HotRowCache — the HeterPS-analog device-resident embedding cache.

Reference role: paddle/fluid/framework/fleet/heter_ps/ps_gpu_wrapper.h
(GPU-resident hot rows over the host/SSD table, EndPass merge-back).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401  (backend/device setup)
from paddle_tpu.distributed.ps import HotRowCache, SparseTable

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mk(optimizer="sgd", lr=0.1, seed=11, **kw):
    remote = SparseTable(dim=4, optimizer=optimizer, learning_rate=lr,
                         init_range=0.01, seed=seed)
    cache = HotRowCache(remote, optimizer=optimizer, learning_rate=lr,
                        **kw)
    return remote, cache


class TestHotRowCache:
    def test_hit_path_is_rtt_free_and_exact(self):
        remote, cache = _mk(capacity=64)
        baseline = SparseTable(dim=4, optimizer="sgd", learning_rate=0.1,
                               init_range=0.01, seed=11)
        rng = np.random.RandomState(0)
        keys = np.array([3, 7, 7, 20], np.int64)
        for step in range(10):
            rows_c = np.asarray(cache.pull(keys))
            rows_b = baseline.pull(keys)
            np.testing.assert_allclose(rows_c, rows_b, rtol=1e-6,
                                       atol=1e-7)
            g = rng.randn(4, 4).astype(np.float32)
            cache.push(keys, g)
            baseline.push(keys, g)
        s = cache.stats()
        # 1 miss RTT on first sight of the 3 unique keys, then pure hits
        assert s["rtts"]["pull"] == 1
        assert s["rtts"]["push"] == 0 and s["rtts"]["push_delta"] == 0
        assert s["hits"] == 9 * 3 and s["misses"] == 3
        # write-back lands the locally-trained rows on the host table
        cache.flush()
        np.testing.assert_allclose(remote.pull(keys), baseline.pull(keys),
                                   rtol=1e-6, atol=1e-7)

    def test_adagrad_matches_host_table(self):
        remote, cache = _mk(optimizer="adagrad", capacity=32, seed=5)
        baseline = SparseTable(dim=4, optimizer="adagrad",
                               learning_rate=0.1, init_range=0.01, seed=5)
        rng = np.random.RandomState(1)
        keys = np.arange(8, dtype=np.int64)
        for _ in range(6):
            np.testing.assert_allclose(np.asarray(cache.pull(keys)),
                                       baseline.pull(keys), rtol=1e-5,
                                       atol=1e-6)
            g = rng.randn(8, 4).astype(np.float32)
            cache.push(keys, g)
            baseline.push(keys, g)
        cache.flush()
        np.testing.assert_allclose(remote.pull(keys), baseline.pull(keys),
                                   rtol=1e-5, atol=1e-6)

    def test_adagrad_duplicate_keys_match_host_sequential_apply(self):
        """Review regression: the host table applies each duplicate
        occurrence sequentially (accum += g_i^2 per row); summing
        duplicates first gives accum = (sum g)^2 — wrong weights."""
        remote, cache = _mk(optimizer="adagrad", capacity=16, seed=17)
        baseline = SparseTable(dim=4, optimizer="adagrad",
                               learning_rate=0.1, init_range=0.01,
                               seed=17)
        rng = np.random.RandomState(2)
        keys = np.array([7, 3, 7, 7, 3], np.int64)  # multiplicities 3, 2
        for _ in range(4):
            np.testing.assert_allclose(np.asarray(cache.pull(keys)),
                                       baseline.pull(keys), rtol=1e-5,
                                       atol=1e-6)
            g = rng.randn(5, 4).astype(np.float32)
            cache.push(keys, g)
            baseline.push(keys, g)
        cache.flush()
        np.testing.assert_allclose(remote.pull(keys), baseline.pull(keys),
                                   rtol=1e-5, atol=1e-6)

    def test_adagrad_accumulator_survives_eviction(self):
        """Review regression: eviction + re-admission must restore the
        adagrad accumulator (spilled host-side), not restart full-size
        steps for the row."""
        remote, cache = _mk(optimizer="adagrad", capacity=2, seed=19)
        baseline = SparseTable(dim=4, optimizer="adagrad",
                               learning_rate=0.1, init_range=0.01,
                               seed=19)
        a = np.array([1], np.int64)
        g1 = np.full((1, 4), 2.0, np.float32)
        cache.pull(a); cache.push(a, g1)
        baseline.pull(a); baseline.push(a, g1)
        # force key 1 out (2 new keys fill the 2-slot cache)
        cache.pull(np.array([50, 51], np.int64))
        assert 1 not in cache._slot_of
        # re-admit and push again: second step must use accum g1^2+g2^2
        g2 = np.full((1, 4), 1.0, np.float32)
        cache.pull(a); cache.push(a, g2)
        baseline.pull(a); baseline.push(a, g2)
        cache.flush()
        np.testing.assert_allclose(remote.pull(a), baseline.pull(a),
                                   rtol=1e-5, atol=1e-6)

    def test_sgd_cache_allocates_no_accumulator(self):
        _, cache = _mk(capacity=8)
        assert cache._accum is None

    def test_empty_push_and_pull_are_noops(self):
        for opt in ("sgd", "adagrad"):
            _, cache = _mk(optimizer=opt, capacity=8)
            e = np.array([], np.int64)
            cache.push(e, np.zeros((0, 4), np.float32))
            assert np.asarray(cache.pull(e)).shape == (0, 4)

    def test_spill_dict_is_bounded(self):
        _, cache = _mk(optimizer="adagrad", capacity=2)
        cache.spill_capacity = 4
        for k in range(40):  # constant churn through a 2-slot cache
            key = np.array([k], np.int64)
            cache.pull(key)
            cache.push(key, np.ones((1, 4), np.float32))
        assert len(cache._accum_spill) <= 4

    def test_duplicate_keys_in_batch_accumulate(self):
        remote, cache = _mk(lr=1.0, capacity=16)
        keys = np.array([5, 5, 5], np.int64)
        before = np.asarray(cache.pull(np.array([5], np.int64))).copy()
        g = np.ones((3, 4), np.float32)
        cache.push(keys, g)
        after = np.asarray(cache.pull(np.array([5], np.int64)))
        np.testing.assert_allclose(after, before - 3.0, rtol=1e-6)

    def test_eviction_keeps_hot_rows_and_writes_back_cold(self):
        remote, cache = _mk(lr=1.0, capacity=8, seed=2)
        hot = np.arange(4, dtype=np.int64)
        for _ in range(5):
            cache.pull(hot)  # score up the hot set
        cold = np.arange(100, 104, dtype=np.int64)
        cache.pull(cold)
        cache.push(cold, np.ones((4, 4), np.float32))
        cold_local = np.asarray(cache.pull(cold)).copy()
        # 4 new keys cannot fit beside 8 residents: evict the cold ones
        # (lowest decayed-frequency score), never the hot set
        newer = np.arange(200, 204, dtype=np.int64)
        cache.pull(newer)
        s = cache.stats()
        assert s["evictions"] == 4
        for k in hot.tolist():
            assert k in cache._slot_of, "hot row evicted before cold"
        for k in cold.tolist():
            assert k not in cache._slot_of
        # dirty cold rows were written back on eviction: the host table
        # (and a fresh re-pull through the cache) sees the trained values
        np.testing.assert_allclose(remote.pull(cold), cold_local,
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(cache.pull(cold)),
                                   cold_local, rtol=1e-6)

    def test_capacity_overflow_passes_through_correctly(self):
        remote, cache = _mk(lr=1.0, capacity=4, seed=3)
        baseline = SparseTable(dim=4, optimizer="sgd", learning_rate=1.0,
                               init_range=0.01, seed=3)
        keys = np.arange(10, dtype=np.int64)  # > capacity uniques
        rows_c = np.asarray(cache.pull(keys))
        np.testing.assert_allclose(rows_c, baseline.pull(keys), rtol=1e-6)
        g = np.ones((10, 4), np.float32)
        cache.push(keys, g)
        baseline.push(keys, g)
        cache.flush()
        np.testing.assert_allclose(remote.pull(keys), baseline.pull(keys),
                                   rtol=1e-6)

    def test_refresh_folds_other_trainers_updates(self):
        remote, cache = _mk(lr=1.0, capacity=16, seed=7)
        keys = np.array([1, 2], np.int64)
        mine = np.asarray(cache.pull(keys)).copy()
        # another trainer pushes directly to the host table
        remote.push(keys, np.full((2, 4), 2.0, np.float32))
        # cached rows are stale by design until the EndPass refresh
        np.testing.assert_allclose(np.asarray(cache.pull(keys)), mine,
                                   rtol=1e-6)
        cache.flush(refresh=True)
        np.testing.assert_allclose(np.asarray(cache.pull(keys)),
                                   mine - 2.0, rtol=1e-6)

    def test_flush_interval_auto_syncs(self):
        remote, cache = _mk(lr=1.0, capacity=16, seed=9,
                            flush_interval=3)
        keys = np.array([4, 5], np.int64)
        cache.pull(keys)
        for _ in range(3):
            cache.push(keys, np.ones((2, 4), np.float32))
        # third push crossed the interval: host table already has it
        got = remote.pull(keys)
        init = SparseTable(dim=4, optimizer="sgd", learning_rate=1.0,
                           init_range=0.01, seed=9).pull(keys)
        np.testing.assert_allclose(got, init - 3.0, rtol=1e-6)

    def test_distributed_embedding_integration(self):
        """DistributedEmbedding(table=cache): autograd pushes land in the
        cache, not the wire, and write back on flush."""
        from paddle_tpu.distributed.ps import DistributedEmbedding

        remote, cache = _mk(lr=0.1, capacity=32, seed=13)
        emb = DistributedEmbedding(4, table=cache)
        ids = paddle.to_tensor(np.array([[1, 2], [2, 8]], np.int64))
        out = emb(ids)
        assert tuple(out.shape) == (2, 2, 4)
        loss = (out * out).sum()
        loss.backward()
        s = cache.stats()
        assert s["rtts"]["pull"] == 1
        assert s["rtts"]["push"] == 0
        assert cache._dirty.any()
        cache.flush()
        np.testing.assert_allclose(
            remote.pull(np.array([1, 2, 8], np.int64)),
            np.asarray(cache.pull(np.array([1, 2, 8], np.int64))),
            rtol=1e-6)


@pytest.mark.slow
def test_wide_deep_two_process_cached_convergence(tmp_path):
    """VERDICT r3 #2 'done' bar: 2-process Wide&Deep through HotRowCache
    converges like the uncached run, with a measured >0 hit rate and
    fewer service RTTs per step than the uncached 2/step."""
    script = tmp_path / "wd_cached.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {REPO!r})
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu import nn, optimizer
        from paddle_tpu.distributed.store import TCPStore
        from paddle_tpu.distributed.ps import (
            DistributedSparseTable, HotRowCache, start_ps_server,
            wait_ps_endpoints)
        from paddle_tpu.models.wide_deep import WideDeep

        rank = int(os.environ["PADDLE_TRAINER_ID"])
        world = int(os.environ["PADDLE_TRAINERS_NUM"])
        host, port = os.environ["PADDLE_MASTER"].split(":")
        store = TCPStore(host, int(port), is_master=False,
                         world_size=world)
        srv = start_ps_server(dim=4, index=rank, store=store,
                              optimizer="adagrad", learning_rate=0.1)
        srv_w = start_ps_server(dim=1, index=world + rank, store=store,
                                optimizer="adagrad", learning_rate=0.1)
        eps = wait_ps_endpoints(store, 2 * world)
        deep_remote = DistributedSparseTable(
            eps[:world], optimizer="adagrad", learning_rate=0.1)
        wide_remote = DistributedSparseTable(
            eps[world:], optimizer="adagrad", learning_rate=0.1)
        # HBM hot-row caches in front of both tables (HeterPS role):
        # EndPass-style refresh every 4 steps exchanges trainer updates
        deep = HotRowCache(deep_remote, capacity=2048,
                           optimizer="adagrad", learning_rate=0.1,
                           flush_interval=4)
        wide = HotRowCache(wide_remote, capacity=2048,
                           optimizer="adagrad", learning_rate=0.1,
                           flush_interval=4)

        paddle.seed(100 + rank)
        model = WideDeep(sparse_feature_dim=4, num_slots=3,
                         hidden_sizes=(16,), table=deep, wide_table=wide)
        opt = optimizer.Adam(learning_rate=1e-2,
                             parameters=model.parameters())
        rs = np.random.RandomState(rank)
        ids_np = rs.randint(0, 1000, (256, 3)).astype(np.int64)
        y_np = (ids_np[:, 0] % 2 == 0).astype(np.float32)

        losses, steps = [], 0
        for epoch in range(12):
            for lo in range(0, 256, 64):
                ids = paddle.to_tensor(ids_np[lo:lo+64])
                y = paddle.to_tensor(y_np[lo:lo+64])
                logits = model(ids).reshape([-1])
                loss = nn.functional.binary_cross_entropy_with_logits(
                    logits, y)
                loss.backward()
                opt.step(); opt.clear_grad()
                steps += 1
            losses.append(float(loss.numpy()))
        assert losses[-1] < 0.7 * losses[0], f"no convergence: {{losses}}"

        s = deep.stats()
        assert s["hit_rate"] > 0.5, s
        # uncached = 1 pull + 1 push RTT per step; the cache must beat it
        total_rtts = sum(s["rtts"].values())
        assert total_rtts < 2 * steps, (total_rtts, steps)
        deep.close(); wide.close()
        store.barrier(tag="trained")
        deep_remote.close(); wide_remote.close()
        srv.stop(); srv_w.stop()
        print("RANK", rank, "WD-CACHED OK", losses[0], "->", losses[-1],
              "hit_rate", round(s["hit_rate"], 3), "rtts", total_rtts,
              "steps", steps)
    """))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    log_dir = str(tmp_path / "logs")
    rc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", log_dir, str(script)],
        cwd=REPO, capture_output=True, timeout=300, env=env)
    assert rc.returncode == 0, (rc.stderr.decode()[-2000:],
                                rc.stdout.decode()[-500:])
    for r in range(2):
        with open(os.path.join(log_dir, f"workerlog.{r}")) as f:
            assert f"RANK {r} WD-CACHED OK" in f.read()


class TestRound5Hardening:
    def test_two_trainer_staleness_bound(self):
        """Trainer B reads trainer A's update after at most
        flush_interval of B's own steps (the EndPass merge bound the
        docstring promises): A pushes + flushes; B's interval refresh
        folds the server state in."""
        lr = 1.0
        remote = SparseTable(dim=4, optimizer="sgd", learning_rate=lr,
                             init_range=0.0, seed=1)
        k = 3
        a = HotRowCache(remote, optimizer="sgd", learning_rate=lr,
                        capacity=16)
        b = HotRowCache(remote, optimizer="sgd", learning_rate=lr,
                        capacity=16, flush_interval=k)
        keys = np.array([7], np.int64)
        a.pull(keys)
        b.pull(keys)                     # both cache the row (zeros)

        g = np.full((1, 4), 1.0, np.float32)
        a.push(keys, g)                  # A: w -= 1
        a.flush()                        # A's update reaches the server

        # B pushes a DISJOINT key so key 7 stays clean in B's cache
        other = np.array([9], np.int64)
        b.pull(other)
        seen = []
        for step in range(k):
            b.push(other, g)             # steps B's flush counter
            seen.append(float(np.asarray(b.pull(keys))[0, 0]))
        # staleness bound: by the k-th step the refresh has run
        assert seen[-1] == -1.0, seen
        # and before the boundary B legitimately served the stale row
        assert seen[0] == 0.0, seen

    def test_async_flush_matches_sync(self):
        """async_flush moves the RPCs off-thread but must produce the
        same server state and the same staleness boundary."""
        lr = 1.0
        rs, rb = (SparseTable(dim=4, optimizer="sgd", learning_rate=lr,
                              init_range=0.0, seed=2) for _ in range(2))
        sync = HotRowCache(rs, optimizer="sgd", learning_rate=lr,
                           capacity=16, flush_interval=2)
        asy = HotRowCache(rb, optimizer="sgd", learning_rate=lr,
                          capacity=16, flush_interval=2,
                          async_flush=True)
        keys = np.arange(6, dtype=np.int64)
        rng = np.random.RandomState(0)
        for _ in range(7):
            g = rng.randn(6, 4).astype(np.float32)
            sync.pull(keys)
            sync.push(keys, g)
            asy.pull(keys)
            asy.push(keys, g)
            asy.join_flush()      # deterministic comparison point
        sync.close()
        asy.close()
        np.testing.assert_allclose(np.asarray(rs.pull(keys)),
                                   np.asarray(rb.pull(keys)),
                                   rtol=1e-5, atol=1e-6)

    def test_async_flush_does_not_clobber_inflight_updates(self):
        """A push that lands while the background refresh RPC is in
        flight must survive: the refresh application skips slots
        dirtied after the snapshot."""
        import threading

        lr = 1.0

        class SlowTable(SparseTable):
            """Delays pull() until released — holds the refresh RPC
            open while the trainer keeps pushing."""

            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                self.gate = threading.Event()
                self.slow = False

            def pull(self, keys):
                if self.slow:
                    self.gate.wait(5.0)
                return super().pull(keys)

        remote = SlowTable(dim=4, optimizer="sgd", learning_rate=lr,
                           init_range=0.0, seed=3)
        cache = HotRowCache(remote, optimizer="sgd", learning_rate=lr,
                            capacity=16, async_flush=True)
        keys = np.array([5], np.int64)
        cache.pull(keys)
        g = np.full((1, 4), 1.0, np.float32)
        cache.push(keys, g)              # w = -1, dirty

        remote.slow = True
        t = cache.flush_async(refresh=True)   # snapshot w=-1, RPC stalls
        cache.push(keys, g)              # in-flight update: w = -2, dirty
        remote.gate.set()                # let the refresh pull complete
        t.join(10.0)
        assert not t.is_alive()
        # the stale refresh row (-1) must NOT have clobbered w=-2
        np.testing.assert_allclose(np.asarray(cache.pull(keys)),
                                   [[-2.0] * 4], rtol=1e-6)
        cache.close()
        # ...and after close() the server converges to the full history
        np.testing.assert_allclose(np.asarray(remote.pull(keys)),
                                   [[-2.0] * 4], rtol=1e-6)

    def test_admit_fully_releases_lock_for_reentrant_callers(self):
        """A caller already holding cache._lock (re-entrant RLock, depth
        2 inside _pull_locked) must not keep the lock pinned across the
        admission RPC: _admit's old bare release()/acquire() popped ONE
        level, so the lock stayed held for the whole RTT and any thread
        waiting on it (e.g. the async-flush refresh) deadlocked against
        a stalled remote.  The stub remote blocks its pull() until a
        helper thread actually acquires cache._lock — old code times
        out, the full-exit restructure lets it through."""
        import threading

        rpc_started = threading.Event()
        got_lock = threading.Event()

        class BlockingTable(SparseTable):
            """pull() stalls until another thread proves it can take
            the cache lock mid-RPC."""

            def pull(self, keys):
                rpc_started.set()
                assert got_lock.wait(5.0), \
                    "cache._lock still held during the admission RPC"
                return super().pull(keys)

        lr = 0.1
        remote = BlockingTable(dim=4, optimizer="sgd", learning_rate=lr,
                               init_range=0.01, seed=11)
        baseline = SparseTable(dim=4, optimizer="sgd", learning_rate=lr,
                               init_range=0.01, seed=11)
        cache = HotRowCache(remote, optimizer="sgd", learning_rate=lr,
                            capacity=16)

        def contender():
            rpc_started.wait(5.0)
            if cache._lock.acquire(timeout=5.0):
                cache._lock.release()
                got_lock.set()

        t = threading.Thread(target=contender, daemon=True)
        t.start()
        keys = np.arange(6, dtype=np.int64)
        with cache._lock:                 # re-entrant caller, depth 2+
            rows = np.asarray(cache.pull(keys))
        t.join(10.0)
        assert not t.is_alive()
        assert got_lock.is_set()
        # the fetch itself stayed exact, and state is coherent after
        np.testing.assert_allclose(rows, np.asarray(baseline.pull(keys)),
                                   rtol=1e-6)
        g = np.full((len(keys), 4), 0.5, np.float32)
        cache.push(keys, g)
        baseline.push(keys, g, learning_rate=lr)
        np.testing.assert_allclose(np.asarray(cache.pull(keys)),
                                   np.asarray(baseline.pull(keys)),
                                   rtol=1e-6)

    def test_pathological_duplicate_key_high_occupancy(self):
        """One hot key repeated 64x in a single push: 64 adagrad rounds
        must match the host table's sequential application exactly, and
        the power-of-two padding must keep the compile count bounded
        (weak-#7 regression: k rounds of dispatch, one compiled shape —
        asserted via the jitted update's cache size)."""
        lr = 0.1
        remote = SparseTable(dim=4, optimizer="adagrad",
                             learning_rate=lr, init_range=0.01, seed=23)
        baseline = SparseTable(dim=4, optimizer="adagrad",
                               learning_rate=lr, init_range=0.01,
                               seed=23)
        cache = HotRowCache(remote, optimizer="adagrad",
                            learning_rate=lr, capacity=8)
        rng = np.random.RandomState(0)
        hot = np.full(64, 5, np.int64)
        cold = np.arange(3, dtype=np.int64)
        keys = np.concatenate([hot, cold])
        g = rng.randn(len(keys), 4).astype(np.float32)

        from paddle_tpu.distributed.ps.heter import _adagrad_apply

        cache.pull(keys)
        before = _adagrad_apply._cache_size()
        cache.push(keys, g)
        # 64 rounds, but round sizes pad to powers of two: at most a
        # handful of distinct shapes may compile, never one per round
        assert _adagrad_apply._cache_size() - before <= 4, \
            _adagrad_apply._cache_size()
        cache.flush()

        baseline.pull(keys)
        baseline.push(keys, g, learning_rate=lr)
        np.testing.assert_allclose(np.asarray(remote.pull(keys)),
                                   np.asarray(baseline.pull(keys)),
                                   rtol=2e-5, atol=2e-6)
