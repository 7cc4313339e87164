"""KV page migration: token-exact mid-generation handoff.

The load-bearing claims: (1) BlockManager.export_seq/import_seq round-
trip a page chain between pools with refcounts collapsed to a private
copy, all-or-nothing on failure, invariants intact on the importing
pool; (2) an engine-level export/import transplants a RUNNING request
(pages + Request state) so decode resumes mid-generation BITWISE-
identical to an unmigrated run — prefix caching and speculative
decoding included; (3) drain and engine-alive failover migrate instead
of recomputing, gated by a cost-model MigrationPolicy, falling back to
the pre-migration behavior when migration faults — with exact page
reclamation on BOTH pools; (4) ``disaggregate=True`` hands every
sequence from a prefill-role to a decode-role replica at the
prefill→decode boundary through the same path; and (5) a seeded
migration-fault chaos schedule replays to identical event logs.

Satellites live here too: the Router's warm-hash map is LRU-bounded
(stable memory on a 10k-request trace), and Fleet.abort_request racing
_failover can no longer double-finish or resurrect a request.
"""

import warnings

import numpy as np
import pytest

import paddle_tpu as paddle


def _make_model(num_layers=2, seed=0):
    from paddle_tpu.models.gpt import gpt_tiny

    paddle.seed(seed)
    m = gpt_tiny(num_layers=num_layers)
    m.eval()
    return m


def _tiny_fleet(m, replicas=2, **kw):
    from paddle_tpu.inference.llm import Fleet

    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("token_budget", 16)
    return Fleet(m, replicas=replicas, **kw)


def _tiny_engine(m, **kw):
    from paddle_tpu.inference.llm import LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("token_budget", 16)
    return LLMEngine(m, **kw)


def _drive(fleet):
    outs = {}
    while fleet.has_unfinished():
        for fo in fleet.step():
            outs[fo.request_id] = fo
        fleet.check_invariants()
    return outs


def _prompts(seed=0, n=6):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 128, (int(rng.randint(4, 14)),))
            .astype(np.int32) for _ in range(n)]


def _assert_no_leaks(fleet):
    """Every live replica's pool fully reclaimed (cached LRU pages
    count as free — they are adoptable on demand)."""
    for r in fleet.replicas:
        if r.live:
            assert r.engine.block_manager.num_free_blocks == \
                r.engine.num_blocks, f"replica {r.index} leaked pages"


# ---------------------------------------------------------------------------
class TestBlockManagerExportImport:
    def _pool(self, num_blocks=16, block_size=8):
        from paddle_tpu.inference.llm import BlockManager

        return BlockManager(num_blocks, block_size,
                            enable_prefix_caching=True)

    def _seed_seq(self, bm, seq_id, tokens):
        """Allocate + register full pages exactly like the engine
        does (hash authority: prefix_chain_hashes)."""
        bm.allocate(seq_id, len(tokens))
        hashes = bm.prefix_chain_hashes(tokens)
        for i, h in enumerate(hashes[:len(tokens) // bm.block_size]):
            bm.register_full_block(seq_id, i, h)
        return hashes

    def test_round_trip_partially_full_tail(self):
        src, dst = self._pool(), self._pool()
        tokens = list(range(20))              # 2 full pages + 4-token tail
        self._seed_seq(src, "s", tokens)
        exp = src.export_seq("s")
        assert exp["num_tokens"] == 20
        assert exp["page_tokens"] == [8, 8, 4]
        assert len(exp["block_ids"]) == 3
        assert exp["hashes"][2] is None       # tail page never registers
        assert exp["hashes"][0] is not None

        table = dst.import_seq("s", exp)
        assert len(table) == 3
        assert dst.num_tokens("s") == 20
        dst.register_imported("s", exp["hashes"])
        src.check_invariants()
        dst.check_invariants()
        # the importing pool's prefix cache now serves the full pages
        assert dst.match_prefix(exp["hashes"][:2]) == 2
        # export is read-only: the source still owns its chain
        assert src.has_seq("s") and src.num_tokens("s") == 20

    def test_import_collapses_shared_refcounts(self):
        src, dst = self._pool(), self._pool()
        self._seed_seq(src, "a", list(range(16)))
        src.fork("a", "b")                    # every page now ref 2
        exp = src.export_seq("a")
        dst.import_seq("a", exp)
        dst.register_imported("a", exp["hashes"])
        dst.check_invariants()
        for blk in dst.block_table("a"):      # private copy: ref 1
            assert dst._ref[blk] == 1
        dst.free("a")
        dst.check_invariants()
        assert dst.num_free_blocks == dst.num_blocks

    def test_cow_forked_tail_round_trips(self):
        src, dst = self._pool(), self._pool()
        self._seed_seq(src, "p", list(range(12)))
        src.fork("p", "c")
        src.append_slot("c")                  # COW-copies the shared tail
        src.check_invariants()
        exp = src.export_seq("c")
        assert exp["num_tokens"] == 13
        dst.import_seq("c", exp)
        dst.register_imported("c", exp["hashes"])
        src.check_invariants()
        dst.check_invariants()
        assert dst.num_tokens("c") == 13

    def test_corrupt_export_rejected(self):
        src, dst = self._pool(), self._pool()
        self._seed_seq(src, "s", list(range(20)))
        exp = src.export_seq("s")
        exp["block_ids"] = exp["block_ids"][:-1]
        before = dst.num_free_blocks
        with pytest.raises(ValueError, match="corrupt export"):
            dst.import_seq("s", exp)
        assert dst.num_free_blocks == before and not dst.has_seq("s")

    def test_import_all_or_nothing_on_exhausted_pool(self):
        from paddle_tpu.inference.llm import NoFreeBlocksError

        src = self._pool()
        dst = self._pool(num_blocks=2)
        self._seed_seq(src, "s", list(range(20)))   # needs 3 pages
        exp = src.export_seq("s")
        with pytest.raises(NoFreeBlocksError):
            dst.import_seq("s", exp)
        assert dst.num_free_blocks == 2 and not dst.has_seq("s")
        dst.check_invariants()

    def test_invariants_and_growth_on_imported_pool(self):
        src, dst = self._pool(), self._pool()
        self._seed_seq(src, "s", list(range(20)))
        exp = src.export_seq("s")
        dst.import_seq("s", exp)
        dst.register_imported("s", exp["hashes"])
        # the imported chain keeps growing like a native one: fill the
        # tail, cross a page boundary, then release everything
        for _ in range(8):
            dst.append_slot("s")
        dst.check_invariants()
        assert dst.num_tokens("s") == 28
        assert len(dst.block_table("s")) == 4
        dst.free("s")
        dst.check_invariants()
        assert dst.num_free_blocks == dst.num_blocks

    def test_export_unknown_seq_raises(self):
        with pytest.raises(KeyError, match="owns no pages"):
            self._pool().export_seq("ghost")


# ---------------------------------------------------------------------------
class TestEngineMigration:
    def test_export_import_resumes_token_exact(self):
        """Transplant a RUNNING request between two engines mid-decode;
        the merged outputs are bitwise-equal to one unmigrated engine."""
        m = _make_model()
        ref = _tiny_engine(m)
        prompts = _prompts(n=3)
        want = ref.generate(prompts, max_new_tokens=10)

        fleet = _tiny_fleet(m, replicas=2)      # two engines, one
        e0 = fleet.replicas[0].engine           # compile set
        e1 = fleet.replicas[1].engine
        rids = [e0.add_request(p, max_new_tokens=10) for p in prompts]
        outs = {}
        for _ in range(4):                      # everyone mid-decode
            for fo in e0.step():
                outs[fo.request_id] = fo
        mover = rids[1]
        assert len(e0._requests[mover].output_ids) >= 1
        state = e0.export_request(mover)
        e1.import_request(state["request"], state["seq"],
                          state["k_pages"], state["v_pages"])
        e0.release_request(mover)
        e0.scheduler.check_invariants()
        e1.scheduler.check_invariants()
        while e0.has_unfinished() or e1.has_unfinished():
            for fo in e0.step() + e1.step():
                outs[fo.request_id] = fo
        for rid, w in zip(rids, want):
            np.testing.assert_array_equal(outs[rid].all_ids, w)
        # engine logs carry the handoff
        assert any(e[1] == "export" for e in e0.events)
        assert any(e[1] == "release" for e in e0.events)
        assert any(e[1] == "import" for e in e1.events)

    def test_import_capacity_and_shape_guards(self):
        from paddle_tpu.inference.llm import MigrationError

        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2, max_batch=1)
        e0, e1 = (r.engine for r in fleet.replicas)
        r0 = e0.add_request(_prompts(n=1)[0], max_new_tokens=8,
                            request_id="mover")
        r1 = e1.add_request(_prompts(seed=1, n=1)[0], max_new_tokens=8,
                            request_id="homebody")
        for _ in range(3):
            e0.step()
            e1.step()
        state = e0.export_request(r0)
        # destination running set full -> MigrationError("capacity"),
        # nothing allocated
        before = e1.block_manager.num_free_blocks
        with pytest.raises(MigrationError) as ei:
            e1.import_request(state["request"], state["seq"],
                              state["k_pages"], state["v_pages"])
        assert ei.value.reason == "capacity"
        assert e1.block_manager.num_free_blocks == before
        assert r1 in e1._requests
        # wrong payload shape -> ValueError, nothing allocated
        outs = {}
        while e1.has_unfinished():
            for fo in e1.step():
                outs[fo.request_id] = fo
        before = e1.block_manager.num_free_blocks
        with pytest.raises(ValueError, match="payload"):
            e1.import_request(state["request"], state["seq"],
                              state["k_pages"][:, :, :, :4],
                              state["v_pages"][:, :, :, :4])
        assert e1.block_manager.num_free_blocks == before

    def test_import_fault_reclaims_exactly(self):
        """A fault between allocation and registration frees exactly
        the imported pages — the destination pool is untouched and the
        source still serves the request."""
        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2)
        e0, e1 = (r.engine for r in fleet.replicas)
        rid = e0.add_request(_prompts(n=1)[0], max_new_tokens=8)
        for _ in range(3):
            e0.step()
        state = e0.export_request(rid)
        before = e1.block_manager.num_free_blocks

        def boom():
            raise RuntimeError("mid-import fault")

        with pytest.raises(RuntimeError, match="mid-import"):
            e1.import_request(state["request"], state["seq"],
                              state["k_pages"], state["v_pages"],
                              fault_hook=boom)
        assert e1.block_manager.num_free_blocks == before
        assert rid not in e1._requests
        assert not e1.block_manager.has_seq(rid)
        e1.scheduler.check_invariants()
        # the source kept serving: export is read-only until release
        assert e0.block_manager.has_seq(rid)
        while e0.has_unfinished():
            e0.step()
        e0.scheduler.check_invariants()

    def test_export_guards(self):
        m = _make_model()
        eng = _tiny_engine(m)
        with pytest.raises(KeyError, match="unknown request"):
            eng.export_request("ghost")
        rid = eng.add_request(_prompts(n=1)[0], max_new_tokens=4)
        with pytest.raises(ValueError, match="only running"):
            eng.export_request(rid)         # still waiting: no pages
        while eng.has_unfinished():
            eng.step()


# ---------------------------------------------------------------------------
class TestMigrationPolicy:
    def test_validation_and_resolve(self):
        from paddle_tpu.inference.llm import MigrationPolicy

        with pytest.raises(ValueError, match="mode"):
            MigrationPolicy(mode="sometimes")
        with pytest.raises(ValueError, match="profile"):
            MigrationPolicy(profile="tpu-v9")
        with pytest.raises(ValueError, match="link_gbps"):
            MigrationPolicy(link_gbps=0)
        with pytest.raises(TypeError, match="migration="):
            MigrationPolicy.resolve(7)
        assert MigrationPolicy.resolve(None).mode == "auto"
        assert MigrationPolicy.resolve("never").mode == "never"
        assert MigrationPolicy.resolve(
            {"mode": "always", "link_gbps": 2.5}).link_gbps == 2.5
        p = MigrationPolicy()
        assert MigrationPolicy.resolve(p) is p

    def test_estimate_and_decide(self):
        from paddle_tpu.inference.llm import MigrationPolicy

        m = _make_model()
        eng = _tiny_engine(m)
        rid = eng.add_request(np.arange(10, dtype=np.int32),
                              max_new_tokens=6)
        for _ in range(3):
            eng.step()
        req = eng._requests[rid]
        pol = MigrationPolicy()
        est = pol.estimate(eng, req)
        assert est["bytes_moved"] > 0 and est["recompute_flops"] > 0
        assert est["prefer"] in ("migrate", "recompute")
        assert pol.decide(eng, req) == est["prefer"]
        # moving KV pages beats re-running the weights for every cached
        # token whenever 2*params*tokens dwarfs the page bytes — it
        # does for any real model under any bundled profile
        assert est["prefer"] == "migrate"
        assert MigrationPolicy(mode="never").decide(eng, req) \
            == "recompute"
        assert MigrationPolicy(mode="always").decide(eng, req) \
            == "migrate"
        while eng.has_unfinished():
            eng.step()


# ---------------------------------------------------------------------------
class TestFleetMigration:
    def test_drain_migrates_running_token_exact(self):
        """Drain mid-decode: running sequences MOVE to the peer (zero
        recompute) and every output stays bitwise-exact."""
        m = _make_model()
        ref = _tiny_engine(m)
        prompts = _prompts(n=6)
        want = ref.generate(prompts, max_new_tokens=10)

        fleet = _tiny_fleet(m, replicas=2)
        rids = [fleet.add_request(p, max_new_tokens=10)
                for p in prompts]
        outs = {}
        step = 0
        while fleet.has_unfinished():
            for fo in fleet.step():
                outs[fo.request_id] = fo
            if step == 3:
                fleet.drain_replica(1)
            fleet.check_invariants()
            step += 1
        for rid, w in zip(rids, want):
            np.testing.assert_array_equal(outs[rid].all_ids, w)
        assert fleet.stats["migrated"] >= 1
        assert fleet.stats["requeued"] == 0      # nothing recomputed
        assert fleet.stats["migrated_bytes"] > 0
        assert fleet.replica_states()[1] == "drained"
        assert any(e[1] == "migrate" for e in fleet.events)
        assert len(fleet.migration_ms) == fleet.stats["migrated"]
        _assert_no_leaks(fleet)

    def test_engine_alive_failover_migrates_without_recompute(self):
        """Heartbeat death leaves the engine object intact, so its
        RUNNING sequences migrate — the acceptance criterion 'failover
        of a live replica completes without recompute'."""
        from paddle_tpu.inference.llm import Fault, FaultInjector

        m = _make_model()
        ref = _tiny_engine(m)
        prompts = _prompts(n=4)
        want = ref.generate(prompts, max_new_tokens=10)

        fi = FaultInjector(schedule=[
            Fault("replica", "heartbeat", step=s, victim=1)
            for s in range(6)])
        fleet = _tiny_fleet(m, replicas=2, faults=fi)
        rids = [fleet.add_request(p, max_new_tokens=10)
                for p in prompts]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            outs = _drive(fleet)
        assert fleet.replica_states()[1] == "dead"
        assert fleet.stats["migrated"] >= 1
        migrated = {e[2] for e in fleet.events if e[1] == "migrate"}
        requeued = {e[2] for e in fleet.events if e[1] == "failover"}
        assert migrated and not migrated & requeued
        for rid, w in zip(rids, want):
            np.testing.assert_array_equal(outs[rid].all_ids, w)
        _assert_no_leaks(fleet)

    def test_policy_never_falls_back_to_finish_in_place(self):
        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2, migration="never")
        rids = [fleet.add_request(p, max_new_tokens=8)
                for p in _prompts(n=4)]
        outs = {}
        step = 0
        while fleet.has_unfinished():
            for fo in fleet.step():
                outs[fo.request_id] = fo
            if step == 3:
                fleet.drain_replica(1)
            step += 1
        assert fleet.stats["migrated"] == 0
        assert fleet.stats["migration_recomputed"] >= 1
        assert any(e[1] == "migrate_skip" for e in fleet.events)
        assert all(outs[r].ok for r in rids)
        _assert_no_leaks(fleet)

    def test_lifecycle_stats_migration_counters(self):
        m = _make_model()
        fleet = _tiny_fleet(m)
        ls = fleet.lifecycle_stats()
        for key in ("migrated", "migration_recomputed",
                    "migration_failed", "migrated_bytes"):
            assert ls[key] == 0


# ---------------------------------------------------------------------------
class TestDisaggregated:
    def test_token_exact_with_prefix_cache_and_spec(self):
        """Disaggregated serving is invisible to outputs — prefix-cache
        adoption on the prefill side and n-gram speculation on the
        decode side included (the acceptance criterion's hard case)."""
        rng = np.random.RandomState(7)
        shared = rng.randint(0, 128, (16,)).astype(np.int32)
        pat = rng.randint(0, 128, (5,)).astype(np.int32)
        prompts = [np.concatenate([shared, np.tile(pat, 2),
                                   rng.randint(0, 128, (i + 2,))
                                   .astype(np.int32)])
                   for i in range(5)]

        m = _make_model()
        ref = _tiny_engine(m, speculative=2)
        want = ref.generate(prompts, max_new_tokens=10)

        fleet = _tiny_fleet(m, replicas=2, disaggregate=True,
                            speculative=2)
        assert fleet.roles() == {0: "prefill", 1: "decode"}
        watcher = fleet.warmup()
        got = fleet.generate(prompts, max_new_tokens=10)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        # every sequence crossed the boundary exactly once
        assert fleet.stats["migrated"] == len(prompts)
        migr = [e for e in fleet.events if e[1] == "migrate"]
        assert all(e[3] == 0 and e[4] == 1 for e in migr)
        assert fleet.prefix_cache_stats()["prefix_hit_tokens"] > 0
        assert watcher.new_compiles() == []
        fleet.check_invariants()
        _assert_no_leaks(fleet)

    def test_degrades_to_unified_without_decode_replicas(self):
        """Killing the only decode replica must not stall prefilled
        sequences — they decode where they are and new work keeps
        flowing (specialization is a preference, not a constraint)."""
        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2, disaggregate=True)
        prompts = _prompts(n=4)
        rids = [fleet.add_request(p, max_new_tokens=8)
                for p in prompts]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            fleet.step()
            fleet.kill_replica(1)            # decode role gone
            outs = _drive(fleet)
        assert all(outs[r].ok for r in rids)
        _assert_no_leaks(fleet)

    def test_validation(self):
        m = _make_model()
        with pytest.raises(ValueError, match="disaggregate"):
            _tiny_fleet(m, replicas=1, disaggregate=True)


# ---------------------------------------------------------------------------
class TestRouterWarmLRU:
    def test_10k_request_trace_memory_bounded(self):
        """Satellite regression: the warm-hash affinity map is an LRU
        capped at warm_cap — a 10k-request synthetic trace (every
        prompt distinct, 3 page hashes each) leaves bounded state, not
        30k entries."""
        m = _make_model()
        fleet = _tiny_fleet(m)
        router = fleet.router
        replica = fleet.replicas[0]
        for i in range(10_000):
            keys = (("t", i, 0), ("t", i, 1), ("t", i, 2))
            router.record(replica, keys, hit=False)
        assert len(replica.warm_hashes) == router.warm_cap == 4096
        # LRU semantics: the newest keys are the ones retained
        assert ("t", 9_999, 2) in replica.warm_hashes
        assert ("t", 0, 0) not in replica.warm_hashes
        # re-touching an old survivor moves it to the safe end
        survivor = next(iter(replica.warm_hashes))
        router.touch(replica, [survivor])
        router.record(replica, [("fresh", i) for i in range(4095)],
                      hit=False)
        assert survivor in replica.warm_hashes

    def test_warm_cap_validation(self):
        from paddle_tpu.inference.llm import Router

        with pytest.raises(ValueError, match="warm_cap"):
            Router([], warm_cap=0)


# ---------------------------------------------------------------------------
class TestAbortFailoverRace:
    def test_abort_then_death_single_terminal_output(self):
        """Deterministic interleaving of the satellite race: abort a
        request, then kill its owner BEFORE the engine's aborted output
        is forwarded.  The fleet must emit exactly ONE terminal output
        (aborted) and never resurrect the request on the survivor."""
        from paddle_tpu.inference.llm import FinishReason

        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2)
        prompts = _prompts(n=4)
        rids = [fleet.add_request(p, max_new_tokens=10)
                for p in prompts]
        fleet.step()
        victim = next(rid for rid in rids
                      if fleet._live[rid].replica == 1)
        assert fleet.abort_request(victim) is True
        assert fleet.abort_request(victim) is False    # claimed once
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            fleet.kill_replica(1)       # races the pending abort
            outs = []
            while fleet.has_unfinished():
                outs.extend(fleet.step())
        mine = [o for o in outs if o.request_id == victim]
        assert len(mine) == 1
        assert mine[0].finish_reason == FinishReason.ABORTED
        # never requeued, never migrated after the claim
        assert not any(e[1] in ("failover", "migrate") and e[2] == victim
                       for e in fleet.events)
        finishes = [e for e in fleet.events
                    if e[1] == "finish" and e[2] == victim]
        assert len(finishes) == 1
        # everyone else finished normally on the survivor
        others = {o.request_id: o for o in outs
                  if o.request_id != victim}
        assert all(others[r].ok for r in rids if r != victim)

    def test_abort_before_drain_not_rerouted(self):
        """A claimed (aborting) request is skipped by the drain's
        waiting-reroute — cancelled work never moves to a peer."""
        from paddle_tpu.inference.llm import FinishReason

        m = _make_model(num_layers=1)
        fleet = _tiny_fleet(m, replicas=2, max_batch=1)
        rids = [fleet.add_request(p, max_new_tokens=8)
                for p in _prompts(n=4)]
        fleet.step()
        waiting_on_1 = [rid for rid in rids
                        if fleet._live[rid].replica == 1
                        and rid in {q.request_id for q in
                                    fleet.replicas[1].engine
                                    .scheduler.waiting}]
        if not waiting_on_1:
            pytest.skip("routing left no waiting request on replica 1")
        victim = waiting_on_1[0]
        fleet.abort_request(victim)
        fleet.drain_replica(1)
        assert not any(e[1] == "reroute" and e[2] == victim
                       for e in fleet.events)
        outs = _drive(fleet)
        assert outs[victim].finish_reason == FinishReason.ABORTED


# ---------------------------------------------------------------------------
class TestMigrationFaults:
    def test_export_fault_falls_back(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        m = _make_model()
        ref = _tiny_engine(m)
        prompts = _prompts(n=6)
        want = ref.generate(prompts, max_new_tokens=10)

        fi = FaultInjector(schedule=[Fault("migration", "export",
                                           step=3)])
        fleet = _tiny_fleet(m, replicas=2, faults=fi)
        rids = [fleet.add_request(p, max_new_tokens=10)
                for p in prompts]
        outs = {}
        step = 0
        while fleet.has_unfinished():
            for fo in fleet.step():
                outs[fo.request_id] = fo
            if step == 3:
                fleet.drain_replica(1)
            fleet.check_invariants()
            step += 1
        assert fleet.stats["migration_failed"] == 1
        fails = [e for e in fleet.events if e[1] == "migrate_fail"]
        assert fails and fails[0][5] == "export"
        assert fi.events == [(3, "migration", "export", 0)]
        for rid, w in zip(rids, want):
            np.testing.assert_array_equal(outs[rid].all_ids, w)
        _assert_no_leaks(fleet)

    def test_import_fault_exact_reclamation_both_pools(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        m = _make_model()
        fi = FaultInjector(schedule=[Fault("migration", "import",
                                           step=3)])
        fleet = _tiny_fleet(m, replicas=2, faults=fi)
        prompts = _prompts(n=4)
        rids = [fleet.add_request(p, max_new_tokens=10)
                for p in prompts]
        outs = {}
        for _ in range(4):                  # fleet step index reaches 3
            for fo in fleet.step():
                outs[fo.request_id] = fo
        src = fleet.replicas[1].engine
        dst = fleet.replicas[0].engine
        src_before = src.block_manager.num_free_blocks
        dst_before = dst.block_manager.num_free_blocks
        pages_of = {rid: len(src.block_manager.block_table(rid))
                    for rid in src.block_manager._tables}
        fleet.drain_replica(1)              # attempt faults mid-import
        assert fleet.stats["migration_failed"] >= 1
        fails = [e for e in fleet.events if e[1] == "migrate_fail"]
        moved = [e for e in fleet.events if e[1] == "migrate"]
        assert fails[0][5] == "import"
        faulted_rid = fails[0][2]
        # EXACT reclamation, both pools: the destination holds exactly
        # the pages of the migrations that SUCCEEDED (the aborted
        # import freed everything it allocated), and the source still
        # owns the faulted chain untouched (it finishes in place)
        assert dst.block_manager.num_free_blocks == \
            dst_before - sum(e[5] for e in moved)
        assert src.block_manager.num_free_blocks == \
            src_before + sum(pages_of[e[2]] for e in moved)
        assert src.block_manager.has_seq(faulted_rid)
        assert len(src.block_manager.block_table(faulted_rid)) == \
            pages_of[faulted_rid]
        assert not dst.block_manager.has_seq(faulted_rid)
        fleet.check_invariants()
        outs.update(_drive(fleet))
        assert all(outs[r].ok for r in rids)
        _assert_no_leaks(fleet)

    def test_delay_fault_only_slows(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        m = _make_model()
        fi = FaultInjector(schedule=[
            Fault("migration", "delay", step=3, delay_s=0.01)])
        fleet = _tiny_fleet(m, replicas=2, faults=fi)
        rids = [fleet.add_request(p, max_new_tokens=8)
                for p in _prompts(n=4)]
        outs = {}
        step = 0
        while fleet.has_unfinished():
            for fo in fleet.step():
                outs[fo.request_id] = fo
            if step == 3:
                fleet.drain_replica(1)
            step += 1
        assert fleet.stats["migration_failed"] == 0
        if fleet.stats["migrated"]:          # the delay hit a real move
            assert max(fleet.migration_ms) >= 10.0
        assert all(outs[r].ok for r in rids)

    def test_migration_site_validation(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        with pytest.raises(ValueError, match="migration"):
            FaultInjector(schedule=[Fault("migration", "bogus",
                                          step=0)])

    def test_random_fleet_migration_stream_is_independent(self):
        """Adding p_migration must not perturb the replica-site
        schedule — pinned chaos seeds (and their replays) stay valid."""
        from paddle_tpu.inference.llm import FaultInjector

        base = FaultInjector.random_fleet(
            95, steps=256, replicas=3, p_kill=0.02, p_heartbeat=0.06,
            p_drain=0.01)
        plus = FaultInjector.random_fleet(
            95, steps=256, replicas=3, p_kill=0.02, p_heartbeat=0.06,
            p_drain=0.01, p_migration=0.3)
        pick = lambda fi: [(f.kind, f.step, f.victim)  # noqa: E731
                           for f in fi.schedule if f.site == "replica"]
        assert pick(base) == pick(plus)
        assert any(f.site == "migration" for f in plus.schedule)


# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestMigrationChaosSoak:
    """Disaggregated 3-replica fleet (1 prefill + 2 decode) under a
    256-step seeded schedule of heartbeat misses, drains AND migration
    faults: every handoff that faults falls back and retries, survivors
    stay bitwise-exact vs a fault-free single engine, page accounting
    balances on EVERY pool at EVERY step, and the seed replays to
    identical injector + fleet event logs."""

    SEED = 29

    def _workload(self, seed=11, n=14):
        rng = np.random.RandomState(seed)
        return [rng.randint(0, 128, (int(rng.randint(4, 14)),))
                .astype(np.int32) for _ in range(n)]

    def _chaos(self, m, prompts):
        from paddle_tpu.inference.llm import FaultInjector

        fi = FaultInjector.random_fleet(
            self.SEED, steps=256, replicas=3, p_heartbeat=0.04,
            p_drain=0.008, p_migration=0.3)
        fleet = _tiny_fleet(m, replicas=3, disaggregate=True,
                            faults=fi)
        watcher = fleet.warmup()
        outs = {}
        rids = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            i = 0
            while i < len(prompts) or fleet.has_unfinished():
                if i < len(prompts):
                    for p in prompts[i:i + 2]:
                        rids.append(
                            fleet.add_request(p, max_new_tokens=10))
                    i += 2
                for _ in range(4):
                    for fo in fleet.step():
                        outs[fo.request_id] = fo
                    # page conservation on EVERY pool, EVERY step —
                    # a faulted import that leaked even one page
                    # breaks the balance immediately
                    fleet.check_invariants()
                    for r in fleet.replicas:
                        if r.live:
                            r.engine.block_manager.check_invariants()
        assert watcher.new_compiles() == []
        return fleet, fi, rids, outs

    def test_soak(self):
        m = _make_model()
        prompts = self._workload()
        ref_eng = _tiny_engine(m)
        refs = {}
        ref_rids = [ref_eng.add_request(p, max_new_tokens=10)
                    for p in prompts]
        while ref_eng.has_unfinished():
            for fo in ref_eng.step():
                refs[fo.request_id] = fo

        fleet, fi, rids, outs = self._chaos(m, prompts)
        # the schedule really exercised the migration machinery
        assert fleet.stats["migrated"] >= len(prompts) // 2
        assert fleet.stats["migration_failed"] >= 1
        assert any(k == "migration" for _, k, *_ in fi.events)
        assert len(outs) == len(prompts)
        survivors = [r for r in rids if outs[r].ok]
        assert survivors
        for fr, rr in zip(rids, ref_rids):
            if outs[fr].ok:
                np.testing.assert_array_equal(outs[fr].all_ids,
                                              refs[rr].all_ids)
        _assert_no_leaks(fleet)
        # seed replay: identical injector events, fleet events, fates
        fleet_b, fi_b, rids_b, outs_b = self._chaos(m, prompts)
        assert fi.events == fi_b.events
        assert fleet.events == fleet_b.events
        assert {r: o.finish_reason for r, o in outs.items()} == \
               {r: o.finish_reason for r, o in outs_b.items()}


# ---------------------------------------------------------------------------
def test_disagg_bench_smoke(tmp_path):
    """benchmarks/bench_serving.py --disaggregate runs end to end on
    tiny parameters with a migration-fault schedule: token-exact vs
    the single engine, zero leaked pages on every pool, zero new
    compiles, handoff latency percentiles in the row, artifact lands."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    artifact = str(tmp_path / "BENCH_disagg.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    rc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "benchmarks", "bench_serving.py"),
         "--replicas", "2", "--disaggregate", "--migrate-chaos", "7",
         "--requests", "6", "--max-new", "6", "--max-batch", "2",
         "--token-budget", "16", "--artifact", artifact],
        capture_output=True, text=True, timeout=480, env=env, cwd=repo)
    assert rc.returncode == 0, rc.stderr[-1500:]
    row = json.loads(rc.stdout.strip().splitlines()[-1])
    assert row["metric"] == "llm_serving_disagg"
    assert row["roles"] == {"0": "prefill", "1": "decode"}
    assert row["token_exact"] is True
    assert row["leaked_pages"] == 0
    assert row["new_compiles"] == 0
    assert row["executables_shared"] is True
    assert row["migrated"] >= 1
    assert row["migrated_bytes"] > 0
    assert row["handoff_p50_ms"] is not None
    assert row["handoff_p95_ms"] >= row["handoff_p50_ms"]
    with open(artifact) as f:
        doc = json.load(f)
    assert doc["ok"] is True and doc["bench"]["metric"] == \
        "llm_serving_disagg"
