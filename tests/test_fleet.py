"""Fleet serving: affinity router, health checking, token-exact failover.

The load-bearing claims: (1) the router's affinity keys ARE the hashes
the prefix cache registers pages under (one hashing authority), so
same-prefix traffic lands on warm pages; (2) the health state machine
has hysteresis — one missed heartbeat never flaps a replica, sustained
misses kill it; (3) a dead replica's requests replay on survivors
BITWISE-IDENTICAL to a fault-free single-engine run (exactness makes
failover a guarantee, not best-effort); (4) replicas share ONE compiled
executable set — replication and restarts never multiply compiles; and
(5) a seeded fleet-chaos schedule replays to an identical event log,
serial or thread-parallel stepping alike.
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
    """Step a fleet to completion (invariants checked every step);
    returns {rid: RequestOutput}."""
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


# ---------------------------------------------------------------------------
class TestRouterAffinity:
    def test_affinity_keys_equal_registered_cache_hashes(self):
        """The router keys prefix affinity on EXACTLY the content
        hashes the cache registers pages under: same function, same
        page size, same (n-1)//block_size admission cap."""
        from paddle_tpu.inference.llm import prefix_block_hashes

        m = _make_model()
        fleet = _tiny_fleet(m)
        prompt = list(range(20))           # 2 full pages + a tail
        keys = fleet.router.affinity_keys(prompt)
        bm = fleet.replicas[0].engine.block_manager
        assert keys == bm.prefix_chain_hashes(prompt, limit=2)
        assert keys == prefix_block_hashes(prompt, 8, limit=2)
        assert len(keys) == 2
        # run the prompt on a bare engine: every affinity key must now
        # be a registered cache hash (match_prefix finds them all)
        eng = _tiny_engine(m)
        eng.add_request(prompt, max_new_tokens=4)
        while eng.has_unfinished():
            eng.step()
        assert eng.block_manager.match_prefix(keys) == len(keys)

    def test_prefix_chain_hashes_respects_limit_and_page_size(self):
        from paddle_tpu.inference.llm import BlockManager

        bm = BlockManager(num_blocks=8, block_size=4)
        toks = list(range(13))             # 3 full pages + 1 token
        assert len(bm.prefix_chain_hashes(toks)) == 3
        assert bm.prefix_chain_hashes(toks, limit=1) == \
            bm.prefix_chain_hashes(toks)[:1]
        assert bm.prefix_chain_hashes(toks[:3]) == []

    def test_same_prefix_traffic_routes_to_the_warm_replica(self):
        m = _make_model()
        fleet = _tiny_fleet(m, replicas=3)
        rng = np.random.RandomState(1)
        prefix = rng.randint(0, 128, (16,)).astype(np.int32)

        def mk():
            return np.concatenate(
                [prefix, rng.randint(0, 128, (5,)).astype(np.int32)])

        r0 = fleet.add_request(mk(), max_new_tokens=2)
        r1 = fleet.add_request(mk(), max_new_tokens=2)
        r2 = fleet.add_request(mk(), max_new_tokens=2)
        routes = {e[2]: (e[3], e[4]) for e in fleet.events
                  if e[1] == "route"}
        # first request lands cold (score 0); the rest follow its warm
        # pages to the SAME replica with a positive affinity score
        assert routes[r0][1] == 0
        assert routes[r1] == (routes[r0][0], 2)
        assert routes[r2] == (routes[r0][0], 2)
        assert fleet.router.affinity_hits == 2
        _drive(fleet)

    def test_cold_traffic_falls_back_least_loaded(self):
        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2)
        prompts = _prompts(n=4)            # distinct prompts: no affinity
        rids = [fleet.add_request(p, max_new_tokens=2) for p in prompts]
        routes = [e[3] for e in fleet.events if e[1] == "route"]
        # score-0 requests spread by load with lowest-index tie-breaks:
        # 0 (tie), 1 (0 loaded), 0 (tie at 1), 1 (0 at 2)
        assert routes == [0, 1, 0, 1]
        outs = _drive(fleet)
        assert all(outs[r].ok for r in rids)


# ---------------------------------------------------------------------------
class TestHealthChecker:
    def test_one_missed_heartbeat_never_flaps(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        m = _make_model()
        fi = FaultInjector(schedule=[
            Fault("replica", "heartbeat", step=1, victim=1)])
        fleet = _tiny_fleet(m, replicas=2, faults=fi)
        for p in _prompts(n=2):
            fleet.add_request(p, max_new_tokens=6)
        _drive(fleet)
        assert fleet.replica_states() == {0: "healthy", 1: "healthy"}
        assert not any(e[1] in ("degraded", "dead")
                       for e in fleet.events)

    def test_sustained_misses_degrade_then_recover(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        m = _make_model()
        fi = FaultInjector(schedule=[
            Fault("replica", "heartbeat", step=s, victim=1)
            for s in (1, 2)])              # degraded_after=2 default
        fleet = _tiny_fleet(m, replicas=2, faults=fi)
        for p in _prompts(n=2):
            fleet.add_request(p, max_new_tokens=8)
        _drive(fleet)
        kinds = [e[1] for e in fleet.events
                 if e[1] in ("degraded", "recovered", "dead")]
        # two consecutive misses demote, two clean beats promote back
        assert kinds == ["degraded", "recovered"]
        assert fleet.replica_states()[1] == "healthy"

    def test_dead_after_misses_kills_and_fails_over(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        m = _make_model()
        fi = FaultInjector(schedule=[
            Fault("replica", "heartbeat", step=s, victim=1)
            for s in range(4)])            # dead_after=4 default
        fleet = _tiny_fleet(m, replicas=2, faults=fi)
        rids = [fleet.add_request(p, max_new_tokens=10)
                for p in _prompts(n=4)]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            outs = _drive(fleet)
        assert fleet.replica_states()[1] == "dead"
        # heartbeat death is ENGINE-ALIVE: the object still holds its
        # pages, so running sequences migrate (zero recompute) and only
        # never-admitted ones replay from scratch
        assert fleet.stats["migrated"] + fleet.stats["requeued"] > 0
        assert fleet.stats["migrated"] >= 1
        assert all(outs[r].ok for r in rids)
        # degraded -> dead walked the full hysteresis ladder
        kinds = [e[1] for e in fleet.events
                 if e[1] in ("degraded", "dead")]
        assert kinds == ["degraded", "dead"]

    def test_health_config_validation(self):
        from paddle_tpu.inference.llm import HealthConfig

        with pytest.raises(ValueError, match="degraded_after"):
            HealthConfig(degraded_after=3, dead_after=3)
        with pytest.raises(ValueError, match="recover_after"):
            HealthConfig(recover_after=0)
        with pytest.raises(TypeError, match="health="):
            HealthConfig.resolve(7)
        assert HealthConfig.resolve(
            {"dead_after": 9}).dead_after == 9


# ---------------------------------------------------------------------------
class TestFailover:
    def test_kill_mid_flight_is_token_exact_vs_single_engine(self):
        """The tentpole guarantee: kill a replica while its requests
        are mid-decode; the survivors' replays produce outputs
        bitwise-equal to a fault-free single-engine run."""
        m = _make_model()
        prompts = _prompts(n=6)
        ref_eng = _tiny_engine(m)
        ref_rids = [ref_eng.add_request(p, max_new_tokens=8)
                    for p in prompts]
        refs = {}
        while ref_eng.has_unfinished():
            for fo in ref_eng.step():
                refs[fo.request_id] = fo

        fleet = _tiny_fleet(m, replicas=2)
        rids = [fleet.add_request(p, max_new_tokens=8) for p in prompts]
        for _ in range(3):
            fleet.step()                   # mid-generation
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            assert fleet.kill_replica(1) is True
            outs = _drive(fleet)
        assert fleet.stats["requeued"] > 0
        for fr, rr in zip(rids, ref_rids):
            assert outs[fr].ok
            np.testing.assert_array_equal(outs[fr].all_ids,
                                          refs[rr].all_ids)
        # the survivor leaks nothing; the dead engine is never touched
        surv = fleet.replicas[0].engine
        assert surv.block_manager.num_free_blocks == surv.num_blocks
        assert fleet.kill_replica(1) is False    # already dead

    def test_no_survivors_finishes_requests_with_error(self):
        from paddle_tpu.inference.llm import FinishReason

        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2)
        rids = [fleet.add_request(p, max_new_tokens=10)
                for p in _prompts(n=3)]
        fleet.step()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            fleet.kill_replica(0)
            fleet.kill_replica(1)
        outs = _drive(fleet)
        assert {outs[r].finish_reason for r in rids} == \
            {FinishReason.ERROR}
        assert fleet.stats["lost"] == 3
        # a dead fleet sheds new arrivals instead of queueing them
        rid = fleet.add_request([1, 2, 3])
        out = {o.request_id: o for o in fleet.step()}[rid]
        assert out.finish_reason == FinishReason.SHED

    def test_step_exception_kills_only_the_raising_replica(self):
        """An engine whose step() raises (a consumed donated pool is
        unrecoverable — PoolLostError) dies immediately; its peers keep
        serving and its requests replay on them."""
        import types

        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2)
        rids = [fleet.add_request(p, max_new_tokens=8)
                for p in _prompts(n=4)]
        fleet.step()                       # both replicas mid-flight
        # simulate replica 1's donated K/V pool having been consumed:
        # its next launch fails and step() surfaces PoolLostError
        fleet.replicas[1].engine.kv_cache = {"k": types.SimpleNamespace(
            is_deleted=lambda: True)}
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            outs = _drive(fleet)
        assert fleet.replica_states()[0] == "healthy"
        assert fleet.replica_states()[1] == "dead"
        assert any(e[1] == "dead" and e[3] == "PoolLostError"
                   for e in fleet.events)
        assert fleet.stats["requeued"] >= 1
        assert all(outs[r].ok for r in rids)


# ---------------------------------------------------------------------------
class TestRollingDrain:
    def test_drain_reroutes_waiting_and_parks_drained(self):
        m = _make_model()
        # max_batch=1 keeps a waiting queue on each replica
        fleet = _tiny_fleet(m, replicas=2, max_batch=1)
        rids = [fleet.add_request(p, max_new_tokens=6)
                for p in _prompts(n=6)]
        fleet.step()                       # one running per replica
        assert fleet.drain_replica(1) is True
        assert fleet.replica_states()[1] == "draining"
        rerouted = [e for e in fleet.events if e[1] == "reroute"]
        assert rerouted and all(e[3] == 1 and e[4] == 0
                                for e in rerouted)
        outs = _drive(fleet)
        assert all(outs[r].ok for r in rids)
        assert fleet.replica_states()[1] == "drained"
        # drains never drop work and never leak pages
        for r in fleet.replicas:
            assert r.engine.block_manager.num_free_blocks == \
                r.engine.num_blocks
        assert fleet.drain_replica(1) is False   # already drained

    def test_restart_after_drain_and_after_death_zero_compiles(self):
        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2)
        watcher = fleet.warmup()
        fleet.drain_replica(1)
        fleet.step()                       # empty -> drained immediately
        fleet.restart_replica(1)
        assert fleet.replica_states()[1] == "healthy"
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            fleet.kill_replica(1)
            # a dead replica restarts with a FRESH engine that adopts
            # the fleet's shared executables: zero new compiles
            fleet.restart_replica(1)
        assert fleet.replica_states()[1] == "healthy"
        assert watcher.new_compiles() == []
        rid = fleet.add_request([1, 2, 3, 4], max_new_tokens=4)
        outs = _drive(fleet)
        assert outs[rid].ok
        assert watcher.new_compiles() == []
        with pytest.raises(RuntimeError, match="only drained or dead"):
            fleet.restart_replica(0)

    def test_replicas_share_one_executable_set(self):
        m = _make_model()
        fleet = _tiny_fleet(m, replicas=3)
        fns = {id(r.engine._ragged) for r in fleet.replicas}
        assert len(fns) == 1
        watcher = fleet.warmup()
        for p in _prompts(n=4):
            fleet.add_request(p, max_new_tokens=4)
        _drive(fleet)
        assert watcher.new_compiles() == []


# ---------------------------------------------------------------------------
class TestFleetAdmission:
    def test_max_queue_sheds_at_the_fleet_gate(self):
        from paddle_tpu.inference.llm import FinishReason

        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2, max_queue=2)
        rids = [fleet.add_request([1, 2, i], max_new_tokens=2)
                for i in range(4)]
        outs = _drive(fleet)
        reasons = [outs[r].finish_reason for r in rids]
        assert reasons[:2] == ["length", "length"]
        assert reasons[2:] == [FinishReason.SHED, FinishReason.SHED]
        assert fleet.stats["shed"] == 2
        assert fleet.lifecycle_stats()["shed"] == 2

    def test_fleet_drain_quiesces_and_reopens(self):
        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2)
        rids = [fleet.add_request(p, max_new_tokens=4)
                for p in _prompts(n=3)]
        outs = {o.request_id: o for o in fleet.drain()}
        assert all(outs[r].ok for r in rids)
        assert not fleet.has_unfinished()
        rid = fleet.add_request([5, 6, 7], max_new_tokens=3)
        outs = _drive(fleet)
        assert outs[rid].ok                # admission reopened

    def test_validation(self):
        from paddle_tpu.inference.llm import Fleet

        m = _make_model()
        with pytest.raises(ValueError, match="replicas"):
            Fleet(m, replicas=0)
        with pytest.raises(ValueError, match="max_queue"):
            _tiny_fleet(m, max_queue=0)
        with pytest.raises(ValueError, match="engine_faults"):
            _tiny_fleet(m, replicas=2, engine_faults=[None])


# ---------------------------------------------------------------------------
class TestFleetDeterminism:
    def _run(self, m, seed, parallel):
        from paddle_tpu.inference.llm import FaultInjector

        fi = FaultInjector.random_fleet(
            seed, steps=64, replicas=2, p_kill=0.03, p_heartbeat=0.1)
        fleet = _tiny_fleet(m, replicas=2, faults=fi,
                            parallel_step=parallel)
        prompts = _prompts(seed=3, n=5)
        outs = {}
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            for i, p in enumerate(prompts):
                fleet.add_request(p, max_new_tokens=6)
                outs.update(
                    {o.request_id: o for o in fleet.step()})
            outs.update(_drive(fleet))
        return fleet, fi, outs

    def test_seed_replay_identical_logs_serial_and_parallel(self):
        m = _make_model()
        fa, ia, oa = self._run(m, seed=5, parallel=False)
        fb, ib, ob = self._run(m, seed=5, parallel=False)
        fp, ip, op = self._run(m, seed=5, parallel=True)
        assert ia.events == ib.events == ip.events
        assert fa.events == fb.events == fp.events
        assert {r: o.finish_reason for r, o in oa.items()} == \
               {r: o.finish_reason for r, o in ob.items()} == \
               {r: o.finish_reason for r, o in op.items()}
        for rid, o in oa.items():
            np.testing.assert_array_equal(o.all_ids, op[rid].all_ids)


# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestFleetChaosSoak:
    """3 replicas, 256-step seeded chaos schedule (seed pinned so a
    kill fires mid-replay and a drain fires later): survivors
    token-exact vs a fault-free single-engine run, zero leaked pages on
    live replicas, zero post-warmup compiles through the shared
    watcher, and the seed replays to identical fleet + injector logs."""

    SEED = 95         # kill(step 10, victim 0), drain(step 19, victim 2)

    def _workload(self, seed=11, n=16):
        rng = np.random.RandomState(seed)
        return [rng.randint(0, 128, (int(rng.randint(4, 14)),))
                .astype(np.int32) for _ in range(n)]

    def _chaos(self, m, prompts):
        from paddle_tpu.inference.llm import FaultInjector

        fi = FaultInjector.random_fleet(
            self.SEED, steps=256, replicas=3, p_kill=0.02,
            p_heartbeat=0.06, p_drain=0.01)
        fleet = _tiny_fleet(m, replicas=3, faults=fi)
        watcher = fleet.warmup()
        outs = {}
        rids = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            # scripted arrivals: two requests every four fleet steps,
            # so the kill at step 25 lands mid-replay with work both
            # in flight and queued
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
                    fleet.check_invariants()
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
        # the schedule really exercised failover mid-replay
        assert fleet.stats["killed"] >= 1
        assert fleet.stats["requeued"] >= 1
        assert fleet.stats["drains"] >= 1
        assert len(outs) == len(prompts)
        survivors = [r for r in rids if outs[r].ok]
        assert survivors                   # the chaos left survivors
        for fr, rr in zip(rids, ref_rids):
            if outs[fr].ok:
                np.testing.assert_array_equal(outs[fr].all_ids,
                                              refs[rr].all_ids)
        for r in fleet.replicas:           # zero leaks on live replicas
            if r.live:
                assert r.engine.block_manager.num_free_blocks == \
                    r.engine.num_blocks
        # seed replay: identical injector events, fleet events, fates
        fleet_b, fi_b, rids_b, outs_b = self._chaos(m, prompts)
        assert fi.events == fi_b.events
        assert fleet.events == fleet_b.events
        assert {r: o.finish_reason for r, o in outs.items()} == \
               {r: o.finish_reason for r, o in outs_b.items()}


# ---------------------------------------------------------------------------
def test_fleet_bench_smoke(tmp_path):
    """benchmarks/bench_serving.py --replicas runs end to end on tiny
    parameters: shared executable signature sets across replicas, zero
    post-warmup compiles, a failover leg whose survivors stay
    token-exact with zero leaked pages, and the artifact lands
    (soak-scale chaos is TestFleetChaosSoak's job)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    artifact = str(tmp_path / "BENCH_fleet.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    rc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "benchmarks", "bench_serving.py"),
         "--replicas", "2", "--requests", "6", "--max-new", "6",
         "--max-batch", "2", "--token-budget", "16", "--kill-at", "3",
         "--no-baseline", "--repeats", "1", "--artifact", artifact],
        capture_output=True, text=True, timeout=480, env=env, cwd=repo)
    assert rc.returncode == 0, rc.stderr[-1500:]
    row = json.loads(rc.stdout.strip().splitlines()[-1])
    assert row["metric"] == "llm_serving_fleet"
    assert row["replicas"] == 2
    assert row["executables_shared"] is True
    assert row["new_compiles"] == 0
    assert row["failover"]["survivor_token_exact"] is True
    assert row["failover"]["leaked_pages"] == 0
    assert row["failover"]["killed"] == 1
    assert row["failover"]["requeued"] >= 1
    for key in ("affinity_hit_rate", "routed", "scaling_vs_1",
                "e2e_p95_ms"):
        assert key in row
    with open(artifact) as f:
        doc = json.load(f)
    assert doc["ok"] is True and doc["bench"]["metric"] == \
        "llm_serving_fleet"


# ---------------------------------------------------------------------------
class TestFleetServing:
    def test_predictor_server_fleet_kwarg(self):
        """PredictorServer(fleet=...) serves generative requests over
        the wire through the replica router, invisibly to clients."""
        import socket
        import struct

        from paddle_tpu.inference.serving import (
            PredictorServer,
            _recv_exact,
            _recv_tensor,
            _send_tensor,
        )

        m = _make_model()
        fleet = _tiny_fleet(m, replicas=2)
        srv = PredictorServer(fleet=fleet)
        try:
            s = socket.create_connection(("127.0.0.1", srv.port))
            try:
                s.sendall(struct.pack("<I", 2))
                _send_tensor(s, np.array([3, 4, 5], np.int64))
                _send_tensor(s, np.asarray(4, np.int64))
                status, n_out = struct.unpack("<BI", _recv_exact(s, 5))
                assert status == 0
                out = [_recv_tensor(s) for _ in range(n_out)][0]
                assert out.shape == (1, 7)
            finally:
                s.close()
        finally:
            srv.stop()

    def test_backend_kwarg_validation(self):
        from paddle_tpu.inference.serving import PredictorServer

        m = _make_model()
        fleet = _tiny_fleet(m)
        with pytest.raises(ValueError, match="exactly one"):
            PredictorServer()
        with pytest.raises(ValueError, match="exactly one"):
            PredictorServer(predictor=object(), fleet=fleet)
