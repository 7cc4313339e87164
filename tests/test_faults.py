"""Request-lifecycle hardening + deterministic fault injection.

The load-bearing claims: (1) a request can be cancelled in ANY state —
waiting, chunk-prefilling, decoding, holding a speculative reservation,
preempted, COW-forked — with pages reclaimed refcount-exactly; (2) the
failure paths (abort / deadline / shed / quarantine) have DEFINED
FinishReasons and leave survivors token-exact; (3) every fault schedule
is replayable from its seed — two runs of the same seed produce
identical engine event logs, which is what makes a chaos failure
debuggable instead of anecdotal.
"""

import socket
import struct
import threading
import time
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


class _FakeClock:
    """Injectable monotonic clock: deadline tests advance time by hand,
    so a missed deadline is a scheduling decision, not a sleep()."""

    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


_FAST_RETRY = {"max_attempts": 3, "base_delay_s": 0.0, "jitter": 0.0}


def _drive(eng, faults=None):
    """Step an engine to completion, checking allocator invariants after
    every step; applies "client"-site faults (abort the oldest live
    request) the way a chaos driver would.  Returns {rid: output}."""
    outs = {}
    while eng.has_unfinished():
        if faults is not None and \
                faults.scheduled("client", eng._step_index + 1):
            live = sorted(eng._requests)
            if live:
                eng.abort_request(live[0])
        for fo in eng.step():
            outs[fo.request_id] = fo
        eng.scheduler.check_invariants()
    return outs


def _tiny_engine(m, **kw):
    from paddle_tpu.inference.llm import LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("token_budget", 16)
    return LLMEngine(m, **kw)


# ---------------------------------------------------------------------------
class TestFinishReason:
    def test_vocabulary_and_done_family(self):
        from paddle_tpu.inference.llm import FinishReason as FR

        assert set(FR.ALL) == {"stop", "length", "aborted", "deadline",
                               "shed", "error"}
        assert FR.is_done("stop") and FR.is_done("length")
        for r in ("aborted", "deadline", "shed", "error"):
            assert not FR.is_done(r)


class TestFaultInjectorUnit:
    def test_random_schedule_is_seed_deterministic(self):
        from paddle_tpu.inference.llm import FaultInjector

        kw = dict(steps=64, p_step=0.1, p_transient=0.1, p_oom=0.1,
                  p_delay=0.05, p_abort=0.05, delay_s=0.001)
        a = FaultInjector.random(7, **kw)
        b = FaultInjector.random(7, **kw)
        assert a.schedule == b.schedule and a.schedule
        c = FaultInjector.random(8, **kw)
        assert c.schedule != a.schedule

    def test_unknown_site_rejected(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        with pytest.raises(ValueError, match="site"):
            FaultInjector(schedule=[Fault("gpu", "melt", step=0)])

    def test_transient_fails_count_attempts_then_succeeds(self):
        from paddle_tpu.inference.llm import (
            Fault,
            FaultInjector,
            InjectedFault,
        )

        fi = FaultInjector(schedule=[
            Fault("step", "transient", step=3, count=2)])
        fi.begin_step(2)
        fi.device_step("decode")            # unscheduled step: no-op
        fi.begin_step(3)
        for _ in range(2):
            with pytest.raises(InjectedFault):
                fi.device_step("decode")
        fi.device_step("decode")            # third attempt passes
        assert fi.events == [(3, "step", "transient", 0),
                             (3, "step", "transient", 1)]

    def test_raise_carries_victim_every_attempt(self):
        from paddle_tpu.inference.llm import (
            Fault,
            FaultInjector,
            InjectedFault,
        )

        fi = FaultInjector(schedule=[
            Fault("step", "raise", step=0, victim=2)])
        fi.begin_step(0)
        for _ in range(3):                  # never absorbed by retries
            with pytest.raises(InjectedFault) as ei:
                fi.device_step("verify")
            assert ei.value.victim == 2

    def test_alloc_fires_once_per_scheduled_step(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        fi = FaultInjector(schedule=[Fault("alloc", "oom", step=5)])
        fi.begin_step(4)
        assert fi.alloc("append_slot") is False
        fi.begin_step(5)
        assert fi.alloc("append_slot") is True
        assert fi.alloc("append_slot") is False    # consumed
        assert fi.events == [(5, "alloc", "oom", 0)]

    def test_socket_faults_index_by_response(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        fi = FaultInjector(schedule=[
            Fault("socket", "disconnect", step=0),
            Fault("socket", "partial", step=2)])
        assert fi.socket_fault() == "disconnect"
        assert fi.socket_fault() is None
        assert fi.socket_fault() == "partial"
        assert fi.socket_fault() is None


class TestRetryPolicy:
    def test_resolve_sugar(self):
        from paddle_tpu.inference.llm import RetryPolicy

        assert RetryPolicy.resolve(None).max_attempts == 3
        assert RetryPolicy.resolve(5).max_attempts == 5
        p = RetryPolicy(max_attempts=2)
        assert RetryPolicy.resolve(p) is p
        assert RetryPolicy.resolve(
            {"max_attempts": 4, "jitter": 0.0}).max_attempts == 4
        with pytest.raises(TypeError):
            RetryPolicy.resolve(True)
        with pytest.raises(TypeError):
            RetryPolicy.resolve("twice")

    def test_backoff_exponential_capped_and_seeded(self):
        from paddle_tpu.inference.llm import RetryPolicy

        p = RetryPolicy(max_attempts=5, base_delay_s=0.1, max_delay_s=0.5,
                        jitter=0.0)
        assert [p.backoff(a) for a in range(4)] == [
            pytest.approx(0.1), pytest.approx(0.2), pytest.approx(0.4),
            pytest.approx(0.5)]                    # capped
        a = RetryPolicy(jitter=0.5, seed=3)
        b = RetryPolicy(jitter=0.5, seed=3)
        seq_a = [a.backoff(i) for i in range(4)]
        seq_b = [b.backoff(i) for i in range(4)]
        assert seq_a == seq_b                      # same seed, same sleeps
        for i, d in enumerate(seq_a):
            base = min(1.0, 0.02 * 2 ** i)
            assert 0.5 * base <= d <= 1.5 * base

    def test_validation(self):
        from paddle_tpu.inference.llm import RetryPolicy

        with pytest.raises(ValueError, match="max_attempts"):
            RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError, match="jitter"):
            RetryPolicy(jitter=1.5)
        with pytest.raises(ValueError, match="delays"):
            RetryPolicy(base_delay_s=-1)


class TestStepWatchdog:
    def test_threshold_and_observation(self):
        from paddle_tpu.inference.llm import StepWatchdog

        with pytest.raises(ValueError, match="threshold"):
            StepWatchdog(0)
        wd = StepWatchdog(0.5)
        assert wd.observe(3, "decode", 0.1) is False
        assert wd.observe(4, "decode", 0.9) is True
        assert wd.num_wedged == 1
        assert wd.wedged == [(4, "decode", 0.9)]


# ---------------------------------------------------------------------------
class TestAbortBattery:
    """abort_request in every lifecycle state: pages reclaimed exactly,
    allocator invariants hold, FinishReason.aborted delivered."""

    def test_abort_waiting_request(self):
        from paddle_tpu.inference.llm import FinishReason

        eng = _tiny_engine(_make_model())
        rid = eng.add_request([1, 2, 3], max_new_tokens=4)
        assert eng.abort_request(rid) is True
        assert eng.abort_request(rid) is False     # already finished
        assert eng.abort_request(99) is False      # unknown
        outs = _drive(eng)
        assert outs[rid].finish_reason == FinishReason.ABORTED
        assert not outs[rid].ok and outs[rid].output_ids.size == 0
        assert eng.block_manager.num_free_blocks == eng.num_blocks
        assert eng.lifecycle_stats()["aborted"] == 1

    def test_abort_mid_chunked_prefill(self):
        eng = _tiny_engine(_make_model())
        rng = np.random.RandomState(0)
        rid = eng.add_request(rng.randint(0, 128, (40,)), max_new_tokens=4)
        eng.step()                       # one 16-token chunk of 40
        req = eng._requests[rid]
        assert not req.prefill_done and req.num_cached == 16
        assert eng.abort_request(rid) is True
        _drive(eng)
        assert eng.block_manager.num_free_blocks == eng.num_blocks
        eng.scheduler.check_invariants()

    def test_abort_one_decoding_request_survivor_token_exact(self):
        from paddle_tpu.inference.llm import FinishReason

        m = _make_model()
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (5, 7)]
        ref = _tiny_engine(m).generate([prompts[0]], max_new_tokens=8)[0]
        eng = _tiny_engine(m)
        keep = eng.add_request(prompts[0], max_new_tokens=8)
        kill = eng.add_request(prompts[1], max_new_tokens=8)
        eng.step()                       # prefill both
        eng.step()                       # first decode token
        assert eng._requests[kill].output_ids
        assert eng.abort_request(kill) is True
        outs = _drive(eng)
        assert outs[kill].finish_reason == FinishReason.ABORTED
        assert len(outs[kill].output_ids) >= 1   # tokens so far delivered
        np.testing.assert_array_equal(outs[keep].all_ids, ref)
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_abort_while_preempted(self):
        from paddle_tpu.inference.llm import BlockManager, Scheduler
        from paddle_tpu.inference.llm.scheduler import (
            RUNNING,
            WAITING,
            Request,
        )

        bm = BlockManager(num_blocks=8, block_size=4,
                          enable_prefix_caching=False)
        sch = Scheduler(bm, max_batch=2, token_budget=8)
        req = Request(request_id=1, prompt_ids=(1, 2, 3, 4, 5),
                      max_new_tokens=4)
        bm.allocate(1, 5)
        req.status = RUNNING
        req.num_cached = 5
        sch.running.append(req)
        sch._preempt(req)
        assert req.status == WAITING and not bm.has_seq(1)
        assert req.num_preemptions == 1
        assert sch.abort(req) is True
        assert req not in sch.waiting
        assert bm.num_free_blocks == 8
        sch.check_invariants()

    def test_abort_mid_cow_fork(self):
        from paddle_tpu.inference.llm import BlockManager, Scheduler
        from paddle_tpu.inference.llm.scheduler import RUNNING, Request

        bm = BlockManager(num_blocks=8, block_size=4,
                          enable_prefix_caching=False)
        sch = Scheduler(bm, max_batch=4, token_budget=8)
        parent = Request(request_id="p", prompt_ids=(1,) * 6,
                         max_new_tokens=1)
        child = Request(request_id="c", prompt_ids=(1,) * 6,
                        max_new_tokens=1)
        bm.allocate("p", 6)
        bm.fork("p", "c")
        slots, cows = bm.append_slots("c", 3)    # COW copy + fresh page
        assert cows
        for r in (parent, child):
            r.status = RUNNING
            sch.running.append(r)
        free_mid_fork = bm.num_free_blocks
        assert sch.abort(child) is True
        bm.check_invariants()
        # the child's COW copy and its fresh page came back (2 pages);
        # the first page is SHARED with the parent, so it only drops a
        # refcount — the parent's 2 pages are all that stay allocated
        assert bm.num_free_blocks == free_mid_fork + 2
        assert bm.num_tokens("p") == 6 and bm.has_seq("p")
        assert sch.abort(parent) is True
        assert bm.num_free_blocks == 8
        bm.check_invariants()

    def test_abort_after_prefix_cache_registration_keeps_cache(self):
        m = _make_model()
        rng = np.random.RandomState(2)
        prefix = rng.randint(0, 128, (16,)).astype(np.int32)  # 2 pages
        eng = _tiny_engine(m)
        eng.generate([np.concatenate([prefix, [1, 2]])],
                     max_new_tokens=4)
        cached_before = eng.block_manager.num_cached_blocks
        assert cached_before >= 2
        rid = eng.add_request(np.concatenate([prefix, [3, 4, 5]]),
                              max_new_tokens=4)
        eng.step()                                # adopts cached prefix
        assert eng.scheduler.prefix_hit_tokens >= 16
        assert eng.abort_request(rid) is True
        _drive(eng)
        # private pages freed; the hashed prefix pages SURVIVE on the
        # LRU list (refcount 0 counts as free) for the next request
        assert eng.block_manager.num_free_blocks == eng.num_blocks
        assert eng.block_manager.num_cached_blocks >= cached_before
        eng.scheduler.check_invariants()

    def test_abort_with_speculative_reservation(self):
        m = _make_model()
        # highly repetitive prompt: the n-gram drafter proposes drafts,
        # so decode rows hold 1+K reservations when we abort mid-flight
        prompt = np.array([7, 8, 9] * 5, np.int32)
        eng = _tiny_engine(m, speculative=2)
        rid = eng.add_request(prompt, max_new_tokens=12)
        eng.step()                                # prefill
        eng.step()                                # decode/verify
        if rid in eng._requests:
            assert eng.abort_request(rid) is True
        _drive(eng)
        assert eng.block_manager.num_free_blocks == eng.num_blocks
        eng.scheduler.check_invariants()


# ---------------------------------------------------------------------------
class TestDeadlinesAndShedding:
    def test_deadline_expires_running_request(self):
        from paddle_tpu.inference.llm import FinishReason

        clk = _FakeClock()
        eng = _tiny_engine(_make_model(), clock=clk)
        rid = eng.add_request([1, 2, 3], max_new_tokens=30,
                              deadline_ms=50)
        eng.step()                                 # prefill, in budget
        eng.step()
        clk.advance(0.1)                           # blow the deadline
        outs = _drive(eng)
        assert outs[rid].finish_reason == FinishReason.DEADLINE
        assert len(outs[rid].output_ids) < 30      # cut short
        assert eng.block_manager.num_free_blocks == eng.num_blocks
        assert eng.lifecycle_stats()["deadline_missed"] == 1

    def test_deadline_expires_waiting_request(self):
        from paddle_tpu.inference.llm import FinishReason

        clk = _FakeClock()
        eng = _tiny_engine(_make_model(), clock=clk, max_batch=1)
        first = eng.add_request([1, 2, 3], max_new_tokens=4)
        queued = eng.add_request([4, 5, 6], max_new_tokens=4,
                                 deadline_ms=10)
        clk.advance(1.0)
        outs = _drive(eng)
        assert outs[queued].finish_reason == FinishReason.DEADLINE
        assert outs[queued].output_ids.size == 0
        assert outs[first].ok
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_deadline_validation_up_front(self):
        eng = _tiny_engine(_make_model())
        for bad in (0, -5, True, "soon"):
            with pytest.raises(ValueError, match="deadline_ms"):
                eng.add_request([1, 2], deadline_ms=bad)
            with pytest.raises(ValueError, match="deadline_ms"):
                eng.generate([[1, 2]], deadline_ms=bad)
        assert not eng.has_unfinished()            # nothing half-queued

    def test_queue_depth_sheds_past_max_queue(self):
        from paddle_tpu.inference.llm import FinishReason

        eng = _tiny_engine(_make_model(), max_queue=2)
        rids = [eng.add_request([1, 2, i], max_new_tokens=4)
                for i in range(4)]
        outs = _drive(eng)
        reasons = [outs[r].finish_reason for r in rids]
        assert reasons.count(FinishReason.SHED) == 2   # 3rd and 4th
        assert reasons[:2] == ["length", "length"]
        assert eng.lifecycle_stats()["shed"] == 2
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_max_queue_validation(self):
        m = _make_model()
        for bad in (0, -1, True, 2.5, "deep"):
            with pytest.raises(ValueError, match="max_queue"):
                _tiny_engine(m, max_queue=bad)

    def test_drain_completes_everything_and_sheds_newcomers(self):
        from paddle_tpu.inference.llm import FinishReason

        eng = _tiny_engine(_make_model())
        rids = [eng.add_request([1, 2, i], max_new_tokens=4)
                for i in range(2)]
        outs = {o.request_id: o for o in eng.drain()}
        assert all(outs[r].finish_reason == "length" for r in rids)
        assert not eng.has_unfinished()
        assert eng.block_manager.num_free_blocks == eng.num_blocks
        # drain() has returned: admission is open again
        again = eng.add_request([5, 6], max_new_tokens=2)
        outs2 = _drive(eng)
        assert outs2[again].ok
        # but DURING a drain, add_request sheds
        eng._draining = True
        try:
            shed = eng.add_request([7, 8], max_new_tokens=2)
        finally:
            eng._draining = False
        out = _drive(eng)[shed]
        assert out.finish_reason == FinishReason.SHED

    def test_drain_timeout_aborts_stragglers(self):
        from paddle_tpu.inference.llm import FinishReason

        eng = _tiny_engine(_make_model())
        rid = eng.add_request([1, 2, 3], max_new_tokens=40)
        outs = {o.request_id: o for o in eng.drain(timeout_s=0.0)}
        assert outs[rid].finish_reason == FinishReason.ABORTED
        assert eng.block_manager.num_free_blocks == eng.num_blocks


# ---------------------------------------------------------------------------
class TestStepIsolation:
    def test_transient_fault_absorbed_by_retry_token_exact(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        m = _make_model()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (5, 7)]
        refs = _tiny_engine(m).generate(prompts, max_new_tokens=8)
        eng = _tiny_engine(
            m, retry=_FAST_RETRY,
            faults=FaultInjector(schedule=[
                Fault("step", "transient", step=2, count=1)]))
        outs = eng.generate(prompts, max_new_tokens=8)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        s = eng.lifecycle_stats()
        assert s["retries"] == 1 and s["quarantined"] == 0
        assert s["step_faults"] == 1
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_raise_fault_quarantines_victim_only(self):
        from paddle_tpu.inference.llm import (
            Fault,
            FaultInjector,
            FinishReason,
        )

        m = _make_model()
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (5, 7)]
        ref = _tiny_engine(m).generate([prompts[0]], max_new_tokens=8)[0]
        eng = _tiny_engine(
            m, retry=1,          # no retries: quarantine on first failure
            faults=FaultInjector(schedule=[
                Fault("step", "raise", step=2, victim=1)]))
        keep = eng.add_request(prompts[0], max_new_tokens=8)
        kill = eng.add_request(prompts[1], max_new_tokens=8)
        with pytest.warns(RuntimeWarning, match="quarantin"):
            outs = _drive(eng)
        assert outs[kill].finish_reason == FinishReason.ERROR
        assert "injected raise" in outs[kill].error
        np.testing.assert_array_equal(outs[keep].all_ids, ref)
        assert eng.lifecycle_stats()["quarantined"] == 1
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_delay_fault_trips_watchdog(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        m = _make_model()
        prompt = np.arange(1, 6, dtype=np.int32)
        ref = _tiny_engine(m).generate([prompt], max_new_tokens=4)[0]
        eng = _tiny_engine(
            m, step_timeout_s=0.01,
            faults=FaultInjector(schedule=[
                Fault("step", "delay", step=1, delay_s=0.05)]))
        out = eng.generate([prompt], max_new_tokens=4)[0]
        np.testing.assert_array_equal(out, ref)
        assert eng.watchdog.num_wedged >= 1
        assert eng.lifecycle_stats()["wedged_steps"] >= 1

    def test_injected_oom_forces_preemption_token_exact(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        m = _make_model()
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (5, 7)]
        refs = _tiny_engine(m).generate(prompts, max_new_tokens=8)
        eng = _tiny_engine(
            m, faults=FaultInjector(schedule=[
                Fault("alloc", "oom", step=2)]))
        outs = eng.generate(prompts, max_new_tokens=8)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        assert eng.scheduler.num_preemptions >= 1
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_injected_oom_single_sequence_self_preempts(self):
        # a REAL one-sequence OOM is fatal (pool too small); an injected
        # one fires once per step, so self-preempt + recompute recovers
        from paddle_tpu.inference.llm import Fault, FaultInjector

        m = _make_model()
        prompt = np.arange(1, 8, dtype=np.int32)
        ref = _tiny_engine(m).generate([prompt], max_new_tokens=6)[0]
        eng = _tiny_engine(
            m, faults=FaultInjector(schedule=[
                Fault("alloc", "oom", step=2)]))
        out = eng.generate([prompt], max_new_tokens=6)[0]
        np.testing.assert_array_equal(out, ref)
        assert eng.scheduler.num_preemptions >= 1
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_pool_lost_is_surfaced_not_limped_on(self):
        import types

        from paddle_tpu.inference.llm import (
            Fault,
            FaultInjector,
            PoolLostError,
        )

        eng = _tiny_engine(
            _make_model(), retry=1,
            faults=FaultInjector(schedule=[
                Fault("step", "raise", step=1)]))
        eng.add_request([1, 2, 3], max_new_tokens=4)
        eng.step()                                 # prefill fine
        # simulate the donated pool having been consumed by the failure
        eng.kv_cache = {"k": types.SimpleNamespace(
            is_deleted=lambda: True)}
        with pytest.raises(PoolLostError, match="donated"):
            eng.step()

    def test_retry_backoff_sleeps_are_bounded(self):
        from paddle_tpu.inference.llm import Fault, FaultInjector

        eng = _tiny_engine(
            _make_model(),
            retry={"max_attempts": 3, "base_delay_s": 0.001,
                   "jitter": 0.0},
            faults=FaultInjector(schedule=[
                Fault("step", "transient", step=1, count=2)]))
        eng.add_request([1, 2, 3], max_new_tokens=2)
        t0 = time.monotonic()
        _drive(eng)
        assert time.monotonic() - t0 < 30          # retries, not hangs
        assert eng.lifecycle_stats()["retries"] == 2


# ---------------------------------------------------------------------------
class TestEventLogDeterminism:
    """Same fault seed twice -> byte-identical engine event logs and
    injector event logs (the chaos determinism contract)."""

    def _run(self, m, prompts, seed):
        from paddle_tpu.inference.llm import FaultInjector

        fi = FaultInjector.random(seed, steps=64, p_transient=0.15,
                                  p_oom=0.1, p_abort=0.08)
        eng = _tiny_engine(m, faults=fi, retry=_FAST_RETRY)
        for p in prompts:
            eng.add_request(p, max_new_tokens=8)
        outs = _drive(eng, faults=fi)
        assert eng.block_manager.num_free_blocks == eng.num_blocks
        return eng, fi, outs

    def test_same_seed_identical_event_logs(self):
        m = _make_model()
        rng = np.random.RandomState(6)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (4, 9, 6)]
        eng_a, fi_a, outs_a = self._run(m, prompts, seed=11)
        eng_b, fi_b, outs_b = self._run(m, prompts, seed=11)
        assert fi_a.events == fi_b.events and fi_a.events
        assert eng_a.events == eng_b.events
        assert outs_a.keys() == outs_b.keys()
        for rid in outs_a:
            assert outs_a[rid].finish_reason == outs_b[rid].finish_reason
            np.testing.assert_array_equal(outs_a[rid].all_ids,
                                          outs_b[rid].all_ids)

    def test_chaos_smoke_survivors_token_exact(self):
        m = _make_model()
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (4, 9, 6)]
        refs = _tiny_engine(m).generate(prompts, max_new_tokens=8)
        eng, fi, outs = self._run(m, prompts, seed=11)
        assert fi.events                           # chaos actually hit
        survived = 0
        for rid, ref in zip(sorted(outs), refs):
            out = outs[rid]
            if out.ok:
                survived += 1
                np.testing.assert_array_equal(out.all_ids, ref)
            else:
                # greedy chaos casualties emitted a PREFIX of the
                # reference stream before they died
                got = out.all_ids
                np.testing.assert_array_equal(got, ref[:len(got)])
        assert eng.lifecycle_stats()["shed"] == 0  # no max_queue set


# ---------------------------------------------------------------------------
class TestLifecycleGauges:
    def test_gauges_track_a_scripted_workload_exactly(self):
        """queue_depth / inflight / free_pages / last_step_ms follow a
        hand-scripted workload value for value: depth counts admissions
        not yet running, inflight the running set, free_pages the
        allocatable pool (LRU-parked cached pages included), and
        last_step_ms is None until the first step ever runs."""
        m = _make_model()
        eng = _tiny_engine(m, max_batch=2, token_budget=16)
        total = eng.num_blocks

        def gauges():
            ls = eng.lifecycle_stats()
            return (ls["queue_depth"], ls["inflight"],
                    ls["free_pages"], ls["last_step_ms"])

        assert gauges() == (0, 0, total, None)
        # three short requests (each fits one page for its whole
        # lifetime: prompt + 3 generated <= 8) against max_batch=2
        for toks, n in (([1] * 4, 3), ([2] * 5, 3), ([3] * 3, 3)):
            eng.add_request(toks, max_new_tokens=n)
        assert gauges() == (3, 0, total, None)   # queued, nothing ran
        eng.step()      # admits exactly max_batch=2; third one waits
        q, infl, free, ms = gauges()
        assert (q, infl, free) == (1, 2, total - 2)
        assert isinstance(ms, float) and ms > 0.0
        eng.step()      # decode step: occupancy unchanged
        assert gauges()[:3] == (1, 2, total - 2)
        while eng.has_unfinished():
            eng.step()
        q, infl, free, ms = gauges()
        assert (q, infl, free) == (0, 0, total)  # every page returned
        assert isinstance(ms, float) and ms > 0.0

    def test_fleet_gauges_aggregate_live_replicas_only(self):
        from paddle_tpu.inference.llm import Fleet

        m = _make_model()
        fleet = Fleet(m, replicas=2, block_size=8, max_batch=2,
                      max_model_len=64, token_budget=16)
        total = fleet.replicas[0].engine.num_blocks
        ls = fleet.lifecycle_stats()
        assert ls["free_pages"] == 2 * total
        assert ls["last_step_ms"] is None
        assert ls["replicas_live"] == 2
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            fleet.kill_replica(1)
        ls = fleet.lifecycle_stats()
        # the dead replica's pages are gone from the aggregate view
        assert ls["free_pages"] == total
        assert ls["replicas_live"] == 1


# ---------------------------------------------------------------------------
class _WedgedStubEngine:
    """step() blocks until released — probes close()'s join timeout."""

    def __init__(self):
        self.release = threading.Event()
        self._requests = {}

    def add_request(self, prompt_ids, **kwargs):
        self._requests[0] = None
        return 0

    def abort_request(self, rid):
        self._requests.pop(rid, None)
        return True

    def has_unfinished(self):
        return bool(self._requests)

    def step(self):
        self.release.wait(timeout=60)
        self._requests.clear()
        return []


class TestAsyncLifecycle:
    def test_abort_delivers_aborted_output(self):
        from paddle_tpu.inference.llm import AsyncLLMEngine, FinishReason

        eng = _tiny_engine(_make_model())
        a = AsyncLLMEngine(eng)
        try:
            rid = a.submit([1, 2, 3], max_new_tokens=50)
            a.abort(rid)
            out = a.result(rid, timeout=120)
            assert out.finish_reason in (FinishReason.ABORTED, "length")
        finally:
            a.close(join_timeout=120)
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_result_timeout_aborts_the_request(self):
        from paddle_tpu.inference.llm import AsyncLLMEngine

        eng = _tiny_engine(_make_model())
        a = AsyncLLMEngine(eng)
        try:
            rid = a.submit([1, 2, 3], max_new_tokens=50)
            with pytest.raises(TimeoutError, match="aborted"):
                a.result(rid, timeout=0.01)
            # the walked-away request must not keep generating: once the
            # loop applies the abort, the engine empties out and pages
            # come back
            deadline = time.monotonic() + 120
            while eng.has_unfinished() and time.monotonic() < deadline:
                time.sleep(0.05)
            assert not eng.has_unfinished()
            assert rid not in a._results           # output discarded
        finally:
            a.close(join_timeout=120)
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_close_aborts_pending_and_recovers_pages(self):
        from paddle_tpu.inference.llm import AsyncLLMEngine

        eng = _tiny_engine(_make_model())
        a = AsyncLLMEngine(eng)
        rids = [a.submit([1, 2, i], max_new_tokens=50) for i in range(3)]
        a.close(join_timeout=120)
        assert not eng.has_unfinished()
        assert eng.block_manager.num_free_blocks == eng.num_blocks
        # every caller blocked on result() gets a terminal output
        for rid in rids:
            out = a.result(rid, timeout=1)
            assert out.finish_reason in ("aborted", "length")
        with pytest.raises(RuntimeError, match="stopped"):
            a.submit([9, 9])

    def test_submit_racing_drain_gets_terminal_result(self):
        """Regression: a submit that loses the race against drain()
        must still produce a per-request FinishReason (shed) — never a
        silent drop — and admission must reopen once the drain ends."""
        from paddle_tpu.inference.llm import AsyncLLMEngine, FinishReason

        eng = _tiny_engine(_make_model())
        a = AsyncLLMEngine(eng)
        try:
            r1 = a.submit([1, 2, 3], max_new_tokens=40)
            t = threading.Thread(target=a.drain)
            t.start()
            deadline = time.monotonic() + 30
            while not a._draining and time.monotonic() < deadline:
                time.sleep(0.001)
            assert a._draining
            # r1 (40 tokens) holds the drain open; this submit races it
            r2 = a.submit([4, 5, 6], max_new_tokens=4)
            out2 = a.result(r2, timeout=120)
            assert out2.finish_reason == FinishReason.SHED
            out1 = a.result(r1, timeout=120)     # in-flight work finishes
            assert out1.ok
            t.join(timeout=120)
            assert not t.is_alive()
            out3 = a.generate([7, 8, 9], max_new_tokens=3, timeout=120)
            assert out3.ok                       # admission reopened
        finally:
            a.close(join_timeout=120)
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_drain_timeout_aborts_stragglers_async(self):
        """drain(timeout_s=) bounds the quiesce: a request still
        running at the deadline is aborted with a reported reason, and
        the engine comes back empty with its pages reclaimed."""
        from paddle_tpu.inference.llm import AsyncLLMEngine

        eng = _tiny_engine(_make_model())
        a = AsyncLLMEngine(eng)
        try:
            rid = a.submit([1, 2, 3], max_new_tokens=50)
            a.drain(timeout_s=0.01)
            out = a.result(rid, timeout=120)
            assert out.finish_reason in ("aborted", "length")
            assert not a._draining
        finally:
            a.close(join_timeout=120)
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_close_raises_when_worker_wedges(self):
        from paddle_tpu.inference.llm import AsyncLLMEngine

        stub = _WedgedStubEngine()
        a = AsyncLLMEngine(stub)
        a.submit([1])
        time.sleep(0.2)                    # loop is now inside step()
        try:
            with pytest.warns(RuntimeWarning, match="survived"):
                with pytest.raises(RuntimeError, match="failed to stop"):
                    a.close(join_timeout=0.2)
        finally:
            stub.release.set()             # let the thread die
            a._thread.join(timeout=10)


# ---------------------------------------------------------------------------
class TestServingFaults:
    """Socket-layer injection + connection-failure containment: one bad
    (or sacrificed) connection never takes down the accept loop."""

    @staticmethod
    def _query(port, ids, max_new):
        from paddle_tpu.inference.serving import (
            _recv_exact,
            _recv_tensor,
            _send_tensor,
        )

        s = socket.create_connection(("127.0.0.1", port))
        try:
            s.sendall(struct.pack("<I", 2))
            _send_tensor(s, np.asarray(ids, np.int64))
            _send_tensor(s, np.asarray(max_new, np.int64))
            status, n_out = struct.unpack("<BI", _recv_exact(s, 5))
            if status != 0:
                raise RuntimeError(_recv_exact(s, n_out).decode())
            return [_recv_tensor(s) for _ in range(n_out)][0]
        finally:
            s.close()

    def test_disconnect_and_partial_faults_spare_the_server(self):
        from paddle_tpu.inference.llm import (
            Fault,
            FaultInjector,
            LLMEngine,
        )
        from paddle_tpu.inference.serving import PredictorServer

        m = _make_model()
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        fi = FaultInjector(schedule=[
            Fault("socket", "disconnect", step=0),
            Fault("socket", "partial", step=1)])
        srv = PredictorServer(engine=eng, faults=fi)
        try:
            prompt = np.array([3, 4, 5], np.int64)
            # response 0: server vanishes before replying
            with pytest.raises((ConnectionError, OSError)):
                self._query(srv.port, prompt, 4)
            # response 1: half a frame, then gone — the client's framing
            # layer sees a short read, not a hang
            with pytest.raises((ConnectionError, OSError, struct.error)):
                self._query(srv.port, prompt, 4)
            # response 2: clean — the accept loop survived both
            out = self._query(srv.port, prompt, 4)
            assert out.shape[1] == len(prompt) + 4
            assert [e[2] for e in fi.events] == ["disconnect", "partial"]
        finally:
            srv.stop()
        assert eng.block_manager.num_free_blocks == eng.num_blocks

    def test_malformed_frame_gets_error_reply_server_survives(self):
        from paddle_tpu.inference.llm import LLMEngine
        from paddle_tpu.inference.serving import PredictorServer, _recv_exact

        m = _make_model()
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        srv = PredictorServer(engine=eng)
        try:
            # bad dtype code -> explicit error reply, not a dropped conn
            s = socket.create_connection(("127.0.0.1", srv.port))
            try:
                s.sendall(struct.pack("<I", 1) + struct.pack("<BB", 99, 0))
                status, n = struct.unpack("<BI", _recv_exact(s, 5))
                assert status == 1
                assert "dtype" in _recv_exact(s, n).decode()
            finally:
                s.close()
            # client dies mid-frame: only ITS connection fails
            s = socket.create_connection(("127.0.0.1", srv.port))
            s.sendall(b"\x02\x00")         # half the n_inputs header
            s.close()
            # the server still serves fresh connections after both
            out = self._query(srv.port, np.array([3, 4, 5], np.int64), 4)
            assert out.shape[1] == 7
        finally:
            srv.stop()

    def test_non_done_finish_reason_is_a_wire_error(self):
        from paddle_tpu.inference.llm import LLMEngine
        from paddle_tpu.inference.serving import (
            PredictorServer,
            _recv_exact,
            _send_tensor,
        )

        m = _make_model()
        # a draining engine sheds every admission — the one failure
        # path reachable deterministically without real wall-clock
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        srv = PredictorServer(engine=eng)
        try:
            eng._draining = True           # every admission sheds
            s = socket.create_connection(("127.0.0.1", srv.port))
            try:
                s.sendall(struct.pack("<I", 2))
                _send_tensor(s, np.array([3, 4, 5], np.int64))
                _send_tensor(s, np.asarray(4, np.int64))
                status, n = struct.unpack("<BI", _recv_exact(s, 5))
                assert status == 1
                assert "shed" in _recv_exact(s, n).decode()
            finally:
                s.close()
        finally:
            eng._draining = False
            srv.stop()

    def test_wire_deadline_validation(self):
        from paddle_tpu.inference.llm import LLMEngine
        from paddle_tpu.inference.serving import (
            PredictorServer,
            _recv_exact,
            _send_tensor,
        )

        m = _make_model()
        eng = LLMEngine(m, block_size=8, max_batch=4, max_model_len=64)
        srv = PredictorServer(engine=eng)
        try:
            s = socket.create_connection(("127.0.0.1", srv.port))
            try:
                s.sendall(struct.pack("<I", 5))
                _send_tensor(s, np.array([3, 4, 5], np.int64))
                _send_tensor(s, np.asarray(4, np.int64))
                _send_tensor(s, np.asarray(0.0, np.float32))
                _send_tensor(s, np.asarray(0, np.int64))
                _send_tensor(s, np.asarray(-1.0, np.float32))  # bad
                status, n = struct.unpack("<BI", _recv_exact(s, 5))
                assert status == 1
                assert "deadline_ms" in _recv_exact(s, n).decode()
            finally:
                s.close()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestChaosSoak:
    """Replay a trace under a randomized-but-seeded fault schedule at
    tp=1 and tp=2, speculative off and on: survivors token-exact vs the
    fault-free run, ZERO leaked pages (invariants checked every step),
    zero post-warmup compiles, and a seed replay reproduces the event
    log byte for byte."""

    @pytest.mark.parametrize("tp", [1, 2])
    @pytest.mark.parametrize("spec", [None, 2])
    def test_soak(self, tp, spec):
        from paddle_tpu.inference.llm import FaultInjector, LLMEngine

        m = _make_model()
        rng = np.random.RandomState(42)
        prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
                   for n in (4, 11, 7, 19, 5, 9)]
        kw = dict(block_size=8, max_batch=4, max_model_len=64,
                  token_budget=16, speculative=spec)
        if tp > 1:
            kw["tensor_parallel"] = tp
        refs = {}
        ref_eng = LLMEngine(m, **kw)
        rids = [ref_eng.add_request(p, max_new_tokens=10) for p in prompts]
        for rid, out in _drive(ref_eng).items():
            refs[rid] = out
        assert all(refs[r].ok for r in rids)

        def chaos(seed):
            fi = FaultInjector.random(
                seed, steps=256, p_step=0.03, p_transient=0.1,
                p_oom=0.08, p_delay=0.03, p_abort=0.05, delay_s=0.002)
            eng = LLMEngine(m, faults=fi, retry=_FAST_RETRY,
                            step_timeout_s=0.001, **kw)
            watcher = eng.warmup()
            for p in prompts:
                eng.add_request(p, max_new_tokens=10)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                with watcher:
                    outs = _drive(eng, faults=fi)
            assert watcher.new_compiles() == []
            assert eng.block_manager.num_free_blocks == eng.num_blocks
            eng.scheduler.check_invariants()
            return eng, fi, outs

        eng_a, fi_a, outs_a = chaos(seed=13)
        for rid, out in outs_a.items():
            ref = refs[rid].all_ids
            if out.ok:
                np.testing.assert_array_equal(out.all_ids, ref)
            elif out.finish_reason != "error":
                got = out.all_ids          # greedy prefix property
                np.testing.assert_array_equal(got, ref[:len(got)])
        # seed replay: identical fault timing, identical lifecycle log
        eng_b, fi_b, outs_b = chaos(seed=13)
        assert fi_a.events == fi_b.events
        assert eng_a.events == eng_b.events
        assert {r: o.finish_reason for r, o in outs_a.items()} == \
               {r: o.finish_reason for r, o in outs_b.items()}


def test_chaos_bench_smoke(tmp_path):
    """benchmarks/bench_serving.py --chaos runs end to end on tiny
    parameters: the row carries the lifecycle counters, survivors are
    token-exact vs the embedded fault-free baseline, zero pages leak,
    and the artifact lands (soak-scale chaos is TestChaosSoak's job)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    artifact = str(tmp_path / "BENCH_chaos.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    rc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "benchmarks", "bench_serving.py"),
         "--chaos", "7", "--requests", "6", "--max-new", "8",
         "--max-batch", "4", "--artifact", artifact],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert rc.returncode == 0, rc.stderr[-1500:]
    row = json.loads(rc.stdout.strip().splitlines()[-1])
    assert row["metric"] == "llm_serving_chaos"
    assert row["chaos_seed"] == 7
    assert row["survivor_token_exact"] is True
    assert row["leaked_pages"] == 0
    assert row["survivors"] + row["aborted"] + row["shed"] + \
        row["deadline_missed"] + row["quarantined"] >= row["requests"]
    for key in ("retries", "step_faults", "preemptions",
                "e2e_p95_delta_ms"):
        assert key in row
    with open(artifact) as f:
        doc = json.load(f)
    assert doc["ok"] is True and doc["bench"]["metric"] == \
        "llm_serving_chaos"
