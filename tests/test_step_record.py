"""The train step's flight record (docs/PROFILER.md, "The step's flight
record"): one record a call of ``jit.TrainStep`` / ``parallel.SpmdTrainStep``
with no profiler session on, planted stalls read in the one field each
belongs to, the rule for ``long``, and what the record costs."""

import contextlib
import gc
import os
import statistics
import threading
import time

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer, profiler
from paddle_tpu.distributed.fleet.topology import build_mesh
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.nn import functional as F
from paddle_tpu.parallel import SpmdTrainStep
from paddle_tpu.profiler import RecordEvent, StepTrace

FIELDS = ["name", "step", "enter", "call_s", "operands_s", "dispatch_s",
          "sync_s", "between_s", "between_cpu_s", "call_cpu_s",
          "between_run_delay_s", "call_run_delay_s", "process_cpu_s",
          "nivcsw", "majflt", "gc", "compiled", "long", "threads"]
WHICH = ["train", "spmd"]
STALL = 0.3
PACE = 0.08


@pytest.fixture(autouse=True)
def log():
    """The process's log, emptied: every test reads its own records."""
    StepTrace.log.clear()
    return profiler.step_log


def _batch(rows=4, length=16):
    rng = np.random.default_rng(0)
    return rng.integers(0, 128, (rows, length)).astype("int32")


def _step_and_batch(which, **batch):
    paddle.seed(0)
    model = gpt_tiny(num_layers=1)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    if which == "train":
        return (TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt),
                paddle.to_tensor(_batch(**batch)))
    mesh = build_mesh(devices=jax.devices()[:4], dp=2, mp=2)
    return SpmdTrainStep(model, opt, mesh), _batch(**batch)


def _tiny_step():
    paddle.seed(0)
    model = nn.Linear(4, 4)
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    x = paddle.to_tensor(np.ones((2, 4), "float32"))
    return TrainStep(model, lambda o, y: F.mse_loss(o, y), opt), x


def _call(step, ids, times=1, pause=0.0):
    """``times`` calls, each waited for as a training loop's caller does.
    ``PACE`` as the pause gives the tests of ``long`` intervals of which a
    quarter is more than this machine's jitter."""
    for _ in range(times):
        jax.block_until_ready(step(ids, ids)._data)
        time.sleep(pause)


def _busy(seconds):
    """``seconds`` of the calling thread's own CPU time, however long the
    machine takes to give them."""
    until = time.thread_time() + seconds
    while time.thread_time() < until:
        pass


# ---- one record a call ----------------------------------------------------
@pytest.mark.parametrize("which", WHICH)
def test_a_call_adds_one_record_under_the_spans_step(which, monkeypatch):
    seen = []

    class Spy(contextlib.nullcontext):
        def __init__(self, name, **kw):
            super().__init__()
            seen.append((name, kw.get("step")))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    step, ids = _step_and_batch(which)
    for n in (1, 2, 3):
        del seen[:]
        _call(step, ids)
        records = profiler.step_log()
        assert len(records) == n
        assert records[-1]["name"] == "train_step"
        assert records[-1]["step"] == n
        assert (StepTrace.STEP, n) in seen
        assert {s for name, s in seen if name.startswith("train_step")} == {n}


@pytest.mark.parametrize("which", WHICH)
def test_a_record_holds_every_field_with_no_session_on(which):
    step, ids = _step_and_batch(which)
    assert not profiler.host_events_active()
    _call(step, ids, 3)
    first, second, third = profiler.step_log()
    for rec in (first, second, third):
        assert list(rec) == FIELDS
    assert first["compiled"] and not second["compiled"]
    for name in ("between_s", "between_cpu_s", "between_run_delay_s",
                 "process_cpu_s", "nivcsw", "majflt", "gc"):
        assert first[name] is None and third[name] is not None, name
    # the first call after the compile takes the baseline of the threads
    assert first["threads"] is None and third["threads"] is None
    assert threading.get_native_id() in [t[0] for t in second["threads"]]
    assert third["operands_s"] > 0 and third["dispatch_s"] > 0
    assert (third["sync_s"] > 0) == (which == "train")
    assert third["operands_s"] + third["dispatch_s"] + third["sync_s"] \
        <= third["call_s"]
    assert third["call_cpu_s"] >= 0 and third["call_run_delay_s"] >= 0
    assert third["nivcsw"] >= 0 and third["majflt"] >= 0
    # a call and the caller's time after it ARE the interval to the next
    assert second["call_s"] + third["between_s"] == pytest.approx(
        third["enter"] - second["enter"], abs=1e-9)


@pytest.mark.parametrize("which", WHICH)
def test_the_records_outlive_the_step_object(which):
    step, ids = _step_and_batch(which)
    _call(step, ids, 2)
    del step
    gc.collect()
    assert [r["step"] for r in profiler.step_log()] == [1, 2]


def test_lowering_the_step_adds_no_record_and_traces_none_of_it(monkeypatch):
    """The compiled program is the parent's: what is lowered with the
    record in place is, character for character, what is lowered with the
    spans alone (the parent's ``__call__``)."""
    step, ids = _step_and_batch("train")
    with_record = step.lower(ids, ids).as_text()
    assert profiler.step_log() == []
    monkeypatch.setattr(StepTrace, "call", lambda self, step: RecordEvent(
        StepTrace.STEP, step=step))
    monkeypatch.setattr(StepTrace, "phase", lambda self, name: RecordEvent(
        name))
    bare, _ = _step_and_batch("train")
    _call(bare, ids)
    assert profiler.step_log() == []
    assert bare.lower(ids, ids).as_text() == with_record


# ---- planted stalls: each in the one field it belongs to -------------------
@pytest.mark.parametrize("which", WHICH)
def test_a_sleeping_caller_reads_off_cpu_and_marks_the_step_long(which):
    step, ids = _step_and_batch(which)
    _call(step, ids, 10, PACE)          # a compile, then nine intervals
    time.sleep(STALL - PACE)
    _call(step, ids, 2, PACE)
    held, after = profiler.step_log()[-3:-1]
    assert STALL <= after["between_s"] < 2 * STALL
    assert after["between_cpu_s"] < 0.05
    # asleep, not runnable: whatever the machine's load made of the rest
    assert (after["between_s"] - after["between_cpu_s"]
            - after["between_run_delay_s"]) >= STALL - 0.01
    assert held["long"] and held["step"] == 10
    # a loaded machine may make another interval long; it makes none short
    flagged = [r["step"] for r in profiler.step_log() if r["long"]]
    assert step.stats() == {"steps": 12, "compiles": 1,
                            "long_steps": len(flagged)}
    assert threading.get_native_id() in [t[0] for t in held["threads"]]
    tid, comm, on_cpu, delay, slices = held["threads"][0]
    assert isinstance(comm, str) and on_cpu >= 0 and delay >= 0 and slices >= 0


def test_a_busy_caller_reads_on_cpu():
    step, ids = _step_and_batch("train")
    _call(step, ids, 2)
    _busy(STALL)
    _call(step, ids)
    rec = profiler.step_log()[-1]
    assert rec["between_s"] >= rec["between_cpu_s"] >= STALL
    assert rec["process_cpu_s"] >= rec["between_cpu_s"]


def test_a_stall_inside_the_call_reads_in_its_phase(monkeypatch):
    step, ids = _step_and_batch("train")
    _call(step, ids, 2)
    operands = TrainStep._operands

    def slow(self, *args):
        time.sleep(STALL)
        return operands(self, *args)

    monkeypatch.setattr(TrainStep, "_operands", slow)
    _call(step, ids)
    rec = profiler.step_log()[-1]
    assert STALL <= rec["operands_s"] <= rec["call_s"] < 2 * STALL
    # asleep in the call: not on the CPU, and nobody else's time moved
    assert rec["call_cpu_s"] < 0.05
    assert rec["between_s"] < STALL / 2 and rec["dispatch_s"] < STALL / 2


def test_a_collection_between_calls_shows_in_gc():
    step, ids = _step_and_batch("train")
    _call(step, ids, 2)
    assert profiler.step_log()[-1]["gc"][0] >= 0
    _call(step, ids)
    quiet = profiler.step_log()[-1]["gc"]
    gc.collect()
    _call(step, ids)
    count, seconds = profiler.step_log()[-1]["gc"]
    assert count >= quiet[0] + 1 and seconds > 0


# ---- the rule for ``long`` -------------------------------------------------
def _judged(trace, *intervals, compiled=()):
    """The records of calls whose intervals were ``intervals``, judged by
    the rule alone as the entry of each next call does it (no clock: the
    machine's load cannot move a made-up interval)."""
    records = [{"compiled": n in compiled, "long": False, "threads": None}
               for n in range(len(intervals) + 1)]
    for n, interval in enumerate(intervals):
        trace._judge(records[n], interval, records[n + 1])
    return records


def test_eight_intervals_have_to_be_known_before_one_is_long():
    trace = StepTrace()
    records = _judged(trace, *[0.02] * 7, 0.07, 0.02)
    # the eighth interval is the long one: seven known, so unjudged
    assert trace.long_steps == 0 and not any(r["long"] for r in records)
    records = _judged(trace, 0.07, 0.0251, 0.0249)
    assert [r["long"] for r in records] == [True, True, False, False]
    assert trace.long_steps == 2
    # over SNAPSHOT_OVER the record takes the threads, under it none
    assert records[0]["threads"] and records[1]["threads"] is None


def test_a_call_that_compiled_is_never_long_and_never_in_the_median():
    step, ids = _step_and_batch("train")
    short = paddle.to_tensor(_batch(length=8))
    _call(step, ids, 10, PACE)
    _call(step, short, 1, PACE)         # another shape: a compile, seconds
    _call(step, short, 9, PACE)
    compiled = [r for r in profiler.step_log() if r["compiled"]]
    assert [r["step"] for r in compiled] == [1, 11]
    assert step.stats()["compiles"] == 2
    assert not compiled[0]["long"] and not compiled[1]["long"]
    assert max(step._trace._intervals) < compiled[1]["call_s"]
    # the first call after each compile took the baseline of the threads
    assert all(profiler.step_log()[n]["threads"] for n in (1, 11))

    trace = StepTrace()                 # and by the rule alone
    records = _judged(trace, *[0.02] * 11, 5.0, 0.02, compiled=(11,))
    assert trace.long_steps == 0 and list(trace._intervals) == [0.02] * 12
    assert records[12]["threads"]       # the baseline after the compile


# ---- what the record cannot read -------------------------------------------
def test_without_schedstat_the_run_delays_and_the_threads_are_none(
        monkeypatch, tmp_path):
    monkeypatch.setattr(StepTrace, "SCHEDSTAT", str(tmp_path / "schedstat"))
    monkeypatch.setattr(profiler, "_sched", threading.local())
    step, ids = _step_and_batch("train")
    _call(step, ids, 10, PACE)
    time.sleep(STALL - PACE)
    _call(step, ids, 2, PACE)
    held, after = profiler.step_log()[-3:-1]
    assert held["long"] and held["threads"] is None
    assert profiler.step_log()[1]["threads"] is None    # the baseline's place
    assert after["between_run_delay_s"] is None
    assert after["call_run_delay_s"] is None
    assert after["between_s"] >= STALL and after["between_cpu_s"] < 0.05


@pytest.mark.parametrize("fault", ["short_read", "descriptor_gone"])
def test_a_schedstat_that_fails_under_way_raises_nothing(
        fault, monkeypatch, tmp_path):
    """An always-on record never raises into the step: a read that fails
    (a descriptor from before a fork whose thread has gone) or comes
    short ends the run-delay's reading, and the calls go on."""
    stat = tmp_path / "schedstat"
    stat.write_text("1 2 3\n")
    monkeypatch.setattr(StepTrace, "SCHEDSTAT", str(stat))
    monkeypatch.setattr(profiler, "_sched", threading.local())
    trace = StepTrace()
    for n in (1, 2):
        with trace.call(n):
            pass
    assert profiler.step_log()[-1]["call_run_delay_s"] == 0.0
    if fault == "short_read":
        stat.write_text("1\n")
    else:
        os.close(profiler._sched.stat.fd)
    for n in (3, 4):
        with trace.call(n):
            pass
    third, fourth = profiler.step_log()[-2:]
    assert profiler._sched.stat.fd is None
    for rec in (third, fourth):
        assert rec["between_run_delay_s"] is None
        assert rec["call_run_delay_s"] is None and rec["call_s"] > 0
    assert profiler.thread_snapshot() is None


def test_a_call_from_another_thread_starts_anew():
    """CPU seconds and context switches are a thread's own: a call by
    another thread than the last has no caller's time to split."""
    step, ids = _step_and_batch("train")
    _call(step, ids, 2)
    worker = threading.Thread(target=_call, args=(step, ids))
    worker.start()
    worker.join(timeout=60)
    assert not worker.is_alive()
    _call(step, ids, 2)
    own, other, back, again = profiler.step_log()[1:]
    assert own["between_s"] is not None and again["between_s"] is not None
    for rec in (other, back):
        assert rec["between_s"] is None and rec["between_cpu_s"] is None
        assert rec["nivcsw"] is None and rec["call_cpu_s"] >= 0


def test_a_call_that_raises_leaves_its_record():
    trace = StepTrace()
    with pytest.raises(ValueError, match="no such batch"):
        with trace.call(1):
            with trace.phase(StepTrace.OPERANDS):
                raise ValueError("no such batch")
    (rec,) = profiler.step_log()
    assert rec["step"] == 1 and rec["call_s"] >= rec["operands_s"] > 0


def test_the_log_keeps_the_newest_records_oldest_first():
    trace = StepTrace()
    for n in range(StepTrace.KEEP + 10):
        with trace.call(n):
            pass
    assert StepTrace.KEEP == 4096 == len(profiler.step_log())
    assert [r["step"] for r in profiler.step_log()] == list(
        range(10, StepTrace.KEEP + 10))


# ---- what it costs ---------------------------------------------------------
def test_the_record_costs_under_a_hundred_microseconds_a_call(monkeypatch):
    """A guard against a file opened every call, not a measurement: the
    same tiny step with the record and with the spans alone (the parent's
    ``__call__``), called in turn so that the machine's load meets both."""
    opened = []
    os_open = os.open
    monkeypatch.setattr(os, "open", lambda path, *a, **kw: (
        opened.append(path), os_open(path, *a, **kw))[1])
    monkeypatch.setattr(profiler, "_sched", threading.local())
    (recorded, x), (bare, _) = _tiny_step(), _tiny_step()
    bare._trace.call = lambda step: RecordEvent(StepTrace.STEP, step=step)
    bare._trace.phase = lambda name: RecordEvent(name)
    took = {recorded: [], bare: []}
    gc.collect()
    for n in range(2 * 2020):
        step = (recorded, bare)[n % 2]
        t0 = time.perf_counter()
        loss = step(x, x)
        took[step].append(time.perf_counter() - t0)
        jax.block_until_ready(loss._data)
    assert len(profiler.step_log()) == 2020
    assert opened.count(StepTrace.SCHEDSTAT) == 1
    # the median, or the lower quartile where the machine's load swelled
    # the upper half of one side
    with_, without = (statistics.quantiles(took[step][20:], n=4)
                      for step in (recorded, bare))
    cost = min(with_[1] - without[1], with_[0] - without[0])
    assert cost < 100e-6, (with_, without)
