"""The reader of the train step's flight record
(``readers/step_record.py``: ``paddle_tpu.profiler.step_log()``) on made-up
logs and intervals, on the trace recorded from the scoped tiny model, and
on the program's own records; the five metric files and their entries."""

import importlib
import json
import os
import time
from types import SimpleNamespace

import pytest

from chipbench import stats, trace, xplane_meta
from chipbench.readers import step_record
from chipbench.tests.test_scopes import SCOPED, recorded_env

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = {"step_ms_max.train": ("max_ms", "train step", "program_counter"),
       "step_excess_ms.in_call": ("in_call", "train step",
                                  "program_counter"),
       "step_excess_ms.caller_on_cpu": ("caller_on_cpu", "train step",
                                        "program_counter"),
       "step_excess_ms.caller_off_cpu": ("caller_off_cpu", "train step",
                                         "program_counter"),
       "step_excess_ms.device_busy": ("device_busy", "device",
                                      "device_trace")}
STEP, CALL, FEED, WAIT = 0.300, 0.005, 0.002, 0.293


def made_up_log(first=1, count=12, at=1000.0, stall=None, where=None,
                seconds=0.0):
    """``count`` calls of one object from step ``first``: a call of 5 ms,
    then the caller 2 ms on the CPU and 293 ms asleep.  ``stall`` is the
    step whose interval holds ``seconds`` more, ``where`` the part that
    held them."""
    log, enter, left = [], at, None
    for step in range(first, first + count):
        call_s = CALL + (seconds if (step, where) == (stall, "in_call")
                         else 0.0)
        rec = {"name": "train_step", "step": step, "enter": enter,
               "call_s": call_s, "operands_s": 0.002, "dispatch_s": 0.002,
               "sync_s": 0.001, "call_cpu_s": 0.004, "call_run_delay_s": 0.0,
               "between_s": None, "between_cpu_s": None,
               "between_run_delay_s": None, "process_cpu_s": None,
               "nivcsw": None, "majflt": None, "gc": None,
               "compiled": False, "long": False, "threads": None}
        if left is not None:
            rec.update(between_s=enter - left, between_cpu_s=FEED + busy,
                       between_run_delay_s=0.0, process_cpu_s=0.02,
                       nivcsw=0, majflt=0, gc=(0, 0.0))
        log.append(rec)
        busy = seconds if (step, where) == (stall, "caller_on_cpu") else 0.0
        asleep = seconds if (step, where) == (stall, "caller_off_cpu") \
            else 0.0
        left = enter + call_s
        enter = left + FEED + busy + WAIT + asleep
    return log


def readings(log, lo=0.0, hi=1e9):
    pairs = step_record.in_window(log, lo, hi)
    return {what: step_record.reading(pairs, what)
            for what in ("max_ms", "in_call", "caller_on_cpu",
                         "caller_off_cpu")}


# ---- the four readings of the record ---------------------------------------
def test_a_window_without_a_long_step_reads_its_step_and_no_excess():
    got = readings(made_up_log())
    assert got["max_ms"] == pytest.approx(1e3 * STEP)
    for part in ("in_call", "caller_on_cpu", "caller_off_cpu"):
        assert got[part] == pytest.approx(0.0, abs=1e-6)


@pytest.mark.parametrize("where", ["in_call", "caller_on_cpu",
                                   "caller_off_cpu"])
def test_a_planted_long_interval_reads_in_the_part_that_held_it(where):
    got = readings(made_up_log(stall=7, where=where, seconds=1.5))
    assert got["max_ms"] == pytest.approx(1e3 * (STEP + 1.5))
    for part in ("in_call", "caller_on_cpu", "caller_off_cpu"):
        assert got[part] == pytest.approx(1500.0 if part == where else 0.0,
                                          abs=1e-6), part


def test_only_the_windows_records_are_read_and_only_consecutive_calls():
    log = made_up_log(stall=3, where="in_call", seconds=2.0)
    # the window opens after the stalled step: it is set-up's
    opened = log[4]["enter"] - 0.001
    assert readings(log, lo=opened)["max_ms"] == pytest.approx(1e3 * STEP)
    assert len(step_record.in_window(log, opened, 1e9)) == 12 - 4 - 1
    assert len(step_record.in_window(log, opened, log[8]["enter"])) == 4
    # another object's calls between (a second trainer) pair with nothing
    other = made_up_log(first=1, count=3, at=log[6]["enter"] + 0.1)
    mixed = sorted(log + other, key=lambda r: r["enter"])
    steps = [(a["step"], b["step"])
             for a, b in step_record.in_window(mixed, 0.0, 1e9)]
    assert all(b == a + 1 for a, b in steps) and (6, 7) in steps
    assert (7, 8) not in steps      # the other object's call lies between
    # fewer than two calls: nothing to read
    assert step_record.reading(step_record.in_window(log[:1], 0, 1e9),
                               "max_ms") is None
    with pytest.raises(ValueError, match="unknown reading"):
        step_record.reading(step_record.in_window(log, 0, 1e9), "p99")


# ---- the device's side ------------------------------------------------------
def test_the_devices_busy_time_inside_an_interval_found_by_step():
    spans = [("train_step", 10.0 * n, 10.0 * n + 1.0, n) for n in range(1, 6)]
    spans.append(("train_step::dispatch", 20.5, 20.75, 2))
    spans.append(("chipbench::step", 20.0, 21.0, None))
    # every step 8 s of one op; step 3 holds a second, nested op and a
    # container round both: the union counts a second once
    events = [(f"fusion.{n}", 10.0 * n + 1.0, 10.0 * n + 9.0)
              for n in range(1, 6)]
    assert step_record.device_busy(events, spans, 2) == pytest.approx(0.0)
    events += [("while.1", 30.5, 39.75), ("fusion.9", 31.0, 32.0)]
    assert step_record.device_busy(events, spans, 3) \
        == pytest.approx(1e3 * (9.25 - 8.0))
    # an op that runs across the next span's start is cut there: 8.5 s in
    # step 1 and 8.25 in step 2, beside 9.25 and 8 (the median 8.375)
    events.append(("fusion.10", 19.5, 20.25))
    assert step_record.device_busy(events, spans, 1) \
        == pytest.approx(1e3 * (8.5 - 8.375))
    # the window's last step has no next span; a step the trace lost
    assert step_record.device_busy(events, spans, 5) is None
    assert step_record.device_busy(events, spans, 9) is None
    assert step_record.device_busy(events, spans[:1], 1) is None
    # a ``step`` that decodes as text still lays its span; one that is no
    # whole number lays none, and nothing is raised
    texts = [(name, s, e, None if n is None else str(n))
             for name, s, e, n in spans]
    assert step_record.device_busy(events, texts, 3) \
        == pytest.approx(1e3 * (9.25 - 8.375))
    broken = [(name, s, e, "n/a") for name, s, e, n in spans]
    assert step_record.device_busy(events, broken, 3) is None


def env_over(tmp_path, monkeypatch, log_of):
    """The recorded trace's ``Env`` with a log made up to its spans: one
    record a ``train_step`` span, its ``enter`` on ``perf_counter``."""
    env, notes = recorded_env(SCOPED, tmp_path)
    lo, hi = env.traced["window"]
    loaded = xplane_meta.load(trace.find_xplane(env.ctx.trace_dir))
    spans = [sp for sp in xplane_meta.spans(loaded, ("train_step",))
             if sp[0] == "train_step"]
    now = time.perf_counter()
    # the context's clock started 100 s ago and the window opened 50 s ago
    env.ctx.clock = lambda: time.perf_counter() - (now - 100.0)
    env.res["window_opened_at"] = 50.0
    opened = now - 50.0
    log = log_of([(sp[3], opened + (sp[1] - lo)) for sp in spans])
    monkeypatch.setattr(step_record, "program_log", lambda: log)
    return env, notes, spans


def log_at(entries):
    log = made_up_log(first=entries[0][0], count=len(entries))
    for rec, (step, enter) in zip(log, entries):
        assert rec["step"] == step
        rec["enter"] = enter
    return log


def test_the_five_metrics_on_the_recorded_trace(tmp_path, monkeypatch):
    if not os.path.exists(SCOPED):
        pytest.skip("no trace of the scoped program recorded yet")
    env, notes, spans = env_over(tmp_path, monkeypatch, log_at)
    lo, hi = env.traced["window"]
    inside = [sp for sp in spans if lo <= sp[1] < hi]
    assert len(inside) >= 4
    gaps = {a[3]: b[1] - a[1] for a, b in zip(inside, inside[1:])}
    longest = max(gaps, key=gaps.get)
    values = {name: step_record.read(env, what)
              for name, (what, _, _) in NEW.items()}
    assert values["step_ms_max.train"] == pytest.approx(
        1e3 * gaps[longest], rel=1e-6)
    # the set-up's steps before the window's opening are not read
    assert spans[0][1] < lo
    events = env.traced["devices"][0]
    want = {n: trace.busy_seconds(trace.clip(events, a[1], b[1]))
            for n, (a, b) in zip(gaps, zip(inside, inside[1:]))}
    assert values["step_excess_ms.device_busy"] == pytest.approx(
        1e3 * (want[longest] - stats.median(list(want.values()))), abs=1e-6)
    assert values["step_excess_ms.in_call"] == pytest.approx(0.0, abs=1e-6)
    line = [n for n in notes if n.startswith("step record: ")]
    assert len(line) == 2
    assert f"the longest {1e3 * gaps[longest]:.3f} ms at step {longest} " \
        in line[0]
    assert "not flagged by the program, 0 flagged in the window" in line[0]
    assert "nivcsw 0, majflt 0, gc (0, 0.0)" in line[0]
    assert "threads since" not in line[0] and "read in" in line[1]


def test_the_note_names_the_threads_that_moved(tmp_path, monkeypatch):
    if not os.path.exists(SCOPED):
        pytest.skip("no trace of the scoped program recorded yet")

    def flagged(entries):
        log = log_at(entries)
        gaps = [b["enter"] - a["enter"] for a, b in zip(log, log[1:])]
        held = log[max(range(1, len(gaps)), key=gaps.__getitem__)]
        log[0]["threads"] = [(7, "python3", 5_000_000, 100_000, 9),
                             (8, "tpu_driver", 1_000_000, 0, 4),
                             (9, "pjrt_worker", 0, 0, 1)]
        held["long"] = True
        held["threads"] = [(7, "python3", 6_000_000, 100_000, 12),
                           (8, "tpu_driver", 1_500_000, 400_000_000, 6),
                           (9, "pjrt_worker", 0, 0, 1),
                           (10, "new_thread", 300_000, 0, 1)]
        return log

    env, notes, _ = env_over(tmp_path, monkeypatch, flagged)
    assert step_record.read(env, "max_ms") > 0
    assert "flagged long by the program, 1 flagged in the window" in notes[0]
    assert "threads since the snapshot before (4 now), run-delay: " \
        "tpu_driver(8) +400.0 ms; " in notes[0]
    assert "on CPU: python3(7) +1.0 ms; tpu_driver(8) +0.5 ms; " \
        "new_thread(10) +0.3 ms; pjrt_worker(9) +0.0 ms" in notes[0]


# ---- the parent, and the program's own records ------------------------------
def test_a_program_without_a_step_log_reads_nothing_five_times(
        tmp_path, monkeypatch):
    from paddle_tpu import profiler

    assert step_record.program_log() is not None
    monkeypatch.delattr(profiler, "step_log")       # the parent of PR 49
    assert step_record.program_log() is None
    env = SimpleNamespace(steps=[(0.0, 0.3, 128)], ctx=None, res=None)
    for what, _, _ in NEW.values():
        assert step_record.read(env, what) is None


def test_a_window_without_whole_steps_or_records_reads_nothing(monkeypatch):
    monkeypatch.setattr(step_record, "program_log", made_up_log)
    now = time.perf_counter()
    ctx = SimpleNamespace(clock=lambda: time.perf_counter() - (now - 100.0),
                          note=[].append)
    env = SimpleNamespace(steps=[], ctx=ctx, res={"window_opened_at": 50.0})
    assert step_record.read(env, "max_ms") is None
    env.steps = [(0.0, 0.3, 128), (0.3, 0.6, 128)]   # the log's clock is far
    assert step_record.read(env, "max_ms") is None
    assert step_record.read(env, "device_busy") is None


def test_the_reader_reads_the_programs_own_records():
    """The records ``jit.TrainStep`` leaves, through the reader: the names
    the two sides agree on."""
    import numpy as np

    import jax
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import gpt_tiny

    paddle.seed(0)
    model = gpt_tiny(num_layers=1)
    step = TrainStep(model, lambda lg, lb: model.loss(lg, lb),
                     optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters()))
    ids = paddle.to_tensor(np.zeros((2, 8), "int32"))
    for n in range(6):
        if n == 4:
            time.sleep(0.25)            # the caller, asleep
        jax.block_until_ready(step(ids, ids)._data)
    log = step_record.program_log()
    mine = log[-6:]
    assert [r["step"] for r in mine] == [1, 2, 3, 4, 5, 6]
    pairs = step_record.in_window(log, mine[1]["enter"], mine[-1]["enter"])
    assert [a["step"] for a, _ in pairs] == [2, 3, 4, 5]
    assert step_record.reading(pairs, "max_ms") >= 250.0
    assert step_record.reading(pairs, "caller_off_cpu") >= 240.0
    assert abs(step_record.reading(pairs, "in_call")) < 125.0
    assert abs(step_record.reading(pairs, "caller_on_cpu")) < 125.0
    assert "the longest" in step_record.describe(log, pairs)
    json.dumps(log)                     # the tool writes the log as it is


# ---- the metric files and their entries -------------------------------------
def test_the_new_metric_files_and_entries_are_found_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [c["name"] for c in bench["workloads"]]
    for name, (what, layer, source) in NEW.items():
        with open(os.path.join(ROOT, "chipbench", "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert spec == {"reader": "step_record", "args": {"what": what}}
        assert hasattr(importlib.import_module(
            "chipbench.readers." + spec["reader"]), "read")
        assert entries[name] == {
            "name": name, "unit": "ms", "better": "lower", "source": source,
            "layer": layer, "moves": "train_tokens_per_s_per_chip",
            "workloads": cells}
