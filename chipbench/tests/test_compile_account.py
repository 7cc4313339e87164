"""The readers of what the program records where its step compiles
(``readers/compile_account.py``: the process's compile log) and of the
compiler's own mark on the ops it rematerialised
(``readers/op_mark_device_ms.py``), on made-up records and intervals and
on the trace recorded from the scoped tiny model."""

import importlib
import json
import os
import time
from types import SimpleNamespace

import pytest

from chipbench.readers import compile_account, op_mark_device_ms, \
    scope_device_ms
from chipbench.tests.test_scopes import SCOPED, recorded_env

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = {"compiled_hbm_gb.train": ("device", "train_tokens_per_s_per_chip"),
       "compiled_temp_hbm_gb.train": ("device",
                                      "train_tokens_per_s_per_chip"),
       "device_ms_per_step.xla_remat": ("train step",
                                        "train_tokens_per_s_per_chip"),
       "setup_compile_s.train": ("train step", "setup_s"),
       "setup_compile_s.other": ("train step", "setup_s"),
       "compile_cache_misses.setup": ("train step", "setup_s"),
       "setup_init_s.train": ("train step", "setup_s")}


# ---- op_mark_device_ms -----------------------------------------------------
def test_the_compilers_clones_are_found_by_their_instruction_names():
    step = "jit(train_step)/"
    ops = [
        # a layer scan: the clone and its original run inside the while
        ("while.3 (bf16[4,8])", 0.0, 10.0, ""),
        ("fusion.12 bf16[4,8]", 0.0, 3.0, step + "jvp(m)/h.0/mlp/dot"),
        ("fusion.12.remat bf16[4,8]", 3.0, 5.0, step + "jvp(m)/h.0/mlp/dot"),
        ("convolution_add_fusion.remat2 bf16[4,8]", 5.0, 6.0,
         step + "jvp(m)/h.0/mlp/dot"),
        # jax.checkpoint's recomputation is the PROGRAM's: not the mark
        ("fusion.7 bf16[4,8]", 6.0, 9.0,
         step + "transpose(jvp(m))/checkpoint/rematted_computation/h.0/dot"),
        ("copy.remat_start bf16[4,8]", 10.0, 10.5, ""),
        ("fusion.remat.1 f32[8]", 11.0, 11.25, step + "optimizer/mul"),
    ]
    own = scope_device_ms.own_seconds(ops)
    assert op_mark_device_ms.marked_seconds(own, ".remat") \
        == pytest.approx(2.0 + 1.0 + 0.5 + 0.25)
    # the container keeps its overhead alone; nothing of it is marked
    assert own[("while.3 (bf16[4,8])", "")] == pytest.approx(1.0)
    assert op_mark_device_ms.marked_seconds(own, ".nothing") == 0.0
    assert op_mark_device_ms.instruction("fusion.12.remat bf16[4,8]") \
        == "fusion.12.remat"


def test_a_step_the_compiler_cloned_nothing_in_reads_zero(tmp_path):
    env, _ = recorded_env(SCOPED, tmp_path)
    assert env.steps
    value = op_mark_device_ms.read(env, mark=".remat")
    assert value == 0.0 and value is not None
    # the mark reads the instruction, the phase reads the path
    assert op_mark_device_ms.read(env, mark="fusion") > 0.0


def test_without_a_whole_step_nothing_is_read(tmp_path):
    env, _ = recorded_env(SCOPED, tmp_path)
    env.steps = []
    assert op_mark_device_ms.read(env, mark=".remat") is None


# ---- compile_account ---------------------------------------------------------
def executable(at, program="jit(f)", cache="hit", seconds=(0.5, 0.25, 1.0),
               **more):
    trace_s, lower_s, backend_s = seconds
    return {"kind": "executable", "program": program, "at": at,
            "since": at - sum(seconds), "trace_s": trace_s,
            "lower_s": lower_s, "backend_s": backend_s, "cache": cache,
            **more}


def step_record(at, step, temp, **more):
    sizes = {"argument_bytes": 10_000_000_000, "output_bytes": 10_000_000_100,
             "alias_bytes": 10_000_000_000, "temp_bytes": temp,
             "generated_code_bytes": 90_000_000}
    return executable(at, "jit(train_step)", name="train_step", step=step,
                      call_s=30.0, account_s=0.002, **sizes,
                      reserved_bytes=10_000_000_100 + temp + 90_000_000,
                      **more)


LOG = [
    executable(1.0, "jit(convert_element_type)", "miss"),
    {"kind": "init", "name": "train_step::init", "at": 2.0, "seconds": 7.0},
    executable(3.0, "jit(_threefry_split)", "off", (0.125, 0.125, 0.25)),
    step_record(20.0, 1, 5_650_000_000, cache="hit",
                seconds=(4.0, 2.0, 8.0)),
    executable(21.0, "jit(_unstack)", "hit", (0.0, 0.0, 0.5)),
    # the window opens at 30: what follows is not set-up's
    step_record(35.0, 9, 7_000_000_000, cache="miss",
                seconds=(4.0, 2.0, 100.0)),
    executable(36.0, "jit(reference)", "miss", (1.0, 1.0, 50.0)),
    {"kind": "init", "name": "train_step::init", "at": 37.0, "seconds": 3.0},
]


@pytest.mark.parametrize("what,want", [
    ("reserved_gb", 15.7400001),        # the FIRST train_step record's
    ("temp_gb", 5.65),
    ("step_compile_s", 14.0),
    ("other_compile_s", 1.75 + 0.5 + 0.5),
    ("cache_misses", 1),
    ("init_s", 7.0)])
def test_readings_of_a_hand_made_log_cut_at_the_windows_opening(what, want):
    assert compile_account.reading(LOG, what, 30.0) == pytest.approx(want)


def test_a_step_that_compiled_again_before_the_window_is_set_ups_other():
    assert compile_account.reading(LOG, "other_compile_s", 36.5) \
        == pytest.approx(2.75 + 106.0 + 52.0)
    assert compile_account.reading(LOG, "cache_misses", 36.5) == 3
    assert compile_account.reading(LOG, "step_compile_s", 36.5) == 14.0
    assert compile_account.reading(LOG, "init_s", 40.0) == 10.0


def test_a_log_without_the_record_reads_nothing_and_a_warm_run_reads_zero():
    bare = [r for r in LOG if r.get("name") != "train_step"]
    for what in ("reserved_gb", "temp_gb", "step_compile_s"):
        assert compile_account.reading(bare, what, 30.0) is None
    assert compile_account.reading(bare[2:3], "init_s", 30.0) is None
    assert compile_account.reading(LOG[2:5], "cache_misses", 30.0) == 0
    no_account = [{k: v for k, v in LOG[3].items()
                   if not k.endswith("_bytes")}]
    assert compile_account.reading(no_account, "reserved_gb", 30.0) is None
    assert compile_account.reading(no_account, "step_compile_s", 30.0) == 14.0
    with pytest.raises(ValueError, match="unknown reading"):
        compile_account.reading(LOG, "reserved_tb", 30.0)


def env_at(opened_ago, notes):
    """An ``env`` whose window opened ``opened_ago`` seconds ago, on a
    context clock that started 100 s ago (``run.py``'s ``_T0``)."""
    t0 = time.perf_counter() - 100.0
    ctx = SimpleNamespace(clock=lambda: time.perf_counter() - t0,
                          note=notes.append)
    return SimpleNamespace(ctx=ctx,
                           res={"window_opened_at": 100.0 - opened_ago})


def test_the_windows_opening_is_brought_onto_the_logs_clock(monkeypatch):
    now = time.perf_counter()
    log = [step_record(now - 50.0, 1, 5_650_000_000),
           executable(now - 45.0, cache="miss"),
           executable(now - 10.0, cache="miss")]
    monkeypatch.setattr(compile_account, "program_log", lambda: log)
    notes = []
    assert compile_account.read(env_at(20.0, notes), "cache_misses") == 1
    assert compile_account.read(env_at(5.0, notes), "cache_misses") == 2
    assert compile_account.read(env_at(60.0, notes), "cache_misses") == 0
    assert compile_account.read(env_at(20.0, notes), "temp_gb") == 5.65
    assert notes == []
    assert compile_account.read(env_at(20.0, notes), "other_compile_s") \
        == 1.75
    assert len(notes) == 1 and "2 executables before the window" in notes[0]
    assert "jit(train_step) 1.75 s" in notes[0]
    assert "step 1: call 30.000 s, its account 0.0020 s" in notes[0]
    assert "took no account" in compile_account.describe(log[1:], now)


def test_a_program_without_a_compile_log_reads_nothing(monkeypatch):
    from paddle_tpu import profiler

    assert compile_account.program_log() is not None
    monkeypatch.delattr(profiler, "compile_log")    # the parent of PR 34
    assert compile_account.program_log() is None
    for what in ("reserved_gb", "step_compile_s", "cache_misses", "init_s"):
        assert compile_account.read(env_at(20.0, []), what) is None


def test_the_readers_read_the_programs_own_log():
    """The record ``jit.TrainStep`` leaves, through the reader: the names
    the two sides agree on."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer, profiler
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import gpt_tiny

    before = len(profiler.compile_log())
    paddle.seed(0)
    model = gpt_tiny(num_layers=1)
    step = TrainStep(model, lambda lg, lb: model.loss(lg, lb),
                     optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters()))
    ids = paddle.to_tensor(np.zeros((2, 8), "int32"))
    step(ids, ids)
    acc = step.compile_account()
    mine = profiler.compile_log()[before:]
    opened = time.perf_counter()
    assert compile_account.reading(mine, "reserved_gb", opened) \
        == acc["reserved_bytes"] / 1e9
    assert compile_account.reading(mine, "temp_gb", opened) \
        == acc["temp_bytes"] / 1e9
    assert compile_account.reading(mine, "step_compile_s", opened) \
        == acc["trace_s"] + acc["lower_s"] + acc["backend_s"]
    assert compile_account.reading(mine, "init_s", opened) > 0
    assert compile_account.reading(mine, "other_compile_s", opened) > 0


# ---- the shipped files -------------------------------------------------------
@pytest.mark.parametrize("name", sorted(NEW))
def test_every_new_metric_has_its_file_its_reader_and_its_entry(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert (entry["layer"], entry["moves"]) == NEW[name]
    assert entry["better"] == "lower"
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    with open(os.path.join(scope_device_ms.METRICS, name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
    assert callable(reader.read)
    assert spec["reader"] in ("compile_account", "op_mark_device_ms")
    # the arguments are ones the reader takes
    code = reader.read.__code__
    assert set(spec["args"]) <= set(code.co_varnames[:code.co_argcount])


def test_the_new_entries_are_the_last_seven_and_claim_no_scope():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"][-7:]] == list(NEW)
    # a reader that is not scope_device_ms claims no part of the step
    before = scope_device_ms.claimed_segments()
    assert "remat" not in before and ".remat" not in before
