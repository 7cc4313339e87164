"""The readers of the program's own names: op metadata and host spans out
of an ``.xplane.pb`` (``xplane_meta``), device time by model part
(``scope_device_ms``) and the program's host spans (``host_span``) -- on
made-up intervals, on the trace recorded before the program had scopes
(``data/tiny_train.xplane.pb``) and on one recorded from the scoped tiny
model (``data/tiny_train_scoped.xplane.pb``), both on the TPU v5e."""

import glob
import json
import os
import shutil
from types import SimpleNamespace

import pytest

from chipbench import readers, trace, xplane_meta
from chipbench.readers import host_span, scope_device_ms

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BARE = os.path.join(DATA, "tiny_train.xplane.pb")
SCOPED = os.path.join(DATA, "tiny_train_scoped.xplane.pb")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def metric_args(name):
    with open(os.path.join(scope_device_ms.METRICS, name + ".json")) as f:
        spec = json.load(f)
    return spec["reader"], spec["args"]


def new_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [m for m in bench["per_layer"]
            if metric_args(m["name"])[0] in ("scope_device_ms", "host_span")]


# ---- xplane_meta ---------------------------------------------------------
def test_op_metadata_of_the_recorded_trace():
    loaded = xplane_meta.load(BARE)
    meta = loaded["meta"][0]["fusion.307 bf16[64]"]
    assert meta["tf_op"] == "jit(step_fn)/convert_element_type"
    assert meta["source"].endswith("paddle_tpu/optimizer/optimizer.py:124")
    assert meta["hlo_category"] == "loop fusion"


def test_ops_and_spans_agree_with_the_benchmarks_loader():
    loaded, old = xplane_meta.load(BARE), trace.load(BARE)
    ops = loaded["ops"][0]
    assert [n for n, _, _, _ in ops] == [n for n, _, _ in old["devices"][0]]
    for (_, s, e, _), (_, s0, e0) in zip(ops, old["devices"][0]):
        assert s == pytest.approx(s0, abs=2e-9)
        assert e == pytest.approx(e0, abs=2e-9)
    # every program of the window is there, each op under its own
    programs = {tf.split("/")[0] for _, _, _, tf in ops if tf.startswith("jit(")}
    assert programs == {"jit(_threefry_split)", "jit(_unstack)",
                        "jit(step_fn)"}
    mine = xplane_meta.spans(loaded, (trace.SPAN_PREFIX,))
    assert [sp[:3] for sp in mine] == [
        (n, pytest.approx(s), pytest.approx(e)) for n, s, e in old["spans"]]
    assert xplane_meta.spans(loaded, ("train_step",)) == []


def test_segments_take_the_wrappers_off():
    assert xplane_meta.segments(
        "jit(step)/transpose(jvp(h))/checkpoint/mlp/dot_general") == [
        "step", "h", "checkpoint", "mlp", "dot_general"]
    assert xplane_meta.segments("jit(f)/jvp(loss)/sub; jit(f)/optimizer/mul") \
        == ["f", "loss", "sub", "f", "optimizer", "mul"]
    # a parameter is named by its key, dots and all: no segment of it is a part
    assert "mlp" not in xplane_meta.segments("params['gpt.h.0.mlp.fc_in.w']")


# ---- scope_device_ms on made-up intervals -----------------------------------
OPS = [
    ("while.1", 0.0, 10.0, "jit(step)/jvp()/while"),
    ("fusion.1", 0.0, 3.0, "jit(step)/jvp()/while/body/h.0/attn/qkv/dot_general"),
    ("fusion.2", 3.0, 5.0, "jit(step)/transpose(jvp())/while/body/checkpoint/"
                           "rematted_computation/h.0/mlp/fc_in/dot_general"),
    ("all-reduce.3", 5.0, 6.0, "jit(step)/jvp()/while/body/h.0/mlp/fc_out/add"),
    ("fusion.4", 6.0, 7.5, "jit(step)/transpose(jvp())/while/body/h.0/mlp/mul"),
    ("copy.5", 7.5, 8.0, ""),
    ("fusion.1", 11.0, 12.0, "jit(step)/optimizer/mul"),
    ("fusion.6", 12.0, 13.0, "jit(step)/jvp(loss)/sub; jit(step)/optimizer/mul"),
]
CLAIMED = {"attn", "mlp", "loss", "optimizer"}


def part(**kw):
    return scope_device_ms.selected_seconds(
        scope_device_ms.own_seconds(OPS), CLAIMED, **kw)


def test_parts_and_collectives_add_up_to_busy_time():
    busy = trace.busy_seconds([(n, s, e) for n, s, e, _ in OPS])
    parts = [part(scope=["attn"]), part(scope=["mlp"]),
             part(scope=["loss"]), part(scope=["optimizer"]),
             part(scope=[]), part(collectives=True)]
    assert parts == [pytest.approx(v) for v in (3.0, 3.5, 1.0, 1.0, 2.5, 1.0)]
    assert sum(parts) == pytest.approx(busy) == pytest.approx(12.0)


def test_a_collective_is_never_a_part_and_a_fused_op_has_one_owner():
    assert part(scope=["mlp"]) == pytest.approx(3.5)    # not the all-reduce
    assert part(scope=["mlp"], collectives=True) == pytest.approx(1.0)
    # fusion.6 joins a loss op and an optimizer op: the first listed owns it
    assert part(scope=["loss"]) == pytest.approx(1.0)
    assert part(scope=["optimizer"]) == pytest.approx(1.0)
    # two programs' fusion.1 stay apart
    assert part(scope=["attn"]) == pytest.approx(3.0)
    # the while keeps its overhead, and that has no part
    assert part(scope=[]) == pytest.approx(2.0 + 0.5)


def test_phase_marks_cut_across_the_parts():
    assert part(phase="transpose(") == pytest.approx(3.5)
    assert part(phase="rematted_computation") == pytest.approx(2.0)
    assert part(scope=["mlp"], phase="transpose(") == pytest.approx(3.5)
    assert part(scope=["attn"], phase="transpose(") == 0.0


def test_the_shipped_parts_do_not_overlap_and_are_all_claimed():
    lists = [metric_args(m["name"])[1].get("scope")
             for m in new_metrics()
             if metric_args(m["name"])[0] == "scope_device_ms"]
    segs = [s for scope in lists if scope for s in scope]
    assert len(segs) == len(set(segs))
    assert set(segs) == scope_device_ms.claimed_segments()
    assert [] in lists                  # and one metric reads the remainder


# ---- host_span on made-up intervals ---------------------------------------
def test_idle_goes_to_the_innermost_span_of_both_families():
    events = [("a", 0.0, 1.0), ("b", 3.0, 4.0), ("c", 6.0, 9.0)]
    spans = [("chipbench::window", 0.0, 10.0, None),
             ("chipbench::step", 0.9, 3.2, None),
             ("train_step", 1.0, 3.1, 7),
             ("train_step::operands", 1.1, 2.5, 7),
             ("train_step::dispatch", 2.5, 3.0, 7),
             ("chipbench::fetch_loss", 3.2, 6.5, None)]
    gaps = trace.idle_gaps(events, spans, 0.0, 10.0)
    assert gaps == {"train_step::operands": pytest.approx(2.0),   # 1..3
                    "chipbench::fetch_loss": pytest.approx(2.0),  # 4..6
                    "chipbench::window": pytest.approx(1.0)}      # 9..10
    assert host_span.charged(gaps, "train_step") == pytest.approx(2.0)
    assert host_span.charged(gaps, "train_step::dispatch") == 0.0
    assert host_span.charged(gaps, "chipbench::fetch_loss") \
        == pytest.approx(2.0)
    assert host_span.longest_gap(events, 0.0, 10.0) == (1.0, 3.0)
    assert host_span.longest_gap(events, 0.0, 20.0) == (9.0, 20.0)
    threads = {"pjrt/1": [("Execute", 0.5, 1.5, None), ("Wait", 1.4, 2.9, None)],
               "main/2": [("train_step", 1.0, 3.1, 7)]}
    assert host_span.overlapping(threads, 1.0, 3.0, n=2) == [
        (pytest.approx(2.0), "main/2", "train_step"),
        (pytest.approx(1.5), "pjrt/1", "Wait")]
    assert len(host_span.in_window(spans, "train_step", 0.0, 10.0)) == 1
    assert host_span.in_window(spans, "train_step", 2.0, 10.0) == []


# ---- both readers on recorded traces --------------------------------------
def recorded_env(path, tmp_path):
    """The readers' ``Env`` over a recorded trace, as ``run.py`` builds it:
    the steps are the benchmark's ``chipbench::step`` spans of the window."""
    where = tmp_path / "trace" / "plugins" / "profile" / "recorded"
    os.makedirs(where, exist_ok=True)
    shutil.copy(path, where / os.path.basename(path))
    raw = trace.load(path)
    lo, hi = trace.window_of(raw["spans"])
    steps = [(s - lo, e - lo, 128) for n, s, e in raw["spans"]
             if n == "chipbench::step" and lo <= s < hi]
    notes = []
    ctx = SimpleNamespace(config={}, traffic={}, seconds=hi - lo,
                          trace_dir=str(tmp_path / "trace"),
                          note=notes.append)
    env = readers.Env(ctx, {"steps": steps, "end_to_end": {}}, 1)
    return env, notes


def read_metric(env, name):
    reader, args = metric_args(name)
    return getattr(readers, reader).read(env, **args)


def test_a_program_without_scopes_or_spans_reads_nothing(tmp_path):
    env, _ = recorded_env(BARE, tmp_path)
    values = {m["name"]: read_metric(env, m["name"]) for m in new_metrics()}
    # what the compiler names is there all the same: the backward mark,
    # and everything else has no part
    read = {k for k, v in values.items() if v is not None}
    assert read == {"device_ms_per_step.unscoped",
                    "device_ms_per_step.backward"}
    busy = trace.busy_seconds(env.traced["devices"][0])
    assert values["device_ms_per_step.unscoped"] * len(env.steps) \
        == pytest.approx(1e3 * busy, rel=0.02)


@pytest.fixture
def scoped(tmp_path):
    if not os.path.exists(SCOPED):
        pytest.skip("no trace of the scoped program recorded yet")
    return recorded_env(SCOPED, tmp_path)


def test_scoped_trace_every_part_is_found_and_they_add_up(scoped):
    env, _ = scoped
    parts = ["attention", "mlp", "norm", "embed", "head_loss", "optimizer",
             "unscoped"]
    values = {p: read_metric(env, f"device_ms_per_step.{p}") for p in parts}
    assert all(v is not None and v > 0 for v in values.values()), values
    busy = trace.busy_seconds(env.traced["devices"][0])
    assert sum(values.values()) * len(env.steps) \
        == pytest.approx(1e3 * busy, rel=0.02)
    assert read_metric(env, "device_ms_per_step.backward") > 0
    # one chip, nothing rematerialised: nothing to read, not a zero
    assert read_metric(env, "device_ms_per_step.collective") is None
    assert read_metric(env, "device_ms_per_step.recompute") is None
    # every scope the program writes, by name
    own = scope_device_ms.window_own(
        trace.find_xplane(env.ctx.trace_dir), *env.traced["window"], 1)
    seen = {seg for _, tf in own for seg in xplane_meta.segments(tf)}
    assert seen >= {"attn", "mlp", "ln_1", "ln_2", "ln_f", "embeddings",
                    "lm_head", "loss", "optimizer"}


def test_scoped_trace_the_four_spans_a_step(scoped):
    env, notes = scoped
    loaded = xplane_meta.load(glob.glob(os.path.join(
        env.ctx.trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0])
    lo, hi = env.traced["window"]
    own = xplane_meta.spans(loaded, ("train_step",))
    for name in ("train_step", "train_step::operands",
                 "train_step::dispatch", "train_step::sync_to_model"):
        found = host_span.in_window(own, name, lo, hi)
        assert len(found) == len(env.steps), name
        # the step number is what the spans of one step share
        assert [sp[3] for sp in found] == sorted(sp[3] for sp in found)
        assert all(isinstance(sp[3], int) for sp in found)
    whole = read_metric(env, "host_ms_per_step.train_step")
    dispatch = read_metric(env, "host_ms_per_step.dispatch")
    assert 0 < dispatch < whole
    assert read_metric(env, "compiles_in_window.train") == 0
    idle = read_metric(env, "idle_ms_per_step.train_step")
    window_idle = (hi - lo) - trace.busy_seconds(env.traced["devices"][0])
    assert 0 <= idle * len(env.steps) <= 1e3 * window_idle * (1 + 1e-9)
    assert any(n.startswith("longest idle gap") for n in notes)
