"""The train step names itself: model-part scopes on the device ops of
``jit.TrainStep`` / ``parallel.SpmdTrainStep``, host spans and a compile
mark round every call, and ``stats()`` (docs/PROFILER.md)."""

import contextlib
import copy
import gc
import pickle
import re
import threading
import time
import warnings

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer, profiler
from paddle_tpu.distributed.fleet.topology import build_mesh
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.parallel import SpmdTrainStep
from paddle_tpu.profiler import Profiler, RecordEvent, StepTrace

PARTS = ["attn", "mlp", "ln_1", "ln_2", "ln_f", "embeddings", "lm_head",
         "loss", "optimizer", "grad_clip"]


def _batch(rows=4, length=16, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 128, (rows, length)).astype("int32")


def _model_and_opt():
    paddle.seed(0)
    model = gpt_tiny(num_layers=2)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters(),
                          grad_clip=optimizer.ClipGradByGlobalNorm(1.0))
    return model, opt


def _train_step(**kw):
    model, opt = _model_and_opt()
    return TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt, **kw)


def _spmd_step(**kw):
    model, opt = _model_and_opt()
    mesh = build_mesh(devices=jax.devices()[:4], dp=2, mp=2)
    return SpmdTrainStep(model, opt, mesh, **kw)


def _op_names(hlo_text):
    """The ``op_name`` of every instruction, each as its path segments with
    the transformations' wrappers taken off: ``transpose(jvp(loss))`` is
    the segment ``loss`` of a backward op."""
    out = []
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        segs = [re.sub(r"^(?:[\w-]+\()+|\)+$", "", s)
                for s in name.split("/")]
        out.append((name, segs))
    return out


def _spmd_hlo(trainer, ids):
    trainer._build()
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        (trainer.params, trainer.opt_state))
    batch = jax.ShapeDtypeStruct(ids.shape, ids.dtype,
                                 sharding=trainer.batch_sharding)
    return trainer._compiled.lower(
        *shapes, jax.ShapeDtypeStruct((), np.int32),
        jax.ShapeDtypeStruct((), np.float32),
        jax.ShapeDtypeStruct((2,), np.uint32), batch, batch
    ).compile().as_text()


@pytest.fixture(scope="module")
def compiled_names():
    """{"train": ..., "spmd": ...}: op names of the optimized HLO of both
    steps, blocks rematerialised."""
    ids = _batch()
    step = _train_step(remat=True)
    t = paddle.to_tensor(ids)
    return {"train": _op_names(step.lower(t, t).compile().as_text()),
            "spmd": _op_names(_spmd_hlo(_spmd_step(remat=True), ids))}


# ---- scopes from the Layer tree ------------------------------------------
def test_a_layer_is_scoped_by_the_name_its_parent_holds_it_by():
    model = gpt_tiny(num_layers=2)
    scopes = {n: l._scope for n, l in model.named_sublayers(include_self=True)}
    assert scopes[""] == "GPTForCausalLM"        # a root: its class
    assert scopes["gpt"] == "gpt"
    assert scopes["gpt.h.1"] == "h.1"            # a LayerList is never called
    assert scopes["gpt.h.1.attn.qkv"] == "qkv"
    assert scopes["gpt.embeddings"] == "embeddings"


def test_containers_name_their_children_and_slices_leave_them_alone():
    blocks = nn.LayerList([nn.Linear(2, 2) for _ in range(3)])
    assert [b._scope for b in blocks] == ["LayerList.0", "LayerList.1",
                                          "LayerList.2"]

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.stack = blocks
            self.seq = nn.Sequential(nn.Linear(2, 2), nn.ReLU())

        def forward(self, x):
            return self.seq(x)

    net = Net()
    assert [b._scope for b in blocks] == ["stack.0", "stack.1", "stack.2"]
    assert net.seq._scope == "seq" and net.seq[0]._scope == "0"
    blocks[:2]                                   # a view renames nothing
    assert blocks[0]._scope == "stack.0"
    blocks[1] = nn.Linear(2, 2)
    blocks.append(nn.Linear(2, 2))
    assert blocks[1]._scope == "stack.1" and blocks[3]._scope == "stack.3"


def test_a_pickled_layer_is_written_without_its_scope_and_regains_it():
    model = gpt_tiny(num_layers=2)
    want = [(n, l._scope) for n, l in model.named_sublayers(include_self=True)]
    assert "_scope" not in model.__getstate__()
    for twin in (pickle.loads(pickle.dumps(model)), copy.deepcopy(model)):
        assert [(n, l._scope) for n, l in
                twin.named_sublayers(include_self=True)] == want


# ---- scopes in the compiled steps ----------------------------------------
@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("which", ["train", "spmd"])
def test_compiled_step_names_its_parts(compiled_names, which, part):
    hits = [n for n, segs in compiled_names[which] if part in segs]
    assert hits, f"no op of the {which} step is scoped {part!r}"


@pytest.mark.parametrize("which", ["train", "spmd"])
def test_backward_and_recomputed_ops_are_marked(compiled_names, which):
    mlp = [n for n, segs in compiled_names[which] if "mlp" in segs]
    assert any("transpose(" in n for n in mlp)
    assert any("rematted_computation" in n for n in mlp)
    assert any("transpose(" not in n for n in mlp)       # and a forward one


def test_train_step_remat_is_by_block_and_changes_no_value(compiled_names):
    """``TrainStep(remat=True)`` rematerialises each block its LayerList
    holds (one block's activations live at a time), not the whole loss:
    every block is recomputed under its own name, the head and the loss
    are not, and the losses are those of the plain step."""
    recomputed = {seg for n, segs in compiled_names["train"]
                  if "rematted_computation" in n for seg in segs}
    assert {"h.0", "h.1"} <= recomputed
    assert not {"lm_head", "loss", "embeddings"} & recomputed
    t = paddle.to_tensor(_batch())
    plain, remat = _train_step(), _train_step(remat=True)
    for _ in range(3):
        assert float(remat(t, t)._data) == pytest.approx(
            float(plain(t, t)._data), rel=1e-6)


@pytest.fixture(scope="module")
def laguna_names():
    """Op names of a compiled step of the decoder whose layers differ in
    attention (``models/laguna.py``), layers rematerialised with the flash
    forward's results kept, as its cell runs it."""
    from paddle_tpu.models.laguna import laguna_tiny

    paddle.seed(0)
    model = laguna_tiny(num_local_experts=4, expert_offset=2)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                     remat=["flash_attention_out", "flash_attention_lse"])
    t = paddle.to_tensor(_batch(rows=2, length=32))
    return _op_names(step.lower(t, t).compile().as_text())


@pytest.mark.parametrize("inner,layers", [
    ("attn_full", {"layers.0", "layers.4"}),
    ("attn_window", {"layers.1", "layers.2", "layers.3"}),
    ("attn_gate", {f"layers.{i}" for i in range(5)})])
def test_attention_names_its_kind_and_its_gate_inside_attn(laguna_names,
                                                           inner, layers):
    """``attn`` as ever round the whole of attention; inside it
    ``attn_window`` or ``attn_full`` by the layer's kind, and ``attn_gate``
    round the gate's matmul and product, forward and backward."""
    hits = [(n, segs) for n, segs in laguna_names if inner in segs]
    assert hits, f"no op is scoped {inner!r}"
    for n, segs in hits:
        assert "attn" in segs and segs.index("attn") < segs.index(inner), n
        if inner == "attn_gate":
            assert {"attn_full", "attn_window"} & set(segs), n
    assert {seg for _, segs in hits for seg in segs
            if seg.startswith("layers.")} == layers
    assert any("transpose(" in n for n, _ in hits)
    assert any("transpose(" not in n for n, _ in hits)


@pytest.mark.parametrize("part", ["attn", "mlp", "ln_1", "ln_2", "ln_f",
                                  "embeddings", "lm_head", "loss",
                                  "optimizer", "router", "dispatch",
                                  "combine", "experts", "shared_experts"])
def test_the_shell_and_the_expert_layer_keep_their_scopes(laguna_names,
                                                          part):
    assert any(part in segs for _, segs in laguna_names), part


def test_scopes_change_nothing_but_metadata(monkeypatch):
    """The optimized HLO with the scopes every ``with jax.named_scope``
    opens (the Layer tree's, ``lm_head``, ``loss``, ``grad_clip``) is the
    HLO without them: the same instructions under the same names, in the
    same order."""
    def instructions():
        t = paddle.to_tensor(_batch())
        hlo = _train_step().lower(t, t).compile().as_text()
        return [re.sub(r", metadata=\{[^}]*\}", "", line)
                for line in hlo.splitlines() if " = " in line]

    scoped = instructions()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = instructions()
    assert len(scoped) > 100 and scoped == bare


# ---- host spans, the compile mark, stats() --------------------------------
def _spans_of(profiler, fn):
    before = profiler.aggregated_events()
    fn()
    after = profiler.aggregated_events()
    return {k: after[k][1] - before.get(k, (0, 0, 0))[1] for k in after
            if k.startswith("train_step")
            and after[k][1] != before.get(k, (0, 0, 0))[1]}


@pytest.mark.parametrize("which", ["train", "spmd"])
def test_a_step_records_its_spans_and_marks_the_one_that_compiled(which):
    step = _train_step() if which == "train" else _spmd_step()
    put = paddle.to_tensor if which == "train" else (lambda a: a)
    ids, short = put(_batch()), put(_batch(length=8))
    own = {StepTrace.STEP: 1, StepTrace.OPERANDS: 1, StepTrace.DISPATCH: 1}
    if which == "train":
        own[StepTrace.SYNC] = 1
    with Profiler(timer_only=True) as p:
        assert _spans_of(p, lambda: step(ids, ids)) == {
            **own, StepTrace.COMPILED: 1}
        assert step.stats() == {"steps": 1, "compiles": 1,
                                "long_steps": 0}
        assert _spans_of(p, lambda: step(ids, ids)) == own
        assert _spans_of(p, lambda: step(ids, ids)) == own
        assert step.stats() == {"steps": 3, "compiles": 1,
                                "long_steps": 0}
        assert _spans_of(p, lambda: step(short, short)) == {
            **own, StepTrace.COMPILED: 1}
    assert step.stats() == {"steps": 4, "compiles": 2,
                            "long_steps": 0}


def test_record_event_raises_what_the_annotation_raises(monkeypatch):
    class Broken:
        def __init__(self, name, **kw):
            raise ValueError(f"bad span {name}")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Broken)
    with pytest.raises(ValueError, match="bad span x"):
        with RecordEvent("x"):
            pass


def test_record_event_hands_its_attributes_to_the_annotation(monkeypatch):
    seen = []

    class Spy(contextlib.nullcontext):
        def __init__(self, name, **kw):
            super().__init__()
            seen.append((name, kw))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    with RecordEvent("train_step", step=7):
        pass
    assert seen == [("train_step", {"step": 7})]


# ---- the compile's account (docs/PROFILER.md) ------------------------------
BYTES = ["argument_bytes", "output_bytes", "alias_bytes", "temp_bytes",
         "generated_code_bytes"]


def _step_and_batches(which):
    step = _train_step() if which == "train" else _spmd_step()
    put = paddle.to_tensor if which == "train" else (lambda a: a)
    return step, put(_batch()), put(_batch(length=8))


@pytest.mark.parametrize("which", ["train", "spmd"])
def test_the_first_call_takes_the_account_and_builds_nothing_for_it(
        which, monkeypatch):
    step, ids, _ = _step_and_batches(which)
    assert step.compile_account() is None
    counts, take = [], profiler._ExecutablesBuilt.account.__func__

    def watched(cls, *args):
        counts.append(cls.count)
        record = take(cls, *args)
        counts.append(cls.count)
        return record

    monkeypatch.setattr(profiler._ExecutablesBuilt, "account",
                        classmethod(watched))
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # a second build would warn
        step(ids, ids)
    acc = step.compile_account()
    assert len(counts) == 2 and counts[0] == counts[1]
    assert acc["name"] == "train_step" and acc["step"] == 1
    assert acc["program"] == "jit(train_step)" and acc["cache"] == "off"
    assert all(isinstance(acc[k], int) and acc[k] >= 0 for k in BYTES)
    assert acc["argument_bytes"] > 0 and acc["temp_bytes"] > 0
    assert acc["alias_bytes"] > 0           # the state is donated
    assert acc["reserved_bytes"] == (
        acc["argument_bytes"] + acc["output_bytes"] - acc["alias_bytes"]
        + acc["temp_bytes"] + acc["generated_code_bytes"])
    assert acc["trace_s"] > 0 and acc["lower_s"] > 0 and acc["backend_s"] > 0
    # a nested trace is counted once: the parts fit inside the call
    assert acc["trace_s"] + acc["lower_s"] + acc["backend_s"] <= acc["call_s"]
    assert acc["since"] < acc["at"]
    assert 0 < acc["account_s"] < 0.25      # answered from jax's caches
    assert step.stats() == {"steps": 1, "compiles": 1,
                            "long_steps": 0}


@pytest.mark.parametrize("which", ["train", "spmd"])
def test_the_account_counts_the_compiled_collectives(which, monkeypatch):
    """``collectives_async`` / ``collectives_sync``: what ``parallel
    .compiled_collectives`` finds IN THE COMPILED TEXT of the step that
    was built.  The CPU's compiler makes none asynchronous; a step over one
    device holds none, and its text is not even read."""
    from paddle_tpu.parallel import compiled_collectives, trainer

    read = []
    monkeypatch.setattr(
        trainer, "compiled_collectives",
        lambda text: read.append(text) or compiled_collectives(text))
    step, ids, _ = _step_and_batches(which)
    step(ids, ids)
    acc = step.compile_account()
    assert acc["collectives_async"] == 0
    if which == "train":
        assert acc["collectives_sync"] == 0 and not read
    else:
        found = compiled_collectives(
            step.lower(ids, ids).compile().as_text())
        assert len(read) == 1 and found == compiled_collectives(read[0])
        assert acc["collectives_sync"] == len(found) > 0


def test_the_account_of_a_step_with_compile_options_builds_nothing(
        monkeypatch):
    """The options ``SpmdTrainStep`` gives ``jax.jit`` are part of the
    jitted function: the account's lowering of it is still answered from
    jax's caches (an option the CPU's compiler knows stands in for the
    TPU's, which it refuses)."""
    from paddle_tpu.parallel import trainer

    monkeypatch.setattr(trainer, "compile_options",
                        lambda mesh: {"xla_embed_ir_in_executable": True})
    step, ids, _ = _step_and_batches("spmd")
    built = profiler._ExecutablesBuilt.count
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # a second build would warn
        step(ids, ids)
    assert profiler._ExecutablesBuilt.count == built + 1
    acc = step.compile_account()
    assert acc["collectives_sync"] > 0 and 0 < acc["account_s"] < 0.25
    assert step.stats() == {"steps": 1, "compiles": 1,
                            "long_steps": 0}


def test_the_account_is_one_chips_share_under_a_mesh():
    """The compiler's account of a partitioned step is a chip's: the
    donated state of the dp2 x mp2 trainer is under the whole state."""
    one, ids, _ = _step_and_batches("train")
    four, _, _ = _step_and_batches("spmd")
    one(ids, ids)
    four(_batch(), _batch())
    assert four.compile_account()["alias_bytes"] \
        < 0.75 * one.compile_account()["alias_bytes"]


@pytest.mark.parametrize("which", ["train", "spmd"])
def test_only_a_call_that_compiled_adds_a_record(which):
    step, ids, short = _step_and_batches(which)
    step(ids, ids)
    first, log = step.compile_account(), profiler.compile_log()
    assert log[-1] is first
    step(ids, ids)
    step(ids, ids)
    assert step.compile_account() is first
    assert len(profiler.compile_log()) == len(log)
    step(short, short)                      # another shape: a second record
    second = step.compile_account()
    assert second is not first and second["step"] == 4
    assert second is profiler.compile_log()[-1]
    assert first["step"] == 1
    assert second["argument_bytes"] < first["argument_bytes"]


@pytest.mark.parametrize("which", ["train", "spmd"])
def test_the_record_outlives_the_step_object(which):
    step, ids, _ = _step_and_batches(which)
    step(ids, ids)
    step_id = id(step.compile_account())
    del step
    gc.collect()
    kept = [r for r in profiler.compile_log() if id(r) == step_id]
    assert len(kept) == 1 and kept[0]["reserved_bytes"] > 0


@pytest.fixture
def fresh_persistent_cache(tmp_path):
    """jax's persistent compilation cache in a directory of its own, every
    program worth caching; what was set before comes back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ["jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes"]
    before = [getattr(jax.config, n) for n in names]
    for n, v in zip(names, [str(tmp_path), 0.0, 0]):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    yield tmp_path
    for n, v in zip(names, before):
        jax.config.update(n, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("which", ["train", "spmd"])
def test_the_record_says_whether_the_persistent_cache_served_the_build(
        which, fresh_persistent_cache):
    """A twin built from the same seed compiles the same program (the same
    step object's NEXT call need not: ``SpmdTrainStep``'s operands come
    back described differently)."""
    step, ids, _ = _step_and_batches(which)
    step(ids, ids)
    assert step.compile_account()["cache"] == "miss"
    jax.clear_caches()          # the process forgets; the directory does not
    twin, ids, _ = _step_and_batches(which)
    twin(ids, ids)
    again = twin.compile_account()
    assert again is not step.compile_account()
    assert again["cache"] == "hit" and again["step"] == 1
    assert again["temp_bytes"] == step.compile_account()["temp_bytes"]
    assert again["backend_s"] < step.compile_account()["backend_s"]


@pytest.mark.parametrize("which", ["train", "spmd"])
def test_the_trainers_construction_is_recorded_without_a_session(which):
    before = len(profiler.compile_log())
    t0 = time.perf_counter()
    _step_and_batches(which)
    took = time.perf_counter() - t0
    inits = [r for r in profiler.compile_log()[before:]
             if r["kind"] == "init"]
    assert len(inits) == 1 and inits[0]["name"] == StepTrace.INIT
    assert 0 < inits[0]["seconds"] <= took and inits[0]["at"] >= t0
    with Profiler(timer_only=True) as p:    # and as a span where one is on
        _step_and_batches(which)
        assert p.aggregated_events()[StepTrace.INIT][1] == 1


def test_the_compile_mark_says_what_the_compile_was(monkeypatch):
    seen = []

    class Spy(contextlib.nullcontext):
        def __init__(self, name, **kw):
            super().__init__()
            seen.append((name, kw))

    step, ids, _ = _step_and_batches("train")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    step(ids, ids)
    step(ids, ids)
    marks = [kw for name, kw in seen if name == StepTrace.COMPILED]
    acc = step.compile_account()
    assert marks == [{"step": 1, "cache": "off",
                      "backend_s": acc["backend_s"],
                      "temp_bytes": acc["temp_bytes"]}]


def test_the_log_counts_a_nested_trace_once_and_keeps_threads_apart():
    """The listener on made-up events: the jits a program calls report
    their traces before the trace that holds them; a trace that led to no
    build is stale; what a lowering rule traces is inside the lowering;
    another thread's events belong to another record."""
    log = profiler._ExecutablesBuilt
    before = log.count
    other = threading.Thread(target=lambda: log._on(log.TRACE, 3.0))
    other.start()
    other.join(timeout=10)
    assert not other.is_alive()
    log._on(log.TRACE, 0.001, fun_name="stale")
    time.sleep(0.05)
    log._on(log.TRACE, 0.002, fun_name="inner")
    log._on(log.TRACE, 0.002, fun_name="inner")
    log._on(log.TRACE, 0.03, fun_name="f")
    time.sleep(0.02)
    log._on(log.TRACE, 0.001, fun_name="traced_by_a_lowering_rule")
    log._on(log.LOWER, 0.015, fun_name="jit(f)")
    log._on_cache("/jax/compilation_cache/cache_misses")
    log._on(log.EVENT, 0.5, fun_name="jit(f)")
    rec = profiler.compile_log()[-1]
    assert log.count == before + 1
    assert (rec["program"], rec["trace_s"], rec["lower_s"], rec["backend_s"],
            rec["cache"]) == ("jit(f)", 0.03, 0.015, 0.5, "miss")
    assert rec["at"] - rec["since"] == pytest.approx(0.5, abs=0.01)
    log._on(log.EVENT, 0.5, fun_name="jit(g)")     # nothing is carried over
    last = profiler.compile_log()[-1]
    assert (last["trace_s"], last["lower_s"], last["cache"]) \
        == (0.0, 0.0, "off")
    for made_up in (rec, last):
        log.log.remove(made_up)


@pytest.mark.parametrize("fails", ["start_trace", "stop_trace"])
def test_a_device_trace_that_fails_says_so(monkeypatch, tmp_path, fails):
    def broken(*a, **kw):
        raise RuntimeError("only one profile at a time")

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(jax.profiler, fails, broken)
    p = Profiler(trace_dir=str(tmp_path))
    with pytest.warns(RuntimeWarning,
                      match="did not st.*only one profile at a time"):
        p.start()
        with RecordEvent("x"):
            pass
        p.stop()
    assert not p._device_tracing
    assert p.aggregated_events()["x"][1] == 1   # the host events are kept
