"""Runner ``nemotron_h_train`` on the CPU at a size a test run can hold: the
timed step object against the plain reference (every check of the cell),
the lower-precision controls rejected, faults planted in the timed program
(the state dropped between chunks; ``dt_bias`` left out; the experts'
square left out) rejected, the counters handed to the readers, and
the new cost function, reader and metric files on what the runner hands
over.

The rehearsal's ``BENCHMARK.json`` has no cell of this family (no file
that was there is edited), so the cell is built here."""

import argparse
import copy
import importlib
import json
import os
import types

import pytest

from chipbench import run as harness
from chipbench.runners import nemotron_h_train as runner

CELL_NAME = "nemotron-3-super-120b-a12b-train-ep64.seq4096"
CONFIG_FILE = "nemotron-3-super-120b-a12b-train-l11-ep64.json"
# every mechanism of the cell at a toy size, in the published pattern's
# first eleven letters: 8 Mamba heads of 8 over 2 groups at state 16,
# chunks of 16 in 64 tokens (four chunks: the carried state matters); 4 q
# heads over 2 kv heads; 16 router outputs of which 4 are held from expert
# 4 on, four a token, in a latent of 16
TINY = {
    "name": "tiny-nemotron-h", "runner": "nemotron_h_train",
    "reference": "nemotron_h", "dtype": "bfloat16", "hidden_size": 64,
    "num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEM*EME",
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "use_conv_bias": True, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "n_routed_experts": 4,
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "moe_latent_size": 16, "moe_shared_expert_intermediate_size": 64,
    "routed_scaling_factor": 5.0, "norm_topk_prob": True,
    "mlp_hidden_act": "relu2", "layer_norm_epsilon": 1e-5,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "vocab_size": 96,
    "deployment": {"router_experts": 16, "expert_offset": 4},
    "trainer": {"remat": ["flash_attention_out", "flash_attention_lse"],
                "moe_bucket_headroom": 3},
    "optimizer": {"learning_rate": 0.00022, "beta1": 0.9, "beta2": 0.95,
                  "epsilon": 1e-08, "weight_decay": 0.1},
    "check_steps": 2,
    # rehearsal size, read on the CPU at seeds 41 and 42 (sound / int8 / fp8
    # control): loss_gap up to 1.4e-4, 1.0e-4 / 6.7e-4, 6.9e-4 / 1.6e-3,
    # 1.8e-3; first_grad_norm_gap (a Mamba layer's D) 0.0093, 0.0100 /
    # 0.025, 0.070 / 0.080, 0.156; param_change_norm_gap 0.0071, 0.0119 /
    # 0.019, 0.031 / 0.036, 0.039; param_change_direction_gap 0.036, 0.043
    # / 0.098, 0.102 / 2.0, 0.29; expert_count_gap 0.0065, 0.0089 / 0.0116,
    # 0.0151 / 0.027, 0.041.  At 64 wide and weights of 0.02 the routers'
    # gradients and some experts' are under a thousandth of the median
    # leaf's (zero_grad_leaf_norm 0.0008, 0.0010, in median leaf norms);
    # the cell's own limits were read on the chip
    "limits": {"loss_gap": 0.0004, "first_grad_norm_gap": 0.018,
               "param_change_norm_gap": 0.03, "loss_rise": 0.2,
               "expert_count_gap": 0.012,
               "param_change_direction_gap": 0.07,
               "zero_grad_leaf_norm": 0.01},
}
TRAFFIC = {"kind": "pretrain", "batch": 2, "seq": 64}
CELL = {"name": "tiny-nemotron-h.pretrain", "config": "tiny-nemotron-h",
        "traffic": "tiny-pretrain", "chips": 1}


def drive(seed, control="", seconds=0.3, **config):
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0,
                              control=control)
    out = os.path.join(harness.ROOT, "chipbench_out", CELL["name"],
                       f"seed{seed}-trace0")
    os.makedirs(out, exist_ok=True)
    ctx = harness.Context(args, CELL, {**copy.deepcopy(TINY), **config},
                          dict(TRAFFIC), out)
    ctx.notes = []      # what the run says, kept beside what it prints
    ctx.note = lambda text: (ctx.notes.append(text),
                             print(text, flush=True))[1]
    return ctx, runner.run(ctx)


@pytest.fixture(scope="module")
def sound():
    """A sound run, and on ``ctx.bucket_calls`` the arguments the expert
    layers gave ``dropless.row_buckets`` while it was traced."""
    from paddle_tpu.incubate.distributed.models.moe import dropless

    calls, asked = [], dropless.row_buckets
    dropless.row_buckets = lambda *a: (calls.append(a), asked(*a))[1]
    try:
        ctx, res = drive(41)
    finally:
        dropless.row_buckets = asked
    ctx.bucket_calls = calls
    return ctx, res


def test_sound_run_passes_every_check(sound):
    ctx, res = sound
    failed = [c for c in ctx.checks if not c["ok"]]
    assert not failed, failed
    names = {c["name"] for c in ctx.checks}
    assert {"loss_gap.step1", "loss_gap.step2", "first_grad_norm_gap",
            "param_change_norm_gap", "param_change_direction_gap",
            "expert_count_gap", "loss_rise_over_window"} <= names
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    assert res["failed"] == 0 and res["attempted"] == len(res["steps"]) > 0


def test_window_counters_reach_the_readers(sound):
    from chipbench.readers import expert_load

    _, res = sound
    counts = res["counters"]["moe_tokens_per_expert"]
    assert len(counts) == len(res["steps"])
    tokens, k = TRAFFIC["batch"] * TRAFFIC["seq"], TINY["num_experts_per_tok"]
    for step in counts:     # five E layers, four experts held
        assert len(step) == 5 and all(len(layer) == 4 for layer in step)
        for layer in step:      # a share serves some, never more than all
            assert 0 < sum(layer) <= tokens * k
    assert 1.0 <= expert_load.read(_env(sound)) <= 4.0


def test_the_trainer_s_bucket_reaches_every_expert_layer(sound):
    """``trainer.moe_bucket_headroom`` is set on the layers before the step
    is built, so every expert layer asks for its buckets with it (at the
    rehearsal's 128 tokens one row tile is the worst case already); the
    cell's own asks for five times the 1,408 rows expected
    (``trainer_why``), the layer's default is twice."""
    from paddle_tpu.incubate.distributed.models.moe import dropless

    ctx, _ = sound
    tokens = TRAFFIC["batch"] * TRAFFIC["seq"]
    assert len(ctx.bucket_calls) >= 5
    assert set(ctx.bucket_calls) == {(tokens, 4, 4, 16, 3)}
    with open(os.path.join(harness.HERE, "configs", CONFIG_FILE)) as f:
        cfg = json.load(f)
    assert cfg["trainer"]["moe_bucket_headroom"] == 5
    assert dropless.row_buckets(4096, 22, 8, 512, 5) == (7168, 32768)
    assert dropless.row_buckets(4096, 22, 8, 512) == (3072, 32768)


def test_lower_precision_control_fails_a_limit():
    ctx, _ = drive(42, control="fp8,int8")
    own = [c for c in ctx.checks if not c["name"].startswith("control.")]
    assert all(c["ok"] for c in own), [c for c in own if not c["ok"]]
    for precision in ("fp8", "int8"):
        ctl = [c for c in ctx.checks
               if c["name"].startswith(f"control.{precision}.")]
        assert any(not c["ok"] for c in ctl), ctl


@pytest.mark.parametrize("fault", ["state_dropped_between_chunks",
                                   "dt_bias_left_out",
                                   "experts_square_left_out"])
def test_a_fault_planted_in_the_timed_program_is_not_correct(monkeypatch,
                                                             fault):
    """The reference is sound; the timed program starts every chunk from a
    zero state, adds no ``dt_bias``, or runs its routed experts as ``W2
    relu(W1 l)`` without the square.  Each fails a limit."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.incubate.distributed.models.moe import dropless
    from paddle_tpu.models import nemotron_h
    from paddle_tpu.nn import functional as F

    if fault == "state_dropped_between_chunks":
        scan = lax.scan

        def forgetful(f, init, xs, *a, **kw):
            return scan(lambda s, x: f(jnp.zeros_like(s), x), init, xs,
                        *a, **kw)
        monkeypatch.setattr(F.lax, "scan", forgetful)
    elif fault == "dt_bias_left_out":
        sound_sizes = nemotron_h._step_sizes
        monkeypatch.setattr(
            nemotron_h, "_step_sizes",
            lambda dt, bias, a_log: sound_sizes(dt, bias * 0.0, a_log))
    else:
        monkeypatch.setitem(dropless.BODIES, "relu2", (jax.nn.relu, 1))
    ctx, _ = drive(43)
    failed = [c["name"] for c in ctx.checks if not c["ok"]]
    assert failed, [(c["name"], c["value"]) for c in ctx.checks]


def _env(sound, steps=None, events=()):
    ctx, res = sound
    ctx.note = lambda text: None
    return types.SimpleNamespace(
        ctx=ctx, res=res, config=ctx.config, traffic=ctx.traffic,
        steps=res["steps"] if steps is None else steps,
        end_to_end=res["end_to_end"], peaks={"bf16_flops": 197e12},
        traced={"devices": {0: list(events)}})


def test_relu2_grouped_matmul_cost_counts_two_latent_matrices(sound):
    """A row costs ``4 L I`` a pass (two matrices of the LATENT width, no
    gate), the passes are the calls counted in the trace over two a layer
    step, the layer steps the window's steps times the pattern's E's."""
    from chipbench.kernel_costs import moe_grouped_matmul_relu2 as cost

    env = _env(sound)
    counts = env.res["counters"]["moe_tokens_per_expert"]
    rows = sum(sum(sum(layer) for layer in step) for step in counts)
    layer_steps = len(env.steps) * 5
    env.traced = {"devices": {0:
        [("gmm.3 bf16[256,16]", 0.0, 1.0)] * (6 * layer_steps)
        + [("tgmm.1 bf16[4,16,32]", 0.0, 1.0)] * (2 * layer_steps)}}
    flops, nbytes = cost.window_cost(env)
    assert flops == 4.0 * 4.0 * 16 * 32 * rows
    weights, row_io = 4 * 2 * 16 * 32 * 2, rows * 2 * (16 + 32) * 2
    assert nbytes == 4.0 * (layer_steps * weights + row_io)


def test_the_accepted_flash_cost_reads_this_family_s_model_group(sound):
    """``kernel_costs/flash_attention_gqa.py`` is the accepted one: the
    runner's model group names the ``*`` layer as its full-attention layer
    with the q heads it has."""
    from chipbench.kernel_costs import flash_attention_gqa as cost

    steps = 3
    ev = lambda name, n: [(name, 0.0, 1.0)] * n         # noqa: E731
    events = (ev("flash_attention_fwd.1 bf16[2,64,64]", steps)
              + ev("flash_attention_bwd_dq_dkv.2 bf16[2,64,64]", steps)
              + ev("fusion.3 bf16[2,64,64]", 9))
    env = _env(sound, steps=[None] * steps, events=events)
    (ff, ffb), (fb, fbb) = cost.call_costs(2, 64, 4, 2, 16)
    assert cost.window_cost(env) == (steps * (ff + fb), steps * (ffb + fbb))
    m = env.config["model"]
    assert m["layer_types"].count(runner.FULL) == 1 \
        and m["layer_types"].index(runner.FULL) == 7
    assert m["num_attention_heads_per_layer"][7] == 4


def test_active_parameters_follow_the_pattern_and_the_counter(sound):
    from chipbench.readers import mfu_active_nemotron_h as reader

    env = _env(sound)
    m = env.config["model"]
    n0, n1 = reader.active_params(m, 0.0), reader.active_params(m, 1.0)
    assert n1 - n0 == 5 * 2 * 16 * 32       # an expert a token, 5 E layers
    mamba = 64 * (64 + 128 + 8) + 64 * 64 + 128 * 5 + 64 + 24
    attn = 2 * 64 * 64 + 2 * 64 * 32
    moe = 64 * 16 + 2 * 64 * 16 + 2 * 64 * 64
    assert n0 == 5 * mamba + attn + 5 * moe + 11 * 64 + 64 + 64 * 96
    value = reader.read(env, "train_tokens_per_s_per_chip")
    assert 0.0 < value < 100.0
    # a program of another family: nothing to read, and no error
    other = _env(sound)
    other.config = {"model": {"hidden_size": 64}}
    assert reader.read(other, "train_tokens_per_s_per_chip") is None


def test_new_metric_files_name_what_exists():
    """Each new metric: a file that names a reader that is there, and the
    roofline a cost function that is there.  ``mamba`` and the latent's two
    projections are parts of their own (each segment listed by ONE part);
    ``mamba_ssd`` and ``mamba_conv`` select by a mark in the op's name, so
    they claim no part and ``device_ms_per_step.mamba`` holds all of the
    mixer."""
    from chipbench.readers import scope_device_ms

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = [m for m in bench["per_layer"] if m["workloads"] == [CELL_NAME]]
    assert [m["name"] for m in new] == [
        "device_ms_per_step.mamba", "device_ms_per_step.mamba_ssd",
        "device_ms_per_step.mamba_conv", "device_ms_per_step.moe_latent",
        "moe_grouped_matmul_relu2_roofline_pct", "mfu_active_pct.nemotron_h",
        "flash_attn_gqa_roofline_pct.nemotron_h"]
    # the one * layer's flash calls under a name of this cell's own, over
    # the accepted cost function (laguna's metric lists laguna's cell alone)
    with open(os.path.join(harness.HERE, "metrics",
                           new[-1]["name"] + ".json")) as f:
        assert json.load(f)["args"] == {"pattern": "flash_attention",
                                        "cost": "flash_attention_gqa"}
    parts = {}
    for m in bench["per_layer"]:
        with open(os.path.join(harness.HERE, "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(
            f"chipbench.readers.{spec['reader']}")
        assert callable(reader.read)
        if "cost" in spec["args"]:
            assert callable(importlib.import_module(
                f"chipbench.kernel_costs.{spec['args']['cost']}").window_cost)
        if spec["reader"] == "scope_device_ms":
            for seg in spec["args"].get("scope") or ():
                parts.setdefault(seg, []).append(m["name"])
    shared = {seg: names for seg, names in parts.items() if len(names) > 1}
    assert not shared, shared
    claimed = scope_device_ms.claimed_segments()
    assert {"mamba", "latent_down", "latent_up"} <= claimed
    assert not {"mamba_ssd", "mamba_conv", "gated_norm", "moe"} & claimed
    own = {("fusion.1", "jit(step)/NemotronH/layers.0/mamba/mamba_ssd/"
            "dot_general"): 1.0,
           ("fusion.2", "jit(step)/transpose(jvp(NemotronH))/layers.2/"
            "mamba/mamba_conv/mul"): 2.0,
           ("fusion.3", "jit(step)/NemotronH/layers.0/mamba/in_proj/dot"):
               4.0,
           ("fusion.4", "jit(step)/NemotronH/layers.1/moe/latent_down/dot"):
               8.0,
           ("fusion.5", "jit(step)/NemotronH/layers.1/moe/router/dot"): 16.0,
           ("fusion.6", "jit(step)/NemotronH/layers.7/attn/q_proj/dot"):
               32.0,
           ("fusion.7", "jit(step)/NemotronH/layers.7/ln_1/mul"): 64.0}
    sel = lambda **kw: scope_device_ms.selected_seconds(    # noqa: E731
        own, claimed, **kw)
    assert sel(scope=["mamba"]) == 7.0
    assert sel(phase="mamba_ssd") == 1.0 and sel(phase="mamba_conv") == 2.0
    assert sel(scope=["latent_down", "latent_up"]) == 8.0
    assert sel(scope=["router"]) == 16.0 and sel(scope=["attn"]) == 32.0
    assert sel(scope=["ln_1", "ln_2", "ln_f"]) == 64.0 and sel(scope=[]) == 0


def test_the_cell_s_configuration_says_what_it_holds():
    """1,210.9M parameters held, counted from the sizes in the file; every
    number of the source's config under its own key unless ``reduced``."""
    from chipbench.readers import mfu_active_nemotron_h as reader

    with open(os.path.join(harness.HERE, "configs", CONFIG_FILE)) as f:
        cfg = json.load(f)
    m = runner.model_group(cfg)
    h = m["hidden_size"]
    held = m["n_routed_experts"] * 2 * m["moe_latent_size"] \
        * m["moe_intermediate_size"]
    # what a token multiplies with no routed expert, the embedding, the
    # held experts, the router's 512 selection biases (a buffer: not a
    # parameter, so not counted)
    total = reader.active_params(m, 0.0) + m["vocab_size"] * h + 5 * held
    assert total == 1_210_929_024
    assert "1,210,929,024" in cfg["parameters"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "num_nextn_predict_layers"]
    assert (m["router_experts"], m["n_routed_experts"]) == (512, 8)
    assert cfg["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072, "num_nextn_predict_layers": 1,
        "hybrid_override_pattern": cfg["published"][
            "hybrid_override_pattern"]}
    published = cfg["published"]["hybrid_override_pattern"]
    assert len(published) == 88 and published[:11] == "MEMEMEM*EME" \
        == cfg["hybrid_override_pattern"]
    assert (published.count("M"), published.count("E"),
            published.count("*")) == (40, 40, 8)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}[cfg["name"]]
    assert entry["reduced"] == cfg["reduced"]
    cell = {c["name"]: c for c in bench["workloads"]}[CELL_NAME]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        cfg["name"], "pretrain-seq4096-b1", 1)
