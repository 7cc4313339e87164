"""Runner ``sdar_train`` on the CPU at a size a test run can hold: the timed
step object against the plain reference (every check of the cell), the
lower-precision controls rejected, three faults planted in the timed program
rejected each by a named limit, the counters handed to the readers, and the
new traffic kind, cost function, readers and metric files on what the runner
hands over.

The rehearsal's ``BENCHMARK.json`` has no cell of this family (no file that
was there is edited), so the cell is built here."""

import argparse
import copy
import importlib
import json
import os
import types

import numpy as np
import pytest

from chipbench import run as harness
from chipbench.runners import sdar_train as runner

CELL_NAME = "sdar-30b-a3b-train-ep8.seq8192"

# every mechanism of the cell at a toy size: 4 q heads over 2 kv heads of 16,
# 2 layers, 8 experts held of 16 routed (4 a position), blocks of 4 in rows
# of 64 data tokens, vocabulary 512 whose last row is the mask token
TINY = {
    "name": "tiny-sdar", "runner": "sdar_train", "reference": "sdar",
    "dtype": "bfloat16", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rope_theta": 1000000,
    "rms_norm_eps": 1e-6, "vocab_size": 512, "block_length": 4,
    "mask_token_id": 511, "initializer_range": 0.05,
    "deployment": {"router_experts": 16, "expert_offset": 4},
    "trainer": {"remat": ["flash_attention_out", "flash_attention_lse"]},
    "optimizer": {"learning_rate": 0.0003, "beta1": 0.9, "beta2": 0.95,
                  "epsilon": 1e-08, "weight_decay": 0.1},
    "check_steps": 2,
    # rehearsal size, read on the CPU at seeds 41, 42, 43 (sound; then the
    # fp8 and the int8 control at the same seeds): loss_gap, the larger
    # step, 8.9e-4, 4.0e-4, 1.3e-3; 6.4e-3, 9.1e-3, 1.2e-2; 4.5e-3, 2.3e-3,
    # 1.4e-3.  first_grad_norm_gap 0.0028, 0.0033, 0.0061; 0.074, 0.082,
    # 0.040; 0.012, 0.020, 0.019.  param_change_norm_gap 0.0022, 0.0047,
    # 0.0044; 0.0106, 0.0142, 0.0150; 0.0119, 0.0185, 0.0081.
    # param_change_direction_gap 0.018, 0.097, 0.050; 0.31, 0.37, 0.157;
    # 0.076, 0.162, 0.063.  expert_count_gap 0.0029, 0.0049, 0.0042; 0.031,
    # 0.021, 0.018; 0.0103, 0.0103, 0.0114.  masked_loss_terms_gap 0, exact.
    # The loss over 2 x 64 fresh tokens and fresh noise a step (weights 1 /
    # t up to 1,000) moves -1.94 to +0.72 in a run: a divergence guard.  The
    # cell's own limits were read on the chip
    "limits": {"loss_gap": 0.002, "first_grad_norm_gap": 0.009,
               "param_change_norm_gap": 0.007,
               "param_change_direction_gap": 0.13,
               "expert_count_gap": 0.0075, "masked_loss_terms_gap": 0,
               "zero_grad_leaf_norm": 0.01, "loss_rise": 3.0},
}
TRAFFIC = {"kind": "block_diffusion", "batch": 2, "seq": 64, "block": 4,
           "t_low": 0.001, "t_high": 1.0}
CELL = {"name": "tiny-sdar.blockdiff", "config": "tiny-sdar",
        "traffic": "tiny-blockdiff", "chips": 1}


def drive(seed, control="", seconds=0.3, **config):
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0,
                              control=control)
    out = os.path.join(harness.ROOT, "chipbench_out", CELL["name"],
                       f"seed{seed}-trace0")
    os.makedirs(out, exist_ok=True)
    ctx = harness.Context(args, CELL, {**copy.deepcopy(TINY), **config},
                          dict(TRAFFIC), out)
    return ctx, runner.run(ctx)


@pytest.fixture(scope="module")
def sound():
    return drive(41)


def test_sound_run_passes_every_check(sound):
    ctx, res = sound
    failed = [c for c in ctx.checks if not c["ok"]]
    assert not failed, failed
    names = {c["name"] for c in ctx.checks}
    assert {"loss_gap.step1", "loss_gap.step2", "first_grad_norm_gap",
            "param_change_norm_gap", "param_change_direction_gap",
            "expert_count_gap", "masked_loss_terms_gap",
            "loss_rise_over_window"} <= names
    # tokens/s counts DATA tokens, never the noised copy
    assert res["steps"][0][2] == 2 * 64
    assert res["offered"]["positions_per_step"] == 2 * 2 * 64
    assert res["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    assert res["failed"] == 0 and res["attempted"] == len(res["steps"]) > 0


def test_lower_precision_control_fails_a_limit():
    ctx, _ = drive(42, control="fp8,int8")
    own = [c for c in ctx.checks if not c["name"].startswith("control.")]
    assert all(c["ok"] for c in own), [c for c in own if not c["ok"]]
    for precision in ("fp8", "int8"):
        ctl = [c for c in ctx.checks
               if c["name"].startswith(f"control.{precision}.")]
        assert {c["name"].split(".", 2)[2] for c in ctl} >= {
            "expert_count_gap", "param_change_direction_gap",
            "first_grad_norm_gap"}
        assert any(not c["ok"] for c in ctl), ctl


def plant(monkeypatch, fault):
    """Break the TIMED program; the reference stays sound.  (The builder's
    chip runs plant the same three by a script round ``chipbench.run.main``:
    on the chip the mask is the kernels', so the first fault patches their
    tile mask and has every block visited.)"""
    import jax.numpy as jnp

    from paddle_tpu.models import laguna, sdar
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import attention_kernel as ak

    if fault == "noised_sees_earlier_noised":
        # causal by block over the noised half too ("causal over 2 L")
        def dense(seq, block):
            pos = jnp.arange(seq)
            blk, noised = (pos % (seq // 2)) // block, pos < seq // 2
            return jnp.where(noised[None, :] == noised[:, None],
                             blk[None, :] <= blk[:, None],
                             noised[:, None] & (blk[None, :] < blk[:, None]))

        def tile(s, row0, col0, row_axis, half, block):
            import jax
            shift = block.bit_length() - 1
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   row_axis)
            cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   1 - row_axis)
            rb = jnp.where(rows < half, rows, rows - half) >> shift
            cb = jnp.where(cols < half, cols, cols - half) >> shift
            r_noised, c_noised = rows < half, cols < half
            r_clean, c_clean = rows >= half, cols >= half
            keep = (((r_noised & c_noised) | (r_clean & c_clean))
                    & (cb <= rb)) | (r_noised & c_clean & (cb < rb))
            return jnp.where(keep, s, ak._NEG_INF)

        monkeypatch.setattr(pk, "block_diffusion_mask", dense)
        monkeypatch.setattr(ak, "_mask_block_diffusion", tile)
        monkeypatch.setattr(
            ak, "_blockdiff_key_blocks",
            lambda qi, bq, bk, half, block: ((0, 2 * half // bk),
                                             (2 * half // bk,) * 2))
        monkeypatch.setattr(
            ak, "_blockdiff_query_blocks",
            lambda ki, bq, bk, num_qb, half, block: ((0, num_qb),
                                                     (num_qb, num_qb)))
    elif fault == "clean_positions_count_on":
        # the clean half's position ids L .. 2 L - 1
        tables = laguna.rope_tables
        monkeypatch.setattr(
            laguna, "rope_tables",
            lambda head_dim, seq, params, positions=None: tables(
                head_dim, seq, params))
    elif fault == "no_t_weight":
        real = sdar.SdarForBlockDiffusion.loss
        monkeypatch.setattr(
            sdar.SdarForBlockDiffusion, "loss",
            lambda self, logits, ids, noised, t: real(
                self, logits, ids, noised, jnp.ones_like(sdar._data(t))))
    else:
        raise ValueError(fault)


# the limit that must catch each fault (others may fail too)
CAUGHT_BY = {"noised_sees_earlier_noised": "first_grad_norm_gap",
             "clean_positions_count_on": "first_grad_norm_gap",
             "no_t_weight": "loss_gap.step1"}


@pytest.mark.parametrize("fault", sorted(CAUGHT_BY))
def test_a_fault_planted_in_the_timed_program_is_not_correct(monkeypatch,
                                                             fault):
    plant(monkeypatch, fault)
    ctx, _ = drive(43)
    failed = [c["name"] for c in ctx.checks if not c["ok"]]
    assert CAUGHT_BY[fault] in failed, \
        [(c["name"], c["value"]) for c in ctx.checks]
    # the loss terms are the feed's masks: no fault here moves them
    assert "masked_loss_terms_gap" not in failed


def test_a_lost_loss_term_is_caught_exactly(monkeypatch):
    """``masked_loss_terms_gap`` is exact: a program that counts the mask
    token's id one off loses every term's count."""
    from paddle_tpu.models import sdar

    init = sdar.SdarConfig.__init__

    def other_mask(self, *args, **kw):
        init(self, *args, **{**kw, "mask_token_id": 510})
    monkeypatch.setattr(sdar.SdarConfig, "__init__", other_mask)
    ctx, _ = drive(43)
    failed = [c["name"] for c in ctx.checks if not c["ok"]]
    assert "masked_loss_terms_gap" in failed


def _env(sound, steps=None, events=(), rate=None):
    ctx, res = sound
    ctx.note = lambda text: None
    end_to_end = res["end_to_end"] if rate is None else {
        "train_tokens_per_s_per_chip": rate}
    return types.SimpleNamespace(
        ctx=ctx, res=res, config=ctx.config, traffic=ctx.traffic,
        steps=res["steps"] if steps is None else steps,
        end_to_end=end_to_end,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        traced={"devices": {0: list(events)}})


def test_window_counters_reach_the_readers(sound):
    from chipbench.readers import (counter_over_tokens, counter_ratio,
                                   expert_load)

    _, res = sound
    counters = res["counters"]
    assert set(counters) == set(runner.COUNTERS)
    assert all(len(v) == len(res["steps"]) for v in counters.values())
    # off the TPU the composition scores the dense square: (2 L)^2 over
    # L (L + B)
    assert counter_ratio.read(_env(sound), "blockdiff_pairs_scored",
                              "blockdiff_pairs_needed") \
        == pytest.approx(128 * 128 / (64 * 68))
    share = counter_over_tokens.read(_env(sound), "blockdiff_masked_tokens")
    assert 0.3 < share < 0.7
    assert share == pytest.approx(float(np.median(
        [m / 128 for m in counters["blockdiff_masked_tokens"]])))
    assert expert_load.read(_env(sound)) >= 1.0
    kept = sound[1].pop("counters")
    assert counter_over_tokens.read(_env(sound),
                                    "blockdiff_masked_tokens") is None
    sound[1]["counters"] = kept


def test_the_cost_counts_the_pairs_the_mask_holds(sound):
    from chipbench.kernel_costs import flash_attention_blockdiff as cost
    from chipbench.readers import kernel_roofline

    # the cell: L (L + B) pairs a head, 32 heads of 128: forward 2 and
    # backward 5 matmuls of 2 * pairs * 128 FLOP a head
    assert cost.pairs(8192, 4) == 67_141_632
    (f_flop, f_bytes), (b_flop, b_bytes) = cost.call_costs(1, 8192, 4, 32, 4,
                                                           128)
    assert f_flop == 2 * 2.0 * 32 * 67_141_632 * 128
    assert b_flop == 2.5 * f_flop
    assert 1.09e12 < f_flop < 1.11e12           # ISSUE 44's 1.10 TFLOP
    per_q, per_kv, stats = 16384 * 32 * 128 * 2, 16384 * 4 * 128 * 2, \
        32 * 16384 * 4
    assert f_bytes == 2 * per_q + 2 * per_kv + stats
    assert b_bytes == 4 * per_q + 4 * per_kv + 2 * stats

    def one_after_another(names):
        return [(name, 1e-3 * i, 1e-3 * (i + 1)) for i, name in
                enumerate(names)]

    steps, layers = 3, 2
    events = one_after_another(
        ["flash_blockdiff4_attention_fwd.2 bf16[1,128,64]"] * steps * layers
        + ["flash_blockdiff4_attention_bwd_dq_dkv.3 bf16[1,128,64]"]
        * steps * layers + ["fusion.9 bf16[2,64,64]"] * 7
        + ["flash_attention_fwd.1 bf16[1,128,64]"] * 5)
    env = _env(sound, steps=[None] * steps, events=events)
    (f, fb), (b, bb) = cost.call_costs(2, 64, 4, 4, 2, 16)
    assert cost.window_cost(env) == (steps * layers * (f + b),
                                     steps * layers * (fb + bb))
    share = kernel_roofline.read(env, "flash_blockdiff",
                                 "flash_attention_blockdiff")
    assert 0 < share < 100
    # the accepted patterns count none of these kernels
    from chipbench import trace
    assert trace.kernel_seconds(events, "flash_attention") \
        == pytest.approx(5e-3)
    assert trace.kernel_seconds(events, "flash_window") == 0
    # a program without the kernels (the parent): nothing, and no error
    assert kernel_roofline.read(
        _env(sound, steps=[None] * steps,
             events=one_after_another(["fusion.9 bf16[2,64,64]"] * 7)),
        "flash_blockdiff", "flash_attention_blockdiff") is None


def test_mfu_active_counts_two_positions_a_token_and_half_a_head(sound):
    from chipbench.readers import mfu_active_sdar

    m = runner.model_group(TINY)
    attn = 2 * 64 * 64 + 2 * 64 * 32 + 2 * 16
    per_layer = lambda a: attn + 64 * 16 + a * 3 * 64 * 32 + 2 * 64  # noqa
    assert mfu_active_sdar.active_params(m, 2.0) \
        == 2 * per_layer(2.0) + 0.5 * (64 + 64 * 512)
    counts = sound[1]["counters"]["moe_tokens_per_expert"]
    served = sum(sum(sum(layer) for layer in step) for step in counts)
    per_position = served / (len(counts) * 2 * 256)
    got = mfu_active_sdar.read(_env(sound, rate=1000.0),
                               "train_tokens_per_s_per_chip")
    assert got == pytest.approx(
        100 * 6 * mfu_active_sdar.active_params(m, per_position) * 2 * 1000.0
        / 197e12)
    # the cell, a position served by ONE held expert on average: 18.9M of
    # projections, 0.26M of router, 4.72M of expert a layer
    with open(os.path.join(harness.HERE, "configs",
                           "sdar-30b-a3b-chat-train-l6-ep8.json")) as f:
        cell = runner.model_group(json.load(f))
    layer = mfu_active_sdar.active_params(cell, 1.0) \
        - 0.5 * (2048 + 2048 * 18992)
    assert layer == 6 * (18_874_368 + 256 + 262_144 + 4_718_592 + 4096)
    # a program of another family hands over no such key: nothing
    other = _env(sound)
    other.config = {"model": {"hidden_size": 64}}
    assert mfu_active_sdar.read(other, "train_tokens_per_s_per_chip") is None


def test_the_traffic_kind_makes_the_noise_from_seed_and_step():
    from chipbench.traffic_kinds import block_diffusion

    with open(os.path.join(harness.HERE, "traffic",
                           "blockdiff-seq8192-b1-blk4.json")) as f:
        params = json.load(f)
    assert {k: params[k] for k in ("kind", "batch", "seq", "block", "t_low",
                                   "t_high")} == {
        "kind": "block_diffusion", "batch": 1, "seq": 8192, "block": 4,
        "t_low": 0.001, "t_high": 1.0}
    feed = block_diffusion.generate(params, 4000000007, 10, 18992)
    assert feed.tokens_per_step == 8192 and feed.mask_token_id == 18991
    ids, (noised, t) = feed(3)
    again, (noised2, t2) = feed(3)
    assert (ids == again).all() and (noised == noised2).all() \
        and (t == t2).all()
    other, _ = feed(4)
    assert (ids != other).any()
    assert ids.shape == noised.shape == (1, 8192) and t.shape == (1, 2048)
    assert ids.dtype == noised.dtype == np.int32 and t.dtype == np.float32
    assert ids.max() < 18991                    # the data never draws MASK
    masked = noised == 18991
    assert (noised[~masked] == ids[~masked]).all()
    assert 0.001 <= t.min() and t.max() <= 1.0
    # Bernoulli(t) a token: the masked share follows the block's level
    assert 0.45 < masked.mean() < 0.55
    per_block = masked.reshape(2048, 4).mean(axis=1)
    assert np.corrcoef(per_block, t[0])[0, 1] > 0.7
    with pytest.raises(ValueError, match="whole blocks"):
        block_diffusion.generate({**params, "seq": 8190}, 1, 10, 18992)


def test_new_metric_files_name_what_exists():
    """Each new metric: behind PR 43's in ``per_layer``, a file that names a
    reader that is there, the roofline a cost function that is there; the
    cell on the lists ISSUE 44 names and off ``.moe_shared`` and ``.mlp``."""
    from chipbench.readers import scope_device_ms

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = [m for m in bench["per_layer"] if m["workloads"] == [CELL_NAME]]
    assert [m["name"] for m in new] == [
        "flash_attn_blockdiff_roofline_pct",
        "blockdiff_pairs_scored_over_needed", "device_ms_per_step.qk_norm",
        "blockdiff_masked_share", "mfu_active_pct.sdar"]
    # put last when they came (behind the newest entry of PR 43), in this
    # order; by NAME, so that a later PR's entries behind them fail nothing
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(m["name"]) for m in new]
    assert at == list(range(at[0], at[0] + 5))
    assert at[0] == names.index("device_ms_per_step.mla_expand") + 1
    for m in new:
        with open(os.path.join(harness.HERE, "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(
            f"chipbench.readers.{spec['reader']}")
        assert callable(reader.read)
        if "cost" in spec["args"]:
            assert callable(importlib.import_module(
                f"chipbench.kernel_costs.{spec['args']['cost']}").window_cost)
    joined = {m["name"] for m in bench["per_layer"]
              if CELL_NAME in m["workloads"]}
    assert not {"device_ms_per_step.moe_shared", "device_ms_per_step.mlp",
                "flash_attn_gqa_roofline_pct", "mfu_active_pct"} & joined
    assert {"device_ms_per_step.moe_router", "device_ms_per_step.moe_dispatch",
            "device_ms_per_step.moe_experts", "moe_expert_load_max_over_mean",
            "moe_grouped_matmul_roofline_pct", "device_ms_per_step.attention",
            "device_ms_per_step.recompute", "peak_hbm_gb.train",
            "compiled_hbm_gb.train", "compiles_in_window.train"} <= joined
    rate = [m for m in bench["end_to_end"]
            if m["name"] == "train_tokens_per_s_per_chip"][0]
    assert rate["workloads"][-1] == CELL_NAME
    assert bench["workloads"][-1] == {
        "name": CELL_NAME, "config": "sdar-30b-a3b-chat-train-l6-ep8",
        "traffic": "blockdiff-seq8192-b1-blk4", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    assert len(bench["workloads"][-1]["why"]) <= 200

    # the marks as the program writes them: qk_norm inside attn
    claimed = scope_device_ms.claimed_segments()
    fwd = "jit(train_step)/jvp(SdarForBlockDiffusion)/model/layers.2/"
    path = fwd + "attn/attn_blockdiff/qk_norm/mul"
    assert scope_device_ms.part_of(path, claimed) == "attn"
    own = {("fusion.1", path): 1.0,
           ("fusion.2", fwd + "attn/attn_blockdiff/q_proj/dot_general"): 2.0}
    assert scope_device_ms.selected_seconds(own, claimed,
                                            phase="qk_norm") == 1.0
    assert scope_device_ms.selected_seconds(own, claimed,
                                            scope=["attn"]) == 3.0


def test_the_cell_s_configuration_says_what_it_holds():
    """645,623,296 parameters held, counted from the sizes in the file;
    every number of the source's config under its own key unless
    ``reduced``."""
    with open(os.path.join(harness.HERE, "configs",
                           "sdar-30b-a3b-chat-train-l6-ep8.json")) as f:
        cfg = json.load(f)
    m = runner.model_group(cfg)
    h, d = m["hidden_size"], m["head_dim"]
    attn = 2 * h * m["num_attention_heads"] * d \
        + 2 * h * m["num_key_value_heads"] * d + 2 * d
    layer = attn + 2 * h + h * m["router_experts"] \
        + m["num_experts"] * 3 * h * m["moe_intermediate_size"]
    total = m["num_hidden_layers"] * layer + 2 * m["vocab_size"] * h + h
    assert layer == 94_638_336 and total == 645_623_296
    assert "645,623,296" in cfg["parameters"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (6, 16, 18992)
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert cfg["deployment"]["router_experts"] == 128
    assert (cfg["block_length"], cfg["mask_token_id"]) == (4, 18991)
    # how the seeded weights depart from the other families', with reasons
    assert (cfg["embedding_range"], cfg["mask_row"], cfg["qk_norm_gain"]) \
        == (1.0, "mean", 1.6)
    assert all(m[k] == cfg[k] for k in ("embedding_range", "mask_row",
                                        "qk_norm_gain"))
    for key in ("embedding_range", "mask_row", "qk_norm_gain"):
        assert key in cfg["assumed"]["weights"], key
    for key in ("block_length", "noise", "loss", "label_shift", "qk_norm",
                "mask_token_id", "router_score", "rotary", "optimizer",
                "weights", "sequence"):
        assert cfg["assumed"][key], key
    assert cfg["trainer"]["remat"] == ["flash_attention_out",
                                       "flash_attention_lse"]
    assert set(cfg["limits"]) == {
        "loss_gap", "first_grad_norm_gap", "param_change_norm_gap",
        "param_change_direction_gap", "expert_count_gap",
        "masked_loss_terms_gap", "loss_rise"}
    assert cfg["limits"]["masked_loss_terms_gap"] == 0
    for key in ("learning_rate_why", "precision_stated", "limits_why",
                "memory", "trainer_why", "check_steps_why", "stands_for"):
        assert len(cfg[key]) > 40, key
