"""The hybrid decoder whose blocks hold ONE module each
(``paddle_tpu/models/nemotron_h.py``) on the CPU at small widths, in the
published pattern's first eleven letters ``MEMEMEM*EME``:

- the model's loss and the gradient of every parameter against the plain
  reference (``chipbench/reference/nemotron_h.py``: the recurrence run
  position by position, the experts as a dense loop) on seeded weights;
- the share test: what all the shares of one expert layer give, with the
  latent projections, the router and the shared expert counted once, adds
  up to the uncut reference's layer;
- the ``relu2`` body's hand-written backward (``dropless.routed_experts``)
  against autodiff of a dense composition;
- the shell's block of one branch, the step's counters and scopes, and
  the parts ``amp.decorate`` O2 -> ``TrainStep(remat=...)`` trains.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.distributed.models.moe import (DroplessMoELayer,
                                                        dropless)
from paddle_tpu.jit import TrainStep, functional_call
from paddle_tpu.models.nemotron_h import (GroupedAttention, Mamba2Mixer,
                                          NemotronHConfig,
                                          NemotronHForCausalLM, dt_bias_init,
                                          nemotron_h_tiny)

from chipbench.reference import nemotron_h as ref
from chipbench.runners import laguna_train as by_group
from chipbench.runners import nemotron_h_train as runner

PATTERN = "MEMEMEM*EME"
# the source's keys at a toy size; activations of order one (0.02 *
# sqrt(4096) at the published width), so that the experts' squares, the
# decays and the routers' gradients are no rounding noise
SMALL = {
    "hidden_size": 32, "num_hidden_layers": 11,
    "hybrid_override_pattern": PATTERN, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 8,
    "conv_kernel": 4, "chunk_size": 8, "use_conv_bias": True,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "n_routed_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 16, "moe_latent_size": 8,
    "moe_shared_expert_intermediate_size": 24, "routed_scaling_factor": 5.0,
    "norm_topk_prob": True, "mlp_hidden_act": "relu2",
    "layer_norm_epsilon": 1e-5, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4, "vocab_size": 64,
    "initializer_range": 0.2,
    "deployment": {"router_experts": 12, "expert_offset": 4}}


def _both_sides(small, seq):
    """The model in float32 holding the reference's seeded weights, a
    batch, and both sides' loss and gradients under the highest matmul
    precision."""
    m = runner.model_group(small)
    model = NemotronHForCausalLM(runner.model_config(m))
    tree = ref.init_params(7, m, jnp.float32)
    runner.load_seeded(model, tree, ref, m)
    ids = np.random.default_rng(0).integers(0, 64, (2, seq)).astype(np.int32)
    params = {k: v._data for k, v in model.state_dict().items()}

    def program_loss(p):
        logits = functional_call(model, p, paddle.to_tensor(ids))
        return model.loss(logits, paddle.to_tensor(ids))._data

    def reference_loss(p):
        total = sum(ref._row_loss_sum(p, jnp.asarray(row), jnp.asarray(row),
                                      m, "float32")[0] for row in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(program_loss)(params)
        want = jax.value_and_grad(reference_loss)(tree)
    return m, model, got, want


@pytest.fixture(scope="module")
def seeded():
    return _both_sides(SMALL, 24)


def test_loss_is_the_reference_s(seeded):
    """float32 on both sides: the two differ by the order of their sums
    (the chunked scan against the recurrence, the sorted dispatch against
    the dense loop): 2e-6 of a loss near ln 64."""
    _, _, (loss, _), (want, _) = seeded
    assert float(loss) == pytest.approx(float(want), rel=2e-6)


def _assert_gradients_are_the_reference_s(m, model, grads, want):
    want = ref.keyed(ref.split_layers(want, m), m)
    group_of = functools.partial(ref.group_of, m)
    compared = 0
    for name, g in grads.items():
        if name.endswith("e_score_correction_bias"):
            continue        # a buffer: no gradient
        group, leaf, layer = by_group.program_key(name, group_of)
        w = want[(f"{group}.{leaf}", layer)]
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        np.testing.assert_allclose(g, w, rtol=0, atol=5e-5 * scale,
                                   err_msg=name)
        compared += 1
    assert compared == len(model.parameters())


def test_every_parameter_s_gradient_is_the_reference_s(seeded):
    """Each leaf's gradient to 5e-5 of that leaf's largest entry (float32
    sums in another order; the worst read here is ``dt_bias``'s at 5e-6)."""
    m, model, (_, grads), (_, want) = seeded
    _assert_gradients_are_the_reference_s(m, model, grads, want)


def test_the_convolution_s_kernels_give_the_reference_s_loss_and_gradients(
        monkeypatch):
    """The mixers' convolutions through the ``causal_conv_*`` kernels
    (interpret mode) at widths they take: x 16 * 8 = 128 channels, B and C
    2 * 64 = 128 each, read where they lie in the projection's ``[z | x | B
    | C | dt]`` over rows of 32: the reference's loss and every parameter's
    gradient, at the tolerances the composition is held to above."""
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import causal_conv_kernel as ck

    served = []

    def through_the_kernels(x, weight, bias=None, start=0):
        served.append((tuple(x.shape), weight.shape[0], start))
        return ck.causal_conv_pallas(x, weight, bias, start=start,
                                     interpret=True,
                                     block=(16, 128, 16, 128))

    monkeypatch.setattr(pk, "causal_conv1d", through_the_kernels)
    small = dict(SMALL, mamba_num_heads=16, ssm_state_size=64)
    m, model, (loss, grads), (want_loss, want) = _both_sides(small, 32)
    wide = (2, 32, 128 + 384 + 16)
    assert served == [(wide, 128, 128), (wide, 128, 256),
                      (wide, 128, 384)] * PATTERN.count("M")
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    _assert_gradients_are_the_reference_s(m, model, grads, want)


def test_the_gated_norm_s_kernels_give_the_composition_s_mixer(monkeypatch):
    """One mixer's forward and backward with its gated norm through the
    ``gated_norm_*`` kernels (interpret mode) at a width they take: 32 heads
    of 8 = 256 lanes over 2 groups of one tile each, the gate read where it
    lies, the first 256 lanes of the projection's ``[z | x | B | C | dt]``,
    over two batch rows of 16: the composition's output and the gradient of
    the input and of every parameter, to float32's orders of summation."""
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import gated_norm_kernel as gk

    paddle.seed(3)
    mixer = Mamba2Mixer(NemotronHConfig(
        hidden_size=32, num_hidden_layers=1, hybrid_override_pattern="M",
        mamba_num_heads=32, mamba_head_dim=8, n_groups=2, ssm_state_size=8,
        chunk_size=8, initializer_range=0.2))
    r = np.random.default_rng(3)
    params = {k: v._data for k, v in mixer.state_dict().items()}
    params["norm_weight"] = jnp.asarray(
        1.0 + 0.2 * r.standard_normal(256), jnp.float32)
    a, co = (jnp.asarray(r.standard_normal((2, 16, 32)), jnp.float32)
             for _ in range(2))

    def loss(p, a):
        out = functional_call(mixer, p, a)
        return jnp.sum(out * co), out

    both = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = both(params, a)
        served = []

        def through_the_kernels(y, z, weight, groups, epsilon, start=0):
            served.append((tuple(y.shape), tuple(z.shape), groups, start))
            return gk.gated_norm_pallas(
                y, z, weight, groups=groups, epsilon=epsilon, start=start,
                interpret=True, block=(16, 16))

        monkeypatch.setattr(pk, "gated_rms_norm", through_the_kernels)
        (_, got), got_grads = both(params, a)
    assert served == [((2, 16, 256), (2, 16, 256 + 288 + 32), 2, 0)]
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))
    flat = dict(got_grads[0], input=got_grads[1])
    for name, w in dict(want_grads[0], input=want_grads[1]).items():
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        np.testing.assert_allclose(flat[name], w, rtol=0, atol=2e-5 * scale,
                                   err_msg=name)


def test_the_reference_s_gradient_by_blocks_is_its_gradient_whole(seeded):
    """``row_loss_and_grad`` (the chain rule by hand over ``jax.vjp`` of
    each block, what the chip's comparison follows so that float32 at the
    published sizes fits) against ``jax.grad`` of the same forward pass as
    one graph: the same sums, in another order at the blocks' edges and in
    the embedding's scatter-add (3e-6 read there); 1e-5 of each leaf's
    largest entry."""
    m, _, _, _ = seeded
    tree = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  ref.init_params(7, m, jnp.float32))
    row = jnp.asarray(np.random.default_rng(2).integers(0, 64, 24),
                      jnp.int32)
    split = ref.init_split(7, m, jnp.float32)    # made a layer at a time
    for a, b in zip(jax.tree_util.tree_leaves(split),
                    jax.tree_util.tree_leaves(ref.split_layers(tree, m))):
        np.testing.assert_array_equal(a, b)
    with jax.default_matmul_precision("highest"):
        (loss, counts), got = ref.row_loss_and_grad(split, row, row, m)
        (want_loss, want_counts), want = jax.value_and_grad(
            ref._row_loss_sum, has_aux=True)(tree, row, row, m, "float32")
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    np.testing.assert_array_equal(counts, want_counts)
    got, want = ref.keyed(got, m), ref.keyed(ref.split_layers(want, m), m)
    assert set(got) == set(want)
    for key, w in want.items():
        np.testing.assert_allclose(
            got[key], w, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(w))),
            err_msg=str(key))


def test_the_model_is_the_pattern_of_one_branch_blocks(seeded):
    _, model, _, _ = seeded
    kinds = {"M": Mamba2Mixer, "*": GroupedAttention, "E": DroplessMoELayer}
    for letter, layer in zip(PATTERN, model.model.layers):
        held = [n for n in ("mamba", "attn", "moe", "mlp")
                if getattr(layer, n, None) is not None]
        assert len(held) == 1 and layer.ln_2 is None, held
        assert isinstance(getattr(layer, held[0]), kinds[letter])
    names = set(model.state_dict())
    assert "model.layers.0.mamba.A_log" in names
    assert "model.layers.7.attn.q_proj.weight" in names
    assert "model.layers.1.moe.latent_down.weight" in names
    assert "model.layers.1.moe.experts.up" in names
    assert not any(".ln_2." in n or "gate_up" in n for n in names)


def test_a_pattern_of_the_wrong_length_or_letters_is_refused():
    with pytest.raises(ValueError, match="letters for"):
        NemotronHConfig(num_hidden_layers=4)
    with pytest.raises(ValueError, match="pattern letters"):
        NemotronHConfig(num_hidden_layers=2, hybrid_override_pattern="M-")


def test_residual_projections_reckon_one_branch_a_block():
    """``out_std`` is the range over sqrt(L), not sqrt(2 L): a block makes
    one residual add."""
    c = NemotronHConfig()
    assert c.out_std == pytest.approx(0.02 / np.sqrt(11))
    low, high = 0.001, 0.1
    dt = jax.nn.softplus(jnp.asarray(dt_bias_init(64, low, high, 1e-4)))
    assert float(dt.min()) >= low * 0.999 and float(dt.max()) <= high * 1.001


# ---- the shares of one expert layer ------------------------------------
def _expert_layer(m, tree_layer, held, offset):
    layer = DroplessMoELayer(
        m["hidden_size"], m["moe_intermediate_size"], m["router_experts"],
        m["num_experts_per_tok"], 1, m["routed_scaling_factor"], True,
        num_local_experts=held, expert_offset=offset, body="relu2",
        d_latent=m["moe_latent_size"],
        d_shared=m["moe_shared_expert_intermediate_size"])
    sd = {n: Tensor(tree_layer["moe." + n]) for n in layer.state_dict()
          if not n.endswith("e_score_correction_bias")}
    layer.set_state_dict(sd)
    return layer


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """A router of 12, four experts a share: the three shares' routed
    parts, each already through ``latent_up`` (a linear map, so the sum of
    the parts is the part of the sum), plus the shared expert ONCE, equal
    the uncut reference's layer.  float32; 1e-5 of the output's scale
    (sums in another order)."""
    m = runner.model_group({**SMALL, "n_routed_experts": 12,
                            "deployment": {"router_experts": 12}})
    key = ref.seed_key(3)
    whole = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        ref.layer_params(key, 1, "moe", m, jnp.float32))
    a = jnp.asarray(np.random.default_rng(1).standard_normal((40, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, counts = ref.experts(a, whole, m)
        shared = ref._relu2_mlp(a, whole["moe.shared_experts.up.weight"],
                                whole["moe.shared_experts.down.weight"],
                                "float32")
        total, served = shared, []
        for offset in (0, 4, 8):
            share_cfg = {**m, "n_routed_experts": 4, "expert_offset": offset}
            mine = ref.layer_params(key, 1, "moe", share_cfg, jnp.float32)
            np.testing.assert_array_equal(      # a slice of the uncut layer
                mine["moe.experts.up"],
                whole["moe.experts.up"][offset:offset + 4])
            layer = _expert_layer(m, mine, 4, offset)
            out = layer(Tensor(a[None]))._data[0]
            total = total + (out - shared)
            served.append(np.asarray(layer.tokens_per_expert))
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(total, want, rtol=0, atol=1e-5 * scale)
    np.testing.assert_array_equal(np.concatenate(served), counts)
    assert int(counts.sum()) == 40 * m["num_experts_per_tok"]


# ---- the relu2 body's hand backward ------------------------------------
@pytest.mark.parametrize("held,offset", [(6, 0), (2, 3)])
def test_relu2_routed_block_backward_is_autodiff_s(held, offset):
    """``dropless.routed_experts(..., body="relu2")`` (one ``custom_vjp``:
    the experts' intermediates rebuilt, the grouped matmuls' transposes
    called by name) against ``jax.grad`` of a dense loop over the experts;
    rows ``K`` = 8 wide, narrower than anything the router reads.  float32
    sums in another order (a ragged matmul over sorted rows against a
    dense one over all), over 192 outputs: 5e-5 of the value and of each
    gradient's scale."""
    r = np.random.default_rng(held)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa: E731
    tokens, k, experts, width, inner = 24, 3, 6, 8, 16
    x, w_in, w_out = f(tokens, width), f(held, width, inner) * 0.3, \
        f(held, inner, width) * 0.3
    idx = jnp.asarray(np.stack([r.permutation(experts)[:k]
                                for _ in range(tokens)]), jnp.int32)
    weights = jnp.abs(f(tokens, k)) + 0.1
    buckets = dropless.row_buckets(tokens, k, held, experts)

    def block(x, weights, w_in, w_out):
        return jnp.sum(jnp.sin(dropless.routed_experts(
            x, weights, w_in, w_out, idx, offset, buckets, "relu2")))

    def dense(x, weights, w_in, w_out):
        out = 0.0
        for e in range(held):
            w_e = jnp.sum(jnp.where(idx == e + offset, weights, 0.0), axis=1)
            out = out + w_e[:, None] * (
                jnp.square(jax.nn.relu(x @ w_in[e])) @ w_out[e])
        return jnp.sum(jnp.sin(out))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(block, argnums=(0, 1, 2, 3))(
            x, weights, w_in, w_out)
        want = jax.value_and_grad(dense, argnums=(0, 1, 2, 3))(
            x, weights, w_in, w_out)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=5e-5)
    for name, a, b in zip(("x", "weights", "w_in", "w_out"), got[1],
                          want[1]):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=0, atol=5e-5 * scale,
                                   err_msg=name)


def test_a_body_nobody_defined_is_refused():
    with pytest.raises(KeyError):
        DroplessMoELayer(16, 8, 4, 2, body="gelu")


def test_the_gated_body_keeps_its_names_and_its_width():
    """The families before this one: ``gate_up [G, H, 2I]`` and ``down``,
    no latent projections, the shared expert ``num_shared * d_expert``
    wide."""
    layer = DroplessMoELayer(16, 8, 4, 2, num_shared_experts=2)
    names = set(layer.state_dict())
    assert {"experts.gate_up", "experts.down",
            "shared_experts.gate_up.weight"} <= names
    assert not any("latent" in n or n == "experts.up" for n in names)
    assert tuple(layer.experts.gate_up.shape) == (4, 16, 16)
    assert tuple(layer.shared_experts.down.weight.shape) == (16, 16)


@pytest.mark.parametrize("headroom", [None, 3])
def test_the_small_bucket_follows_the_layer_s_headroom(headroom):
    """``bucket_headroom`` is the layer's attribute (2): a trainer whose
    router is not balanced sets it before the first step, and the rows the
    buffers take follow it; what the layer computes does not."""
    paddle.seed(0)
    layer = DroplessMoELayer(16, 8, 16, 2, num_local_experts=4, body="relu2")
    if headroom is not None:
        layer.bucket_headroom = headroom
    x = Tensor(jnp.asarray(np.random.default_rng(0).standard_normal(
        (1, 1024, 16)), jnp.float32))
    out = np.asarray(layer(x)._data)
    buckets = dropless.row_buckets(1024, 2, 4, 16, headroom or 2)
    assert buckets == ((headroom or 2) * dropless.ROW_TILE, 2048)
    assert int(layer.rows_buffered) == buckets[0]
    assert int(np.sum(np.asarray(layer.tokens_per_expert))) <= buckets[0]
    layer.bucket_headroom = 2
    np.testing.assert_array_equal(out, np.asarray(layer(x)._data))


# ---- the step ----------------------------------------------------------
@pytest.fixture(scope="module")
def step_and_names():
    paddle.seed(0)
    model = paddle.amp.decorate(
        nemotron_h_tiny(num_local_experts=4, expert_offset=2), level="O2",
        dtype="bfloat16")
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                     remat=["flash_attention_out", "flash_attention_lse"])
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 512, (2, 64)).astype(np.int32))
    names = []
    for name in re.findall(r'op_name="([^"]*)"',
                           step.lower(ids, ids).compile().as_text()):
        names.append((name, [re.sub(r"^(?:[\w-]+\()+|\)+$", "", s)
                             for s in name.split("/")]))
    return model, step, ids, names


def test_the_step_trains_and_counts_every_expert_layer(step_and_names):
    model, step, ids, _ = step_and_names
    kept = {n for n, p in model.state_dict().items()
            if p.dtype == jnp.float32}
    assert kept and all(n.rsplit(".", 1)[1] in (
        "A_log", "D", "dt_bias", "e_score_correction_bias") or
        n.endswith(("A_log", "D", "dt_bias")) for n in kept), kept
    assert sum(p.dtype == jnp.float32 for p in model.parameters()) == 15
    assert all(p.dtype in (jnp.bfloat16, jnp.float32)
               for p in model.parameters())
    losses = [float(step(ids, ids)._data) for _ in range(6)]
    assert losses[-1] < losses[0] and np.all(np.isfinite(losses))
    counts = np.asarray(step.counters["moe_tokens_per_expert"])
    assert counts.shape == (5, 4) and counts.dtype == np.int32
    assert np.asarray(step.counters["moe_rows_buffered"]).shape == (5,)
    assert (counts.sum(axis=1) <= 2 * 64 * 4).all() and counts.sum() > 0


@pytest.mark.parametrize("inner,outer,layers", [
    ("in_proj", "mamba", "MAMBA"), ("mamba_conv", "mamba", "MAMBA"),
    ("mamba_ssd", "mamba", "MAMBA"), ("gated_norm", "mamba", "MAMBA"),
    ("out_proj", "mamba", "MAMBA"), ("q_proj", "attn", "ATTN"),
    ("latent_down", "moe", "MOE"), ("latent_up", "moe", "MOE"),
    ("router", "moe", "MOE"), ("experts", "moe", "MOE"),
    ("shared_experts", "moe", "MOE")])
def test_a_block_s_ops_are_scoped_by_its_one_module(step_and_names, inner,
                                                    outer, layers):
    """``layers.i`` / ``mamba`` | ``attn`` | ``moe`` round the block's one
    module and the parts inside it, forward and backward, in the layers
    the pattern gives that kind and in no other."""
    names = step_and_names[3]
    letter = {"MAMBA": "M", "ATTN": "*", "MOE": "E"}[layers]
    want = {f"layers.{i}" for i, c in enumerate(PATTERN) if c == letter}
    hits = [(n, segs) for n, segs in names
            if inner in segs and outer in segs]
    assert hits, f"no op is scoped {outer}/{inner}"
    for n, segs in hits:
        assert segs.index(outer) < segs.index(inner), n
    assert {s for _, segs in hits for s in segs
            if s.startswith("layers.")} == want
    assert any("transpose(" in n for n, _ in hits)
    assert any("transpose(" not in n for n, _ in hits)


def test_every_block_has_its_one_norm_and_no_second(step_and_names):
    names = step_and_names[3]
    normed = {s for _, segs in names if "ln_1" in segs for s in segs
              if s.startswith("layers.")}
    assert normed == {f"layers.{i}" for i in range(11)}
    assert not any("ln_2" in segs or "mlp" in segs for _, segs in names)
    recomputed = {s for n, segs in names if "rematted_computation" in n
                  for s in segs}
    assert {"mamba", "moe", "attn"} <= recomputed
    assert not {"lm_head", "loss", "embeddings"} & recomputed
