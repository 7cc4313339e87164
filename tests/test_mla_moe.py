"""Latent attention with routed and shared experts (``models/mla_moe.py``,
``incubate/distributed/models/moe``), at a size the CPU runs, on seeded
weights:

- the model against the plain reference ``chipbench/reference/mla_moe.py``:
  logits, loss, every leaf's gradient, the counters;
- the dropless dispatch against a loop over experts under a routing that
  sends most tokens to one expert: nothing dropped;
- the SHARE test: the routed parts the eight shares of a layer give, plus
  the shared expert counted once, are what the uncut layer gives;
- ``jit.TrainStep`` hands the model's counters back beside the loss;
- the attention dispatch at q/k 192 over v 128.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.incubate.distributed.models.moe import (DroplessMoELayer,
                                                        MoELayer, dropless)
from paddle_tpu.jit import TrainStep, functional_call
from paddle_tpu.models.mla_moe import (MlaMoeConfig, MlaMoeForCausalLM,
                                       apply_rope, mla_moe_tiny, rope_tables)

from chipbench.reference import mla_moe as ref
from chipbench.runners import mla_moe_train as runner

# 16 router outputs, three a token; one dense layer, two expert layers
BASE = dict(hidden_size=64, num_attention_heads=4, num_hidden_layers=3,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=32, q_lora_rank=None, intermediate_size=128,
            moe_intermediate_size=32, n_shared_experts=2,
            num_experts_per_tok=3, first_k_dense_replace=1,
            routed_scaling_factor=2.448, norm_topk_prob=True,
            rms_norm_eps=1e-6, rope_theta=1e6, rope_interleave=True,
            vocab_size=96, max_position_embeddings=64)
SHARES = {"uncut": dict(n_routed_experts=16, deployment={}),
          "share-4-of-16-from-4": dict(
              n_routed_experts=4,
              deployment={"router_experts": 16, "expert_offset": 4})}


def _seeded(share, seed=7):
    """(model group, program model holding the reference's seeded float32
    weights, the reference's tree)."""
    m = runner.model_group({**BASE, **SHARES[share]})
    paddle.seed(0)
    model = MlaMoeForCausalLM(runner.model_config(m))
    tree = ref.init_params(seed, m, jnp.float32)
    runner.load_seeded(model, tree, m["first_k_dense_replace"])
    return m, model, tree


def _ids(seed=0, rows=2, seq=32):
    return np.random.RandomState(seed).randint(
        0, BASE["vocab_size"], (rows, seq)).astype("int32")


@pytest.mark.parametrize("share", list(SHARES))
def test_logits_and_counters_match_the_reference(share):
    m, model, tree = _seeded(share)
    ids = _ids()
    got = model(paddle.to_tensor(ids))._data
    with jax.default_matmul_precision("highest"):
        rows = [ref.forward_row(tree, jnp.asarray(r), m) for r in ids]
    want = jnp.stack([r[0] for r in rows])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-6)
    counts = np.asarray(model.step_counters()["moe_tokens_per_expert"])
    np.testing.assert_array_equal(counts, sum(np.asarray(r[1])
                                              for r in rows))
    assert counts.shape == (2, m["n_routed_experts"])


def _force_expand_kernels(monkeypatch):
    """``MLAttention``'s expansion through the ``mla_expand_*`` kernels, in
    interpret mode, where the CPU's dispatcher takes the composition."""
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas.mla_expand_kernel import mla_expand_pallas

    monkeypatch.setattr(pk, "mla_expand_qkv", lambda *a, **kw:
                        mla_expand_pallas(*a, **kw, interpret=True))


@pytest.fixture(params=["composition", "kernels"])
def expand(request, monkeypatch):
    """The expansion by the path the CPU takes (the XLA composition) or
    with the kernels forced."""
    if request.param == "kernels":
        _force_expand_kernels(monkeypatch)
    return request.param


@pytest.mark.parametrize("share", list(SHARES))
def test_loss_and_every_leafs_gradient_match_the_reference(share, expand):
    m, model, tree = _seeded(share)
    ids = _ids(1)
    state = {n: t._data for n, t in model.state_dict().items()}
    params = {n: a for n, a in state.items() if not n.endswith("_bias")}
    frozen = {n: a for n, a in state.items() if n.endswith("_bias")}

    def loss_f(p):
        out = functional_call(model, {**p, **frozen}, jnp.asarray(ids))
        return model.loss(Tensor(out), Tensor(jnp.asarray(ids)))._data

    def ref_loss(p):
        total = sum(ref._row_loss_sum(p, jnp.asarray(r), jnp.asarray(r), m,
                                      "float32")[0] for r in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    loss, grads = jax.value_and_grad(loss_f)(params)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(ref_loss)(tree)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    first_k = m["first_k_dense_replace"]
    assert len(grads) == len(params) > 30
    for name, g in grads.items():
        group, leaf, layer = runner.program_key(name, first_k)
        w = want[group][leaf]
        if layer is not None:
            w = w[layer if group == "dense" else layer - first_k]
        gap = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert gap < 1e-4, (name, gap)


def test_remat_by_block_changes_no_value_and_recomputes_each_layer():
    ids = paddle.to_tensor(_ids(2))
    losses, recomputed = [], []
    for remat in (False, True, ["flash_attention_out"]):
        _, model, _ = _seeded("share-4-of-16-from-4")
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                         remat=remat)
        recomputed.append(step.lower(ids, ids).compile().as_text().count(
            "rematted_computation/layers."))
        losses.append([float(step(ids, ids)._data) for _ in range(3)])
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
    np.testing.assert_allclose(losses[0], losses[2], rtol=1e-5)
    assert losses[0][2] < losses[0][0]
    assert recomputed[0] == 0 and recomputed[1] > 0 and recomputed[2] > 0


def test_the_expand_kernels_change_no_value_of_the_rematerialised_step(
        monkeypatch):
    """Three steps of ``TrainStep(remat=[...])`` with the ``mla_expand_*``
    kernels (interpret mode; their backward needs no saved input, so the
    rematerialised forward and the backward each call one) against the
    composition's: the float32 losses agree, and a kernel call is in the
    recomputed layers."""
    ids = paddle.to_tensor(_ids(2))
    losses, calls = [], []
    for kernels in (False, True):
        if kernels:
            _force_expand_kernels(monkeypatch)
        _, model, _ = _seeded("share-4-of-16-from-4")
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                         remat=["flash_attention_out"])
        calls.append(str(step.lower(ids, ids).as_text(debug_info=True))
                     .count("rematted_computation/layers.0/attn/mla_expand/"
                            "mla_expand_fwd"))
        losses.append([float(step(ids, ids)._data) for _ in range(3)])
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
    assert losses[1][2] < losses[1][0]
    assert calls[0] == 0 and calls[1] > 0


@pytest.mark.parametrize("share", ["share-4-of-16-from-4", "uncut"])
def test_the_run_sum_kernel_changes_no_value_of_the_rematerialised_step(
        share, monkeypatch):
    """Three steps of ``TrainStep(remat=[...])`` with the ``moe_run_sum``
    kernel in the dispatcher's place (interpret mode, a row block of 16 over
    the tiny model's whole width) against ``dropless._run_sums``: the float32
    losses agree, and the kernel is called in every traced branch of the
    routed block (combine with the weights, the dispatch's transpose
    without), in the buckets of a cut layer and in the one bucket of an
    uncut one."""
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas.moe_run_sum_kernel import moe_run_sum_pallas

    ids = paddle.to_tensor(_ids(2))
    losses, calls = [], []

    def kernel(rows, rem, weights=None, *, max_run):
        calls.append((rows.shape, weights is not None))
        return moe_run_sum_pallas(rows, rem, weights, max_run=max_run,
                                  interpret=True, block=(16, rows.shape[1]))

    for kernels in (False, True):
        if kernels:
            monkeypatch.setattr(pk, "moe_run_sum", kernel)
        _, model, _ = _seeded(share)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                         remat=["flash_attention_out"])
        losses.append([float(step(ids, ids)._data) for _ in range(3)])
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
    assert losses[1][2] < losses[1][0]
    # combine is traced in the forward and in the rematerialised forward,
    # the dispatch's transpose once
    weighted = sum(w for _, w in calls)
    assert weighted == 2 * (len(calls) - weighted) > 0


def test_train_step_hands_back_the_counters_beside_the_loss():
    _, model, _ = _seeded("share-4-of-16-from-4")
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                     remat=True)
    assert step.counters == {}
    ids = _ids(3)
    step(paddle.to_tensor(ids), paddle.to_tensor(ids))
    counts = step.counters["moe_tokens_per_expert"]
    assert isinstance(counts, jax.Array) and counts.dtype == jnp.int32
    assert counts.shape == (2, 4)
    # a share serves part of the 3 assignments a token makes, never more
    served = np.asarray(counts).sum(axis=1)
    assert ((served > 0) & (served <= ids.size * 3)).all()
    # 64 tokens: the worst case's 192 rows are the only bucket
    rows = step.counters["moe_rows_buffered"]
    assert rows.dtype == jnp.int32 and rows.tolist() == [192, 192]
    assert step.stats() == {"steps": 1, "compiles": 1,
                            "long_steps": 0}


def test_a_model_without_counters_returns_none():
    from paddle_tpu.models.gpt import gpt_tiny

    model = gpt_tiny(num_layers=1)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt)
    ids = paddle.to_tensor(_ids(4, seq=16))
    loss = step(ids, ids)
    assert step.counters == {} and np.isfinite(float(loss._data))


def test_eager_backward_reaches_every_parameter():
    model = mla_moe_tiny(num_local_experts=4, expert_offset=2)
    ids = paddle.to_tensor(_ids(5, seq=16) % 64)
    model.loss(model(ids), ids).backward()
    assert all(p.grad is not None for p in model.parameters())


# ------------------------------------------------------------ dispatch ----
def _loop_over_experts(x, idx, w, gate_up, down, offset):
    """Each local expert on the tokens that chose it, one at a time."""
    out = np.zeros(x.shape, np.float64)
    inter = down.shape[1]
    for e in range(gate_up.shape[0]):
        for t, k in zip(*np.nonzero(np.asarray(idx) == e + offset)):
            gu = np.asarray(x[t], np.float64) @ np.asarray(gate_up[e],
                                                           np.float64)
            g, u = gu[:inter], gu[inter:]
            h = g / (1.0 + np.exp(-g)) * u
            out[t] += float(w[t, k]) * (h @ np.asarray(down[e], np.float64))
    return out


def _skewed_routing(s, e, k, hot, seed):
    """Most tokens choose expert ``hot``; the rest of their k distinct."""
    rng = np.random.RandomState(seed)
    idx = np.stack([rng.permutation(e)[:k] for _ in range(s)])
    for t in range(s):
        if rng.rand() < 0.9 and hot not in idx[t]:
            idx[t, 0] = hot
    return jnp.asarray(idx, jnp.int32), \
        jnp.asarray(rng.rand(s, k) + 0.1, jnp.float32)


@pytest.mark.parametrize("offset,held", [(0, 8), (2, 4), (6, 2)])
def test_dropless_dispatch_against_a_loop_over_experts_skewed(offset, held):
    s, h, inter, e, k = 96, 32, 16, 8, 3
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(s, h), jnp.float32)
    gate_up = jnp.asarray(rng.randn(held, h, 2 * inter) * 0.2, jnp.float32)
    down = jnp.asarray(rng.randn(held, inter, h) * 0.2, jnp.float32)
    idx, w = _skewed_routing(s, e, k, hot=offset, seed=1)

    def routed(x, gate_up, down, w):
        order, inverse, counts = dropless.sort_by_expert(idx, offset, held)
        xs = dropless.dispatch(x, order, inverse, counts)
        ys = dropless.experts_mlp(xs, gate_up, down, counts)
        return dropless.combine(ys, w, order, inverse, counts), counts

    got, counts = routed(x, gate_up, down, w)
    want = _loop_over_experts(x, idx, w, gate_up, down, offset)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    # no token is dropped whatever the imbalance: every assignment to an
    # expert held here is counted and served, the hot expert's too
    local = (np.asarray(idx) >= offset) & (np.asarray(idx) < offset + held)
    assert int(counts.sum()) == int(local.sum())
    assert int(counts[0]) == int((np.asarray(idx) == offset).sum()) > 0.8 * s
    # gradients through the permutation gathers (no scatter): against the
    # plain formulation under jax.grad
    def plain(x, gate_up, down, w):
        out = 0.0
        for j in range(held):
            hit = idx == j + offset
            w_e = jnp.sum(jnp.where(hit, w, 0.0), axis=1)
            gu = x @ gate_up[j]
            out = out + w_e[:, None] * (
                (jax.nn.silu(gu[:, :inter]) * gu[:, inter:]) @ down[j])
        return out

    probe = jnp.asarray(rng.randn(s, h), jnp.float32)
    g1 = jax.grad(lambda *a: jnp.sum(routed(*a)[0] * probe),
                  argnums=(0, 1, 2, 3))(x, gate_up, down, w)
    g2 = jax.grad(lambda *a: jnp.sum(plain(*a) * probe),
                  argnums=(0, 1, 2, 3))(x, gate_up, down, w)
    for a, b, name in zip(g1, g2, ("x", "gate_up", "down", "weights")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4, err_msg=name)


# -------------------------------------------------- the routed block ----
# what each expert cell's layer holds: (tokens, top_k, held, router width)
CELL_LAYERS = {"laguna-s-2.1-train-ep32.seq8192": (8192, 10, 8, 256),
               "kanana-2-30b-a3b-train-ep8.seq8192": (16384, 6, 16, 128)}


@pytest.mark.parametrize("cell,want", [
    ("laguna-s-2.1-train-ep32.seq8192", (5120, 65536)),
    ("kanana-2-30b-a3b-train-ep8.seq8192", (24576, 98304))])
def test_row_buckets_at_the_cells_shapes(cell, want):
    """Twice the rows expected here, then the worst case; every bucket
    whole row tiles, so the grouped matmul keeps its 512-row tile and warns
    no fallback."""
    tokens, top_k, held, experts = CELL_LAYERS[cell]
    assert dropless.row_buckets(tokens, top_k, held, experts) == want
    assert want[-1] == dropless.sorted_rows(tokens, top_k, held)
    assert all(rows % 512 == 0 for rows in want)
    expected = tokens * top_k * held / experts
    assert 2 * expected <= want[0] < 2 * expected + 512


@pytest.mark.parametrize("tokens,top_k,held,experts", [
    (64, 3, 16, 16), (96, 3, 8, 8), (8192, 6, 128, 128), (24, 10, 16, 16)])
def test_nothing_cut_means_one_bucket(tokens, top_k, held, experts):
    """``held == E``: what is expected IS the worst case."""
    assert dropless.row_buckets(tokens, top_k, held, experts) \
        == (dropless.sorted_rows(tokens, top_k, held),)


# 8192 tokens, 4 a token, experts 5 and 6 of 64 held: 1,024 rows expected
BLOCK = dict(s=8192, k=4, e=64, held=2, offset=5, h=16, inter=8)
BLOCK_BUCKETS = (2048, 16384)


def _block_case(hot, seed=0):
    """Seeded operands of the routed block; ``hot`` of the tokens choose
    the first expert held."""
    c = BLOCK
    rng = np.random.RandomState(seed)
    idx = np.argsort(rng.rand(c["s"], c["e"]), axis=1)[:, :c["k"]]
    chosen = (rng.rand(c["s"]) < hot) & ~(idx == c["offset"]).any(axis=1)
    idx[chosen, 0] = c["offset"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return (jnp.asarray(idx, jnp.int32),
            f32(rng.randn(c["s"], c["h"])),
            f32(rng.rand(c["s"], c["k"]) + 0.1),
            f32(rng.randn(c["held"], c["h"], 2 * c["inter"]) * 0.3),
            f32(rng.randn(c["held"], c["inter"], c["h"]) * 0.3),
            f32(rng.randn(c["s"], c["h"])))


def _block_routed(idx, x, w, gate_up, down, buckets=None):
    """The layer's routed block -> (out, counts, rows buffered)."""
    c = BLOCK
    counts = dropless.group_sizes(
        dropless.expert_keys(idx, c["offset"], c["held"]), c["held"])
    if buckets is None:
        buckets = dropless.row_buckets(c["s"], c["k"], c["held"], c["e"])
    out = dropless.routed_experts(x, w, gate_up, down, idx, c["offset"],
                                  buckets)
    return out, counts, \
        jnp.asarray(buckets)[dropless.bucket_of(counts, buckets)]


def _block_parent(idx, x, w, gate_up, down):
    """The three pieces one after the other over the worst case's rows."""
    c = BLOCK
    order, inverse, counts = dropless.sort_by_expert(idx, c["offset"],
                                                     c["held"])
    xs = dropless.dispatch(x, order, inverse, counts)
    ys = dropless.experts_mlp(xs, gate_up, down, counts)
    return dropless.combine(ys, w, order, inverse, counts)


def _block_loop(idx, x, w, gate_up, down):
    c = BLOCK
    out = 0.0
    for j in range(c["held"]):
        w_e = jnp.sum(jnp.where(idx == j + c["offset"], w, 0.0), axis=1)
        gu = x @ gate_up[j]
        out = out + w_e[:, None] * ((jax.nn.silu(gu[:, :c["inter"]])
                                     * gu[:, c["inter"]:]) @ down[j])
    return out


# the share of the tokens sent to ONE held expert -> the bucket they need;
# nine tenths of them reach the worst case, which is the parent's path
@pytest.mark.parametrize("hot,bucket", [(0.0, 2048), (0.08, 2048),
                                        (0.3, 16384), (0.9, 16384)])
def test_routed_block_in_every_bucket(hot, bucket):
    idx, x, w, gate_up, down, probe = _block_case(hot)
    assert dropless.row_buckets(
        BLOCK["s"], BLOCK["k"], BLOCK["held"], BLOCK["e"]) == BLOCK_BUCKETS
    got, counts, rows = jax.jit(_block_routed)(idx, x, w, gate_up, down)
    # the bucket taken is named, holds every row, and is the smallest such
    local = (np.asarray(idx) >= BLOCK["offset"]) \
        & (np.asarray(idx) < BLOCK["offset"] + BLOCK["held"])
    assert int(counts.sum()) == int(local.sum())        # nothing dropped
    assert int(rows) == bucket >= int(counts.sum())
    assert all(b < int(counts.sum()) for b in BLOCK_BUCKETS if b < bucket)
    args = (x, w, gate_up, down)
    want = _block_loop(idx, *args)
    parent = _block_parent(idx, *args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(parent),
                               rtol=1e-5, atol=1e-6)
    grads = [jax.jit(jax.grad(lambda *a: jnp.sum(f(idx, *a) * probe),
                              argnums=(0, 1, 2, 3)))(*args)
             for f in (lambda *a: _block_routed(*a)[0], _block_parent,
                       _block_loop)]
    for a, b, c, name in zip(*grads, ("x", "weights", "gate_up", "down")):
        scale = float(jnp.max(jnp.abs(c)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-3,
                                   atol=2e-5 * scale, err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=2e-6 * scale, err_msg=name)


@pytest.mark.parametrize("rows", (1536, 2048, 4096, 16384))
def test_a_larger_bucket_than_needed_gives_the_same(rows):
    """The rows behind the last group are masked wherever rows go back to
    their tokens: ANY bucket that holds the rows gives the value and the
    gradients of the smallest (every bucket sums by runs since PR 45)."""
    idx, x, w, gate_up, down, probe = _block_case(0.0)

    def f(*a, buckets):
        return jnp.sum(_block_routed(idx, *a, buckets=buckets)[0] * probe)

    args = (x, w, gate_up, down)
    got = jax.jit(jax.value_and_grad(
        lambda *a: f(*a, buckets=(rows,)), argnums=(0, 1, 2, 3)))(*args)
    want = jax.jit(jax.value_and_grad(
        lambda *a: f(*a, buckets=(16384,)),
        argnums=(0, 1, 2, 3)))(*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=2e-6 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("top_k,held,experts", [(8, 8, 8), (6, 16, 16),
                                                (3, 5, 7), (10, 8, 12)])
@pytest.mark.parametrize("weighted", [False, True])
def test_sum_by_runs_is_the_sum_by_slots(top_k, held, experts, weighted):
    """Runs of up to ``min(k, held)`` rows a token (most experts held, so
    the doubling passes all have work), rows behind the last group holding
    NaN: both forms give each token's float32 sum."""
    tokens, h, offset = 40, 8, experts - held
    rng = np.random.RandomState(top_k)
    idx = jnp.asarray(np.argsort(rng.rand(tokens, experts), axis=1)
                      [:, :top_k], jnp.int32)
    order, inverse, counts = dropless.sort_by_expert(idx, offset, held)
    total = int(counts.sum())
    rows = np.full((order.shape[0], h), np.nan, np.float32)
    rows[:total] = rng.randn(total, h)
    rows = jnp.asarray(rows)
    weights = jnp.asarray(rng.rand(tokens, top_k) + 0.1, jnp.float32) \
        if weighted else None
    row_weights = weights.T.reshape(-1)[order] if weighted else None
    token = jnp.where(jnp.arange(order.shape[0]) < total, order % tokens,
                      tokens)
    got = dropless._sum_by_runs(
        rows, token, row_weights,
        jnp.bincount(token, length=tokens + 1)[:tokens], min(top_k, held))
    want = dropless._sum_by_slots(rows, weights, inverse, counts, tokens)
    assert np.isfinite(np.asarray(want)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    # the longest run is as long as it may be, or nearly
    here = np.bincount(np.asarray(order[:total]) % tokens, minlength=tokens)
    assert here.max() >= min(top_k, held) - 1


@pytest.mark.parametrize("num_local,conds", [(16, 0), (4, 2)])
def test_the_switch_is_traced_only_where_the_layer_is_cut(num_local, conds):
    """``held == E``: one bucket, no ``cond`` in the jaxpr, forward or
    backward; a cut layer: one switch each way, whose branches hand back
    arrays at the TOKENS' rows alone."""
    s, k, e, h = 2048, 3, 16, 16
    paddle.seed(0)
    layer = DroplessMoELayer(h, 8, e, k, num_local_experts=num_local,
                             expert_offset=e - num_local)
    buckets = dropless.row_buckets(s, k, num_local, e)
    assert len(buckets) == (1 if num_local == e else 2)
    params = {n: p._data for n, p in layer.named_parameters()}
    frozen = {n: b._data for n, b in layer.named_buffers()}

    def loss(p, x):
        out = functional_call(layer, {**p, **frozen}, x)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    x = jnp.asarray(np.random.RandomState(0).randn(1, s, h), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(loss))(params, x))
    assert text.count(" cond[") == conds
    assert np.isfinite(float(jax.jit(loss)(params, x)))


def test_remat_by_block_changes_no_value_under_row_buckets():
    """1,024 tokens: the two expert layers choose between 1,536 and 3,072
    rows inside the compiled step, with and without rematerialisation."""
    ids = paddle.to_tensor(_ids(6, rows=2, seq=512))
    losses, rows = [], []
    for remat in (False, True):
        m = runner.model_group({**BASE, **SHARES["share-4-of-16-from-4"],
                                "max_position_embeddings": 512})
        paddle.seed(0)
        model = MlaMoeForCausalLM(runner.model_config(m))
        runner.load_seeded(model, ref.init_params(7, m, jnp.float32),
                           m["first_k_dense_replace"])
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = TrainStep(model, lambda lg, lb: model.loss(lg, lb), opt,
                         remat=remat)
        losses.append([float(step(ids, ids)._data) for _ in range(2)])
        rows.append(np.asarray(step.counters["moe_rows_buffered"]))
        served = np.asarray(step.counters["moe_tokens_per_expert"]).sum(1)
        assert rows[-1].shape == (2,) and (rows[-1] >= served).all()
        assert set(rows[-1].tolist()) <= {1536, 3072}
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
    np.testing.assert_array_equal(rows[0], rows[1])
    assert losses[0][1] < losses[0][0]


def test_router_norms_over_the_chosen_and_the_bias_only_steers():
    logits = jnp.asarray(np.random.RandomState(0).randn(32, 16), jnp.float32)
    zero = jnp.zeros((16,))
    idx, w = dropless.route_sigmoid_topk(logits, zero, 6, 2.448)
    s = np.asarray(jax.nn.sigmoid(logits))
    np.testing.assert_array_equal(np.sort(np.asarray(idx), 1),
                                  np.sort(np.argsort(-s, 1)[:, :6], 1))
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.448, rtol=1e-5)
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(np.asarray(w),
                               chosen / chosen.sum(1, keepdims=True) * 2.448,
                               rtol=1e-5)
    # a large bias on expert 3 puts it among every token's six; its weight
    # is still its own score's share
    bias = zero.at[3].set(10.0)
    idx_b, w_b = dropless.route_sigmoid_topk(logits, bias, 6, 1.0)
    assert (np.asarray(idx_b) == 3).any(axis=1).all()
    chosen = np.take_along_axis(s, np.asarray(idx_b), 1)
    np.testing.assert_allclose(np.asarray(w_b),
                               chosen / chosen.sum(1, keepdims=True),
                               rtol=1e-5)


# --------------------------------------------------------------- share ----
def _layer(num_local, offset, full=None):
    paddle.seed(0)
    layer = DroplessMoELayer(32, 16, num_experts=16, top_k=6,
                             num_shared_experts=2,
                             routed_scaling_factor=2.448,
                             num_local_experts=num_local,
                             expert_offset=offset)
    if full is not None:        # hold a slice of the uncut layer's experts
        sd = {n: t for n, t in full.state_dict().items()
              if "experts.gate_up" not in n and "experts.down" not in n
              or "shared" in n}
        sl = slice(offset, offset + num_local)
        sd["experts.gate_up"] = Tensor(full.experts.gate_up._data[sl])
        sd["experts.down"] = Tensor(full.experts.down._data[sl])
        layer.set_state_dict(sd)
    return layer


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Routed parts of the eight shares + the shared expert ONCE = the
    uncut layer; the shares' counts are the uncut layer's, split."""
    full = _layer(16, 0)
    x = paddle.to_tensor(np.random.RandomState(1).randn(2, 24, 32)
                         .astype("float32"))
    want = np.asarray(full(x)._data)
    want_counts = np.asarray(full.tokens_per_expert._data)
    shared = np.asarray(full.shared_experts(x.reshape([-1, 32]))._data
                        ).reshape(want.shape)
    routed, counts = np.zeros_like(want), []
    for chip in range(8):
        share = _layer(2, 2 * chip, full)
        routed += np.asarray(share(x)._data) - shared
        counts.append(np.asarray(share.tokens_per_expert._data))
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(counts), want_counts)
    assert want_counts.sum() == 2 * 24 * 6          # every assignment served
    assert np.abs(routed).max() > 10 * np.abs(routed + shared - want).max()


def test_a_share_outside_the_router_is_refused():
    with pytest.raises(ValueError, match="not among the router's"):
        DroplessMoELayer(32, 16, num_experts=16, top_k=6,
                         num_local_experts=4, expert_offset=14)


@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_layer_dropless_equals_dense_when_nothing_is_dropped(top_k):
    paddle.seed(0)
    dense = MoELayer(32, 64, 8, gate="naive", top_k=top_k,
                     capacity_factor=8.0)           # ample: nothing dropped
    paddle.seed(0)
    free = MoELayer(32, 64, 8, gate="naive", top_k=top_k, dropless=True)
    free.set_state_dict(dense.state_dict())
    x = paddle.to_tensor(np.random.RandomState(2).randn(4, 16, 32)
                         .astype("float32"))
    np.testing.assert_allclose(np.asarray(free(x)._data),
                               np.asarray(dense(x)._data), rtol=2e-4,
                               atol=2e-5)
    assert float(free.l_aux._data) == pytest.approx(
        float(dense.l_aux._data), rel=1e-5)


def test_moe_layer_dropless_drops_nothing_where_dense_does():
    """All tokens to one expert: the dense path keeps ``capacity`` of them,
    the dropless one all."""
    paddle.seed(0)
    dense = MoELayer(16, 32, 4, gate="naive", top_k=1, capacity_factor=1.0)
    free = MoELayer(16, 32, 4, gate="naive", top_k=1, dropless=True)
    free.set_state_dict(dense.state_dict())
    wg = np.zeros((16, 4), "float32")
    wg[:, 2] = 1.0                                  # expert 2 for everybody
    for layer in (dense, free):
        layer.gate_weight._data = jnp.asarray(wg)
    x = paddle.to_tensor(np.abs(np.random.RandomState(3).randn(1, 32, 16))
                         .astype("float32"))
    kept_dense = (np.abs(np.asarray(dense(x)._data)).sum(-1) > 0).sum()
    kept_free = (np.abs(np.asarray(free(x)._data)).sum(-1) > 0).sum()
    assert kept_dense == 8 and kept_free == 32


def test_moe_layer_dropless_routes_through_its_gate():
    """The gate's own jitter reaches the dropless dispatch, a capacity is
    refused, and so is a gate that cannot route without one."""
    with pytest.raises(ValueError, match="capacity_factor"):
        MoELayer(16, 32, 4, gate="naive", capacity_factor=2.0, dropless=True)
    with pytest.raises(ValueError, match="gate.route"):
        MoELayer(16, 32, 4, gate=lambda logits, jitter_key=None: None,
                 dropless=True)
    paddle.seed(0)
    free = MoELayer(16, 32, 4, gate="switch", dropless=True)
    x = paddle.to_tensor(np.random.RandomState(4).randn(2, 64, 16)
                         .astype("float32"))
    free.gate.jitter_eps = 5.0          # large: the choice visibly moves
    paddle.seed(1)
    a = np.asarray(free(x)._data)
    paddle.seed(2)
    b = np.asarray(free(x)._data)
    free.eval()
    c, d = np.asarray(free(x)._data), np.asarray(free(x)._data)
    assert np.abs(a - b).max() > 1e-6 and np.array_equal(c, d)


# ----------------------------------------------------------- attention ----
def test_rope_interleaved_layout_gives_the_pairwise_rotation_scores():
    """De-interleave then rotate-half (the published code) and the
    pairwise rotation in place give the same q . k."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 12, 2, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 12, 2, 8), jnp.float32)
    cos, sin = (jnp.asarray(a) for a in rope_tables(8, 12, 1e6))
    got = jnp.einsum("btnd,bsnd->bnts", apply_rope(q, cos, sin, True),
                     apply_rope(k, cos, sin, True))
    want = jnp.einsum("tnd,snd->nts", ref._rope(q[0], 1e6),
                      ref._rope(k[0], 1e6))[None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_sdpa_takes_keys_wider_than_values():
    from paddle_tpu.nn import functional as F

    rng = np.random.RandomState(0)
    q, k = (paddle.to_tensor(rng.randn(1, 16, 2, 192).astype("float32"))
            for _ in range(2))
    v = paddle.to_tensor(rng.randn(1, 16, 2, 128).astype("float32"))
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    assert out.shape == [1, 16, 2, 128]
    s = np.einsum("tnd,snd->nts", np.asarray(q._data[0]),
                  np.asarray(k._data[0])) / np.sqrt(192.0)
    s = np.where(np.tril(np.ones((16, 16), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("nts,snd->tnd", p, np.asarray(v._data[0]))
    np.testing.assert_allclose(np.asarray(out._data[0]), want, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("qk,v,ok", [(128, None, True), (128, 128, True),
                                     (192, 128, True), (192, None, False),
                                     (192, 192, False), (256, 128, False),
                                     (96, 64, False)])
def test_flash_supports_says_what_is_true(qk, v, ok):
    from paddle_tpu.ops.pallas.attention_kernel import supports

    assert supports(8192, 8192, qk, v) is ok


def test_flash_dispatch_takes_the_kernel_at_192_over_128(monkeypatch):
    """On the TPU path the dispatcher hands q/k 192, v 128 to the kernel
    (no fallback warning); a width the kernel does not serve falls back
    aloud."""
    import warnings

    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import attention_kernel as ak

    seen = []
    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        ak, "flash_attention_pallas",
        lambda q, k, v, causal: seen.append((q.shape, v.shape)) or v)
    x = lambda d: jnp.zeros((1, 1024, 2, d), jnp.bfloat16)  # noqa: E731
    with warnings.catch_warnings():
        warnings.simplefilter("error", pk.KernelFallbackWarning)
        pk.flash_attention(x(192), x(192), x(128), is_causal=True)
    assert seen == [((1, 1024, 2, 192), (1, 1024, 2, 128))]
    with pytest.warns(pk.KernelFallbackWarning, match="supports"):
        pk.flash_attention(x(192), x(192), x(192), is_causal=True)


def test_preset_counts_the_published_parameters():
    """kanana-2-30b-a3b's layer sizes from its config: attention 26.35M,
    an expert 4.72M, the shared expert 9.44M, the dense MLP 37.75M."""
    cfg = MlaMoeConfig(
        vocab_size=128256, hidden_size=2048, num_hidden_layers=48,
        num_attention_heads=32, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, kv_lora_rank=512, intermediate_size=6144,
        moe_intermediate_size=768, n_routed_experts=128, n_shared_experts=2,
        num_experts_per_tok=6)
    assert cfg.qk_head_dim == 192 and cfg.num_local_experts == 128
    from chipbench.readers.mfu_active import active_params

    m = dict(hidden_size=2048, num_attention_heads=32, qk_nope_head_dim=128,
             qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
             intermediate_size=6144, moe_intermediate_size=768,
             router_experts=128, n_shared_experts=2, num_hidden_layers=1,
             first_k_dense_replace=1, vocab_size=0)
    dense_layer = active_params(m, 0)
    assert dense_layer == pytest.approx(26.35e6 + 37.75e6, rel=1e-3)
    m["first_k_dense_replace"] = 0
    assert active_params(m, 6) - active_params(m, 0) == 6 * 3 * 2048 * 768
    assert active_params(m, 0) == pytest.approx(26.35e6 + 0.26e6 + 9.44e6,
                                                rel=1e-3)
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        MlaMoeConfig(q_lora_rank=1536)


# ------------------------------------------------------ grouped matmul ----
def test_grouped_matmul_is_ragged_dot_off_the_tpu():
    from paddle_tpu.ops import pallas as pk

    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.randn(24, 8), jnp.float32)
    w = jnp.asarray(rng.randn(3, 8, 5), jnp.float32)
    sizes = jnp.asarray([10, 0, 9], jnp.int32)      # 5 rows belong to nobody
    got = np.asarray(pk.grouped_matmul(xs, w, sizes))
    np.testing.assert_allclose(got[:10], np.asarray(xs[:10] @ w[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[10:19], np.asarray(xs[10:19] @ w[2]),
                               rtol=1e-5, atol=1e-5)
    assert not got[19:].any()


def test_grouped_matmul_transposes_are_its_gradients_off_the_tpu():
    """``transpose_w`` and ``grouped_matmul_dw`` are what ``jax.vjp`` of the
    product gives for its rows and its matrices (the routed block's
    backward calls them by name)."""
    from paddle_tpu.ops import pallas as pk

    rng = np.random.RandomState(1)
    xs = jnp.asarray(rng.randn(24, 8), jnp.float32)
    w = jnp.asarray(rng.randn(3, 8, 5), jnp.float32)
    sizes = jnp.asarray([10, 0, 9], jnp.int32)      # 5 rows belong to nobody
    g = jnp.asarray(rng.randn(24, 5), jnp.float32).at[19:].set(0.0)
    _, vjp = jax.vjp(lambda xs, w: pk.grouped_matmul(xs, w, sizes), xs, w)
    d_xs, d_w = vjp(g)
    np.testing.assert_allclose(
        np.asarray(pk.grouped_matmul(g, w, sizes, transpose_w=True)),
        np.asarray(d_xs), rtol=1e-5, atol=1e-5)
    got = np.asarray(pk.grouped_matmul_dw(xs, g, sizes))
    np.testing.assert_allclose(got, np.asarray(d_w), rtol=1e-5, atol=1e-5)
    assert got.shape == (3, 8, 5) and not got[1].any()


def test_grouped_matmul_lowers_to_the_pallas_kernels_for_the_tpu(monkeypatch):
    """At the cell's widths (the worst case's 98,304 rows, 16 experts of
    2048 x 1536 and 768 x 2048), forward and backward: the Mosaic calls are
    there and ``ragged_dot`` is not."""
    from paddle_tpu.ops import pallas as pk

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    sds = jax.ShapeDtypeStruct

    def loss(xs, gate_up, down, sizes):
        ys = dropless.experts_mlp(xs, gate_up, down, sizes)
        return jnp.sum(ys.astype(jnp.float32))

    args = (sds((98304, 2048), jnp.bfloat16),
            sds((16, 2048, 1536), jnp.bfloat16),
            sds((16, 768, 2048), jnp.bfloat16), sds((16,), jnp.int32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    # the first forward, and a gmm and a tgmm for each matmul's backward
    # (a sum needs no second forward)
    assert text.count("tpu_custom_call") >= 5
    assert "ragged_dot" not in text


def test_grouped_matmul_gives_way_aloud_where_the_rows_do_not_tile(
        monkeypatch):
    from paddle_tpu.ops import pallas as pk

    monkeypatch.setattr(pk, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    xs, w = jnp.ones((100, 8)), jnp.ones((2, 8, 4))
    with pytest.warns(pk.KernelFallbackWarning, match="multiple of 128"):
        out = pk.grouped_matmul(xs, w, jnp.asarray([60, 40], jnp.int32))
    assert out.shape == (100, 4)
