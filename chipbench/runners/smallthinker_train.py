"""Runner ``smallthinker_train``: an expert decoder whose router reads a
block's input before attention and whose experts are ReGLU
(``paddle_tpu.models.smallthinker``), through ``amp.decorate`` O2 ->
``jit.TrainStep`` + ``AdamW`` on one chip.  Everything but the program is
``runners/train.py``'s ``run``; how a layer's leaves are named, loaded and
read is ``runners/laguna_train.py``'s, and what is done with the experts'
counter and with the direction of the parameters' change is
``runners/mla_moe_train.py``'s, all imported (``README.smallthinker.md``).

The configuration's file holds the source's ``config.json`` keys at its top
level, as they are run; ``deployment`` says what the chip holds of a layer
(``router_experts``: the router's published width, ``expert_offset``: the
first expert held) and ``trainer`` how the step is built.
:func:`model_group` gathers them into the ``model`` group ``train.run``, the
reference and the readers take.

Beside the loss the step hands back the experts' counters and
``moe_tokens_unserved`` (float32: the tokens none of whose chosen experts is
held here, the mean over the expert layers); the window's go to the readers
(``moe_unserved_token_share``), the first steps' stand in a note beside the
reference's own count.

One check more than the other expert runners make, ``window_edge_gap``
(:func:`window_edge_gap`): a window one key too wide moves a training step
by one key's weight in 4,096, under bfloat16's rounding, so no number of the
two compared steps sees it (the chip, PR 51: every gap inside the sound
spread).  The program's own attention call is therefore probed where the
window ENDS, on q and k made so that the one key a window must not see
would take the whole softmax.
"""

import functools

import numpy as np

from ..kernel_costs.flash_attention_gqa import FULL, WINDOW
from . import laguna_train as by_group
from . import mla_moe_train as moe
from . import train

# the source's keys that shape the model (architectures.jsonl `config`)
MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_ffn_hidden_size",
    "moe_num_primary_experts", "moe_num_active_primary_experts",
    "moe_primary_router_apply_softmax", "norm_topk_prob", "rope_layout",
    "sliding_window_layout", "sliding_window_size", "rope_theta",
    "rope_scaling", "max_position_embeddings", "rms_norm_eps",
    "tie_word_embeddings", "vocab_size")
# how the SEEDED weights are drawn (the reference module's to read)
SEEDING_KEYS = ("embedding_range",)
UNSERVED = "moe_tokens_unserved"


def model_group(cfg):
    """The ``model`` group: the source's keys as run, what the deployment
    adds, and under the names the accepted cost functions and readers read:
    ``n_routed_experts`` (the experts held), ``first_k_dense_replace`` (the
    dense layers: none) and ``moe_intermediate_size`` for
    ``kernel_costs/moe_grouped_matmul.py``; ``layer_types`` (from
    ``sliding_window_layout``), ``num_attention_heads_per_layer`` and
    ``sliding_window`` for ``kernel_costs/flash_attention_gqa.py`` and
    ``flash_attention_window.py``."""
    model = {k: cfg[k] for k in MODEL_KEYS}
    dep = cfg.get("deployment", {})
    model["router_experts"] = int(dep.get("router_experts",
                                          cfg["moe_num_primary_experts"]))
    model["expert_offset"] = int(dep.get("expert_offset", 0))
    model["initializer_range"] = cfg.get("initializer_range", 0.02)
    for key in SEEDING_KEYS:
        if key in cfg:
            model[key] = cfg[key]
    layers = int(cfg["num_hidden_layers"])
    model["n_routed_experts"] = int(cfg["moe_num_primary_experts"])
    model["first_k_dense_replace"] = 0
    model["moe_intermediate_size"] = int(cfg["moe_ffn_hidden_size"])
    model["num_experts_per_tok"] = int(cfg["moe_num_active_primary_experts"])
    model["layer_types"] = [WINDOW if w else FULL
                            for w in cfg["sliding_window_layout"]]
    model["num_attention_heads_per_layer"] = \
        [int(cfg["num_attention_heads"])] * layers
    model["sliding_window"] = int(cfg["sliding_window_size"])
    return model


def model_config(m):
    from paddle_tpu.models.smallthinker import SmallThinkerConfig

    return SmallThinkerConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"],
        moe_ffn_hidden_size=m["moe_ffn_hidden_size"],
        moe_num_primary_experts=m["router_experts"],
        num_local_experts=m["moe_num_primary_experts"],
        expert_offset=m["expert_offset"],
        moe_num_active_primary_experts=m["moe_num_active_primary_experts"],
        moe_primary_router_apply_softmax=m[
            "moe_primary_router_apply_softmax"],
        norm_topk_prob=m["norm_topk_prob"], rope_layout=m["rope_layout"],
        sliding_window_layout=m["sliding_window_layout"],
        sliding_window_size=m["sliding_window_size"],
        rope_theta=m["rope_theta"], rope_scaling=m["rope_scaling"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=m["rms_norm_eps"],
        tie_word_embeddings=m["tie_word_embeddings"],
        initializer_range=m["initializer_range"])


def build_model(ctx):
    """The program's model in the stated precision, holding the SEEDED
    weights of the reference module."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.smallthinker import SmallThinkerForCausalLM

    cfg = ctx.config
    m = cfg["model"]
    ctx.note(f"set-up: imports done at {ctx.clock():.1f} s")
    paddle.seed(0)
    model = SmallThinkerForCausalLM(model_config(m))
    model = paddle.amp.decorate(model, level="O2", dtype=cfg["dtype"])
    jax.block_until_ready([p._data for p in model.parameters()])
    ctx.note(f"set-up: the program's own model built and cast at "
             f"{ctx.clock():.1f} s")
    ref_mod = ctx.reference()
    by_group.load_seeded(model, ref_mod.init_params(
        ctx.seed, m, jnp.dtype(cfg["dtype"])), ref_mod, m)
    jax.block_until_ready([p._data for p in model.parameters()])
    return model


class Program(by_group.Program):
    """``laguna_train.Program`` (the counters kept a call, the state read by
    the reference's groups, the parameters kept after the first
    ``check_steps`` steps) over this family's model."""

    def __init__(self, ctx, kept):
        from paddle_tpu.jit import TrainStep

        self.model = build_model(ctx)
        self.step = TrainStep(
            self.model, lambda logits, labels: self.model.loss(logits,
                                                               labels),
            train.optimizer_for(ctx, self.model),
            remat=ctx.config.get("trainer", {}).get("remat", False))
        self.chips = 1
        self._group_of = functools.partial(ctx.reference().group_of,
                                           ctx.config["model"])
        self._check_steps = int(ctx.config["check_steps"])
        self._kept = kept


def window_edge_gap(ctx):
    """The largest absolute difference, over positions, heads and dims,
    between the program's window attention and the reference's on a probe of
    the cell's own shapes (one row of the traffic's length, the model's
    heads, its window ``W``), values of unit variance.

    Keys are ``k_j = a P_j`` and queries ``q_t = a P_{t - W}`` with ``P_j =
    [cos(theta_i j) | sin(theta_i j)]`` over seeded frequencies: the score of
    key ``j`` for query ``t`` is ``a^2 sum_i cos(theta_i (t - W - j)) /
    sqrt(D)``, ``a^2 sqrt(D) / 2 = 30`` at ``j = t - W`` (the FIRST key the
    window leaves out) and noise of deviation ``a^2 / sqrt(2)`` elsewhere.
    A sound window never sees that key and the two sides agree to the
    rounding of the probabilities (both get the same bfloat16 q, k, v); a
    window one key too wide hands nearly all of row ``t`` to ``v_{t - W}``.
    The program's side is ``F.scaled_dot_product_attention(..., window=W)``,
    looked up where ``GroupedGatedAttention`` looks it up, so on the TPU the
    ``flash_window<W>_attention_fwd`` kernel at the cell's shapes."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn import functional as F

    m, seq = ctx.config["model"], int(ctx.traffic["seq"])
    n, kv, d = (int(m[k]) for k in ("num_attention_heads",
                                    "num_key_value_heads", "head_dim"))
    window = int(m["sliding_window"])
    dtype = jnp.dtype(ctx.config["dtype"])
    rng = np.random.default_rng(ctx.seed)
    theta = rng.uniform(0.0, np.pi, d // 2)
    amp = np.sqrt(60.0 / np.sqrt(d))

    def coded(positions):
        ang = np.outer(positions, theta)
        return amp * np.concatenate([np.cos(ang), np.sin(ang)], axis=1)

    at = np.arange(seq, dtype=np.float64)
    q = jnp.asarray(np.repeat(coded(at - window)[:, None], n, axis=1), dtype)
    k = jnp.asarray(np.repeat(coded(at)[:, None], kv, axis=1), dtype)
    v = jnp.asarray(rng.standard_normal((seq, kv, d)), dtype)
    got = jax.jit(lambda q, k, v: F.scaled_dot_product_attention(
        Tensor(q[None]), Tensor(k[None]), Tensor(v[None]), is_causal=True,
        window=window)._data[0])(q, k, v)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q, k, v: ctx.reference().attend(
            q, k, v, window))(q.astype(f32), k.astype(f32), v.astype(f32))
    return float(jnp.max(jnp.abs(got.astype(f32) - want)))


def run(ctx):
    cfg = ctx.config
    cfg["model"] = model_group(cfg)
    kept = {"counters": [], "params": None}
    ref = moe._KeepingResults(ctx.reference(), kept)
    ctx.reference = lambda: ref
    res = train.run(ctx, program_cls=functools.partial(Program, kept=kept))

    want = ref.results["float32"]
    controls = {p: out for p, out in ref.results.items() if p != "float32"}
    moe.check_direction(ctx, "param_change_direction_gap", want)
    for precision, out in controls.items():
        moe.check_direction(
            ctx, f"control.{precision}.param_change_direction_gap", out)

    k = int(cfg["check_steps"])
    counts = [np.asarray(c[moe.COUNTER]).tolist() for c in kept["counters"]]
    unserved = [float(np.asarray(c[UNSERVED])) for c in kept["counters"]]
    ctx.check("expert_count_gap",
              moe.count_gap(counts[:k], want["expert_counts"]),
              ctx.limit("expert_count_gap"),
              detail=f"first step, layer by layer: program "
                     f"{np.sum(counts[0], axis=1).tolist()}, reference "
                     f"{np.sum(want['expert_counts'][0], axis=1).tolist()} "
                     f"assignments")
    for precision, out in controls.items():
        ctx.check(f"control.{precision}.expert_count_gap",
                  moe.count_gap(out["expert_counts"], want["expert_counts"]),
                  ctx.limit("expert_count_gap"))
    ctx.check("window_edge_gap", window_edge_gap(ctx),
              ctx.limit("window_edge_gap"),
              detail=f"the program's window attention against the "
                     f"reference's where a window of "
                     f"{cfg['model']['sliding_window']} keys ends")
    tokens = ctx.traffic["batch"] * ctx.traffic["seq"]
    ctx.note(f"counters: {UNSERVED}, first steps, the mean over the expert "
             f"layers: program {unserved[:k]}, reference "
             f"{[float(np.mean(u)) for u in want['tokens_unserved']]} of "
             f"{tokens} tokens")

    # calls: k first steps, one uncounted, then the window's records
    first = k + 1
    window = counts[first:first + len(res["steps"])]
    res["counters"] = {moe.COUNTER: window,
                       UNSERVED: unserved[first:first + len(res["steps"])]}
    if window:
        made = tokens * cfg["model"]["num_experts_per_tok"]
        ctx.note(f"counters: {moe.COUNTER} over {len(window)} window steps: "
                 f"assignments served here a step, of {made} made in each "
                 f"of {len(window[0])} expert layers (none dropped): "
                 f"{[int(np.sum(c)) for c in window]}; by layer in the "
                 f"first and the last step "
                 f"{np.sum(window[0], axis=1).tolist()} -> "
                 f"{np.sum(window[-1], axis=1).tolist()}")
        ctx.note(f"counters: {UNSERVED} a step, first and last of the "
                 f"window: {res['counters'][UNSERVED][0]:.1f} -> "
                 f"{res['counters'][UNSERVED][-1]:.1f} of {tokens} tokens")
    return res
