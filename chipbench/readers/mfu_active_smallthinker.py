"""Model FLOP/s utilisation of training the family whose router sits before
attention (``model_name`` ``smallthinker_*``), in %, a share of the WHOLE
step's peak:

    (6 * N_active + attention's operations a token) * tokens/s/chip
        / bf16 peak of the attached device_kind

``N_active`` is what a token really multiplies HERE: a layer's four
projections (q and o ``H x n D``, k and v ``H x kv D``), the router ``H x
E`` (all of the router's width, whatever is held) and, of the routed
experts, the ones that served the token on this chip: a ReGLU expert's ``3 H
I`` times the assignments a token got here, from the program's own counter
over the window's steps (``res["counters"]``: about 1.5 of a token's 6 where
16 of 64 experts are held), not from the routing's expectation; and the
untied head (the embedding is a lookup).  Norms count for nothing.

Unlike ``readers/mfu_active.py`` this reader COUNTS attention: in this cell
the scores are half the stack's arithmetic, and leaving them out would halve
the share.  It counts the pairs a layer's mask NEEDS
(``kernel_costs/flash_attention_gqa.pairs``: ``T (T + 1) / 2`` causal, ``W (W
+ 1) / 2 + (T - W) W`` under a window of ``W``), two matmuls of ``2 n D`` a
pair forward and twice that backward: ``12 n D`` a pair and layer.  What the
kernels form beyond the mask, what the backward kernel forms twice and what
is recomputed count for nothing.  Returns nothing where the runner handed
over no counter or the model is not of this family.
"""

from ..kernel_costs.flash_attention_gqa import WINDOW, pairs


def active_params(model, assignments_per_token):
    h, d = model["hidden_size"], model["head_dim"]
    n, kv = model["num_attention_heads"], model["num_key_value_heads"]
    attn = 2 * h * n * d + 2 * h * kv * d
    expert = 3 * h * model["moe_ffn_hidden_size"]
    moe = h * model["router_experts"] + assignments_per_token * expert
    return (model["num_hidden_layers"] * (attn + moe)
            + h * model["vocab_size"])


def attention_flops_per_token(model, seq):
    """Forward and backward of every layer's scores, a token of a row of
    ``seq``: ``12 n D`` a pair the layer's mask holds."""
    n, d = model["num_attention_heads"], model["head_dim"]
    window = int(model["sliding_window_size"])
    held = sum(pairs(seq, window if kind == WINDOW else None)
               for kind in model["layer_types"])
    return 12.0 * n * d * held / seq


def read(env, moves):
    from .expert_load import served, window_counts

    counts = window_counts(env)
    model = env.config["model"]
    if not counts or not env.steps or "moe_ffn_hidden_size" not in model:
        return None
    tokens = env.traffic["batch"] * env.traffic["seq"]
    per_token = served(counts) / (len(counts) * model["num_hidden_layers"]
                                  * tokens)
    n_active = active_params(model, per_token)
    attention = attention_flops_per_token(model, env.traffic["seq"])
    env.ctx.note(f"mfu_active.smallthinker: {per_token:.4f} assignments a "
                 f"token and expert layer served here, {n_active / 1e6:.1f}M "
                 f"parameters active a token, attention "
                 f"{attention / 1e9:.3f} GFLOP a token beside "
                 f"{6 * n_active / 1e9:.3f} of matrices")
    return 100.0 * (6.0 * n_active + attention) * env.end_to_end[moves] \
        / env.peaks["bf16_flops"]
