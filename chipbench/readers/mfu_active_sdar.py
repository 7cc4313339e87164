"""Model FLOP/s utilisation of training the block-diffusion expert family
(``model_type`` ``sdar_moe``), in %, as ``readers/mfu_active.py`` reckons it
for the latent-attention family, a share of the WHOLE step:

    6 * N_active * positions/s/chip / bf16 peak of the attached device_kind

A data token runs TWO positions through the stack (its noised and its clean
copy) and one through the head.  ``N_active`` is what a position really
multiplies HERE: the four attention projections, the q/k gains and the
block's two norms, the router and, of the routed experts, the ones that
served it on this chip: one expert's ``3 H I`` times the assignments a
position got here, from the program's own counter over the window's steps
(``res["counters"]``), not from the routing's expectation; the final norm
and the head for the noised half alone, so half a position's share (the
embedding is a lookup).  Attention's own operations (the pairs the mask
holds) are LEFT OUT, as ``mfu_pct`` leaves them out, and recomputation
counts for nothing.  Returns nothing where the runner handed over no
counter or the model is not of this family.
"""


def active_params(model, assignments_per_position):
    """What one POSITION of the ``2 L`` multiplies, the head's half share
    included."""
    h, d = model["hidden_size"], model["head_dim"]
    n, kv = model["num_attention_heads"], model["num_key_value_heads"]
    attn = 2 * h * n * d + 2 * h * kv * d + 2 * d
    expert = 3 * h * model["moe_intermediate_size"]
    moe = h * model["router_experts"] + assignments_per_position * expert
    return (model["num_hidden_layers"] * (attn + moe + 2 * h)
            + 0.5 * (h + h * model["vocab_size"]))


def read(env, moves):
    from .expert_load import served, window_counts

    counts = window_counts(env)
    model = env.config["model"]
    if not counts or not env.steps or "block_length" not in model:
        return None
    positions = 2 * env.traffic["batch"] * env.traffic["seq"]
    per_position = served(counts) / (
        len(counts) * model["num_hidden_layers"] * positions)
    n_active = active_params(model, per_position)
    env.ctx.note(f"mfu_active.sdar: {per_position:.4f} assignments a "
                 f"position and expert layer served here, "
                 f"{n_active / 1e6:.1f}M parameters active a position, two "
                 f"positions a data token")
    return 100.0 * 6.0 * n_active * 2.0 * env.end_to_end[moves] \
        / env.peaks["bf16_flops"]
