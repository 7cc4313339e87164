"""Model FLOP/s utilisation of training a sparse model, in %:

    6 * N_active * tokens/s/chip / bf16 peak of the attached device_kind

``N_active`` is what a token really multiplies HERE: every attention
projection and norm, the dense layers' MLP, the router, the shared
expert, the head (the embedding is a lookup), and of the routed experts
the ones that served it on this chip: one expert's parameters times the
assignments a token got here, from the program's own counter over the
window's steps (``res["counters"]``), not from the routing's expectation.
Attention's own operations (the T^2 terms) are LEFT OUT, as ``mfu_pct``
leaves them out, and recomputation counts for nothing.  Returns nothing
where the runner handed over no counter.
"""


def active_params(model, assignments_per_token):
    h = model["hidden_size"]
    n = model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    v, rank = model["v_head_dim"], model["kv_lora_rank"]
    attn = (h * n * (nope + rope) + h * (rank + rope) + rank
            + rank * n * (nope + v) + n * v * h)
    dense = 3 * h * model["intermediate_size"]
    expert = 3 * h * model["moe_intermediate_size"]
    moe = (h * model["router_experts"]
           + model["n_shared_experts"] * expert
           + assignments_per_token * expert)
    layers = model["num_hidden_layers"]
    n_dense = min(model["first_k_dense_replace"], layers)
    return (layers * (attn + 2 * h) + n_dense * dense
            + (layers - n_dense) * moe + h + h * model["vocab_size"])


def read(env, moves):
    from .expert_load import served, window_counts

    counts = window_counts(env)
    if not counts or not env.steps:
        return None
    model = env.config["model"]
    n_moe = model["num_hidden_layers"] - model["first_k_dense_replace"]
    tokens = env.traffic["batch"] * env.traffic["seq"]
    per_token = served(counts) / (len(counts) * max(n_moe, 1) * tokens)
    n_active = active_params(model, per_token)
    env.ctx.note(f"mfu_active: {per_token:.4f} assignments a token and "
                 f"expert layer served here, {n_active / 1e6:.1f}M "
                 f"parameters active a token")
    return 100.0 * 6.0 * n_active * env.end_to_end[moves] \
        / env.peaks["bf16_flops"]
