"""Model FLOP/s utilisation of training the hybrid family (``model_type``
``nemotron_h``), in %, as ``readers/mfu_active.py`` reckons it for the
latent-attention family:

    6 * N_active * tokens/s/chip / bf16 peak of the attached device_kind

``N_active`` is what a token really multiplies HERE, by the pattern's
letters: an ``M`` layer's ``in_proj`` and ``out_proj``, its convolution's
taps and bias, its gains and the 3 ``nh`` scalars; a ``*`` layer's four
projections; an ``E`` layer's router, latent projections, shared expert
and, of the routed experts, the ones that served the token on this chip:
one expert's ``2 L I`` times the assignments a token got here, from the
program's own counter over the window's steps (``res["counters"]``); every
block's norm, the final norm and the head (the embedding is a lookup).
The scan's own operations (the chunk's ``Q^2`` and state terms) and
attention's (``T^2``) are LEFT OUT, as ``mfu_pct`` leaves attention's out,
and recomputation counts for nothing.  Returns nothing where the runner
handed over no counter.
"""


def active_params(model, assignments_per_token):
    h = model["hidden_size"]
    d = model["mamba_num_heads"] * model["mamba_head_dim"]
    conv = d + 2 * model["n_groups"] * model["ssm_state_size"]
    nh = model["mamba_num_heads"]
    mamba = (h * (d + conv + nh) + d * h + conv * (model["conv_kernel"] + 1)
             + d + 3 * nh)
    n, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    attn = 2 * h * n * hd + 2 * h * kv * hd
    latent = model["moe_latent_size"]
    expert = 2 * latent * model["moe_intermediate_size"]
    moe = (h * model["router_experts"] + 2 * h * latent
           + 2 * h * model["moe_shared_expert_intermediate_size"]
           + assignments_per_token * expert)
    pattern = model["hybrid_override_pattern"]
    return (pattern.count("M") * mamba + pattern.count("*") * attn
            + pattern.count("E") * moe + len(pattern) * h + h
            + h * model["vocab_size"])


def read(env, moves):
    from .expert_load import served, window_counts

    counts = window_counts(env)
    model = env.config["model"]
    if not counts or not env.steps or "hybrid_override_pattern" not in model:
        return None
    n_moe = model["hybrid_override_pattern"].count("E")
    tokens = env.traffic["batch"] * env.traffic["seq"]
    per_token = served(counts) / (len(counts) * max(n_moe, 1) * tokens)
    n_active = active_params(model, per_token)
    env.ctx.note(f"mfu_active.nemotron_h: {per_token:.4f} assignments a "
                 f"token and expert layer served here, "
                 f"{n_active / 1e6:.1f}M parameters active a token")
    return 100.0 * 6.0 * n_active * env.end_to_end[moves] \
        / env.peaks["bf16_flops"]
