"""Model FLOP/s utilisation of training, in %:

    6 * N * tokens/s/chip / bf16 peak of the attached device_kind

N counts every parameter once (the tied head once); attention's own
operations (the T^2 terms) are LEFT OUT, and recomputation counts for
nothing.  The rate is the end-to-end metric's own reading.
"""


def n_params(model):
    h = model["hidden_size"]
    inter = model.get("intermediate_size") or 4 * h
    per_layer = 4 * h * h + 2 * h * inter + 9 * h + inter  # weights, biases, 2 LN
    return (model["num_hidden_layers"] * per_layer
            + (model["vocab_size"] + model["max_position_embeddings"]) * h
            + 2 * h)


def read(env, moves):
    rate = env.end_to_end[moves]
    return 100.0 * 6.0 * n_params(env.config["model"]) * rate \
        / env.peaks["bf16_flops"]
