"""Expert decoder whose ROUTER reads a block's input before attention and
whose experts are ReGLU (``model_name`` ``smallthinker_21b_instruct``:
SmallThinker-21BA3B-Instruct, huggingface.co/PowerInfer/SmallThinker-21BA3B-
Instruct ``config.json``; SmallThinker, arXiv:2507.20984).

The decoder is the shell of ``models/moe_decoder.py`` with ONE option on
(``router_reads_block_input``); a layer's attention is ``models/laguna.py
GroupedGatedAttention`` with its gate and its q/k norms off, and the layers
differ in two ways the config lists layer by layer.  With ``x [T, H]`` the
block's input, ``n`` q heads over ``kv`` kv heads of ``D``, every projection
without a bias, ``rms`` with ``rms_norm_eps``:

    r    = x W_r                      [T, E]   the router reads the block's
                                      INPUT, un-normed, before attention
    a    = rms_1(x)
    q    = W_q a -> [T, n, D];  k = W_k a, v = W_v a -> [T, kv, D]
    if rope_layout[i]:  rotate-half over all D dims of q and k, base
                        ``rope_theta``, plain        (else: NO position
                        encoding, q and k go on as they are)
    o_h  = softmax(causal(q_h . k_{h // (n / kv)} / sqrt(D))) v_{h // (n / kv)}
           if sliding_window_layout[i]: query t sees keys j with
           0 <= t - j < ``sliding_window_size``
    x'   = x + concat_h(o_h) W_o
    b    = rms_2(x')
    S    = the ``moe_num_active_primary_experts`` largest of r, a token;
           w = softmax(r[S]) in float32 over the chosen
           (``moe_primary_router_apply_softmax``, ``norm_topk_prob``: the
           same numbers as a softmax over all E normed over the chosen,
           which is how ``dropless.route_softmax_topk`` forms them)
    y    = sum_{e in S} w_e W_down,e (relu(W_gate,e b) * W_up,e b)    ReGLU;
           no shared expert
    x''  = x' + y;    logits = W_head rms_f(x_L)                       untied

The expert layer is ``DroplessMoELayer(score_func="softmax", body="reglu")``
called as ``moe(b, router_input=x)``.  With no shared expert a token none
of whose chosen experts is held here leaves the layer with exactly nothing:
``moe_tokens_unserved`` counts them (``step_counters``).

What the config has no key for is absent here: the paper's secondary
(hierarchical) experts and its load-balance loss.  That the router reads the
un-normed block input is a reading of the public modeling file and of the
public llama.cpp graph (``ffn_gate_inp`` on the layer's input before
``attn_norm``); the benchmark configuration lists it under ``assumed``
(``chipbench/configs/smallthinker-21b-a3b-train-l4-ep4.json``).

Scopes inside ``attn`` (``docs/PROFILER.md``): ``attn_window`` or
``attn_full``, as laguna's; the router runs under ``moe/router`` as in every
family, though on another tensor.  This file trains; it has no decode path.
"""

from .laguna import GroupedGatedAttention
from .moe_decoder import MoeDecoderConfig, MoeDecoderForCausalLM


class SmallThinkerConfig(MoeDecoderConfig):
    """Keys as the source's ``config.json`` names them.
    ``moe_num_primary_experts`` is the router's width;
    ``num_local_experts`` of them, from ``expert_offset`` on, are held (all
    by default).  ``rope_layout`` and ``sliding_window_layout`` have an entry
    (0 or 1) a layer."""

    router_reads_block_input = True

    def __init__(self, vocab_size=512, hidden_size=64, num_hidden_layers=4,
                 num_attention_heads=6, num_key_value_heads=2, head_dim=16,
                 moe_ffn_hidden_size=32, moe_num_primary_experts=16,
                 moe_num_active_primary_experts=3,
                 moe_primary_router_apply_softmax=True, norm_topk_prob=True,
                 rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
                 sliding_window_size=8, rope_theta=1500000, rope_scaling=None,
                 max_position_embeddings=16384, rms_norm_eps=1e-6,
                 tie_word_embeddings=False, initializer_range=0.02,
                 num_local_experts=None, expert_offset=0):
        for name, per_layer in (("rope_layout", rope_layout),
                                ("sliding_window_layout",
                                 sliding_window_layout)):
            if len(per_layer) != num_hidden_layers:
                raise ValueError(f"{name} has {len(per_layer)} entries for "
                                 f"{num_hidden_layers} layers")
        if not (moe_primary_router_apply_softmax and norm_topk_prob):
            raise NotImplementedError(
                "weights are a softmax over the chosen experts' logits "
                "(moe_primary_router_apply_softmax and norm_topk_prob, as "
                "published); a sigmoid router is not built")
        if rope_scaling is not None or tie_word_embeddings:
            raise NotImplementedError(
                "rope_scaling and a tied head are not built: the published "
                "model has neither")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.moe_ffn_hidden_size = moe_ffn_hidden_size
        self.moe_num_primary_experts = moe_num_primary_experts  # the router's
        self.moe_num_active_primary_experts = moe_num_active_primary_experts
        self.moe_primary_router_apply_softmax = \
            moe_primary_router_apply_softmax
        self.norm_topk_prob = norm_topk_prob
        self.rope_layout = tuple(int(v) for v in rope_layout)
        self.sliding_window_layout = tuple(
            int(v) for v in sliding_window_layout)
        self.sliding_window_size = sliding_window_size
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        self.num_local_experts = moe_num_primary_experts \
            if num_local_experts is None else num_local_experts
        self.expert_offset = expert_offset

    def make_attention(self, layer_idx):
        return GroupedGatedAttention(
            self.hidden_size, self.num_attention_heads,
            self.num_key_value_heads, self.head_dim,
            {"rope_theta": self.rope_theta}
            if self.rope_layout[layer_idx] else None,
            self.initializer_range, self.out_std,
            window=self.sliding_window_size
            if self.sliding_window_layout[layer_idx] else None,
            gate=False)

    def make_ffn(self, layer_idx):
        return self.expert_layer(
            self.moe_ffn_hidden_size, self.moe_num_primary_experts,
            self.moe_num_active_primary_experts, 0, 1.0,
            score_func="softmax", body="reglu")


class SmallThinkerForCausalLM(MoeDecoderForCausalLM):
    """The shell of ``models/moe_decoder.py`` under
    ``router_reads_block_input``."""


def smallthinker_tiny(**kw):
    """Test config: every mechanism at a size the CPU runs (one period: a
    full layer without a position encoding, three window layers with one; 6
    q heads over 2 kv heads, a group of 3)."""
    return SmallThinkerForCausalLM(SmallThinkerConfig(**kw))


def smallthinker_21b_a3b(**kw):
    """SmallThinker-21BA3B-Instruct as its ``config.json`` states it
    (huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct): 52 layers of
    hidden 2560, 28 q heads over 4 kv heads of 128; a window of 4,096 and
    rotary at base 1.5e6 on three layers of four, full attention with no
    position encoding on the fourth; 64 ReGLU experts of 768 in every layer,
    six a token, none shared; vocabulary 151,936 untied.  Keyword arguments
    override (depth, the experts held, the vocabulary's slice):
    ``num_hidden_layers=n`` keeps the first ``n`` entries of the two
    layouts."""
    layers = int(kw.get("num_hidden_layers", 52))
    layout = [0 if i % 4 == 0 else 1 for i in range(layers)]
    cfg = dict(vocab_size=151936, hidden_size=2560, num_hidden_layers=layers,
               num_attention_heads=28, num_key_value_heads=4, head_dim=128,
               moe_ffn_hidden_size=768, moe_num_primary_experts=64,
               moe_num_active_primary_experts=6,
               moe_primary_router_apply_softmax=True, norm_topk_prob=True,
               rope_layout=layout, sliding_window_layout=layout,
               sliding_window_size=4096, rope_theta=1500000,
               rope_scaling=None, max_position_embeddings=16384,
               rms_norm_eps=1e-6, tie_word_embeddings=False)
    cfg.update(kw)
    return SmallThinkerForCausalLM(SmallThinkerConfig(**cfg))
