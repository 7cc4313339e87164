"""GPT-2 as the engine serves it — the ONE serving model there is.

Everything the serving stack knows about the model it runs lives here:
what is read from ``model.config`` and ``model.functional_decompose()``,
what the parameters are called, the math of embed / block / head over
the paged KV cache, how the block's matmuls shard over a tensor-parallel
``'mp'`` axis, the cache's shape, and how a draft model is cut out of
the target.  ``engine.py`` schedules, packs, owns the pools and
launches; it calls :meth:`GPT2ServingModel.forward` and never names a
parameter.  Serving another block is another file like this one plus a
dense reference to test it against (the way ``quality.py`` is this
one's), not an engine edit.

The block is the stacked-params ``lax.scan`` decoder of
``incubate.nn.FusedMultiTransformer`` — pre-LN, fused QKV, tanh GELU,
learned positions, head tied to the word embedding — with the dense
cache replaced by one layer's view of the paged pool.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...incubate.nn import _layernorm
from .kv_cache import KVCacheSpec, token_slots, write_tokens
from .lora import LORA_PREFIX, lora_key
from .paged_attention import paged_ragged_attention
from .quant import scale_key

# The block's four matmuls and how each shards Megatron-style over
# 'mp': a column-parallel GEMM splits its OUTPUT columns (its bias and
# per-output-channel int8 scales split with them), a row-parallel GEMM
# splits its INPUT rows and its partial products meet in a psum (bias
# added once after it; its scales sit on the unsharded output axis and
# stay replicated, so shard-then-dequant equals dequant-then-shard).
# Everything derived from "which leaves are GEMMs" reads this table:
# the int8 weight set (quant.QUANT_BLOCK_LEAVES), the LoRA targets
# (lora.LORA_TARGET_LEAVES) and every PartitionSpec below.
GEMMS = {
    "attn.qkv.weight": "column",
    "attn.proj.weight": "row",
    "mlp.fc_in.weight": "column",
    "mlp.fc_out.weight": "row",
}
GEMM_LEAVES = tuple(GEMMS)
_QKV = "attn.qkv.weight"


def _bias_key(key):
    return key[:-len("weight")] + "bias"


def _block_specs():
    """PartitionSpec of every stacked block leaf that is not replicated
    (leading dim is the layer stack).  Multi-LoRA adapter pools
    ([L, A, in, r] / [L, A, r, out]) shard with their base GEMM: a
    column-parallel target's B pool splits its output columns (A
    replicated), a row-parallel target's A pool splits its input rows
    (B replicated) — the per-device partial deltas ride the layer's
    existing psum, so tp>1 stays bit-identical to tp=1."""
    specs = {}
    for key, kind in GEMMS.items():
        if kind == "column":
            specs[key] = P(None, None, "mp")
            specs[_bias_key(key)] = P(None, "mp")
            specs[scale_key(key)] = P(None, None, "mp")
            specs[lora_key(key, "B")] = P(None, None, None, "mp")
        else:
            specs[key] = P(None, "mp", None)
            specs[lora_key(key, "A")] = P(None, None, "mp", None)
    return specs


def _qkv_head_permutation(num_heads, head_dim, tp):
    """Column permutation taking the fused qkv layout (3, NH, D) to
    (tp, 3, NH/tp, D): a contiguous 1/tp column slice then holds the
    q, k AND v projections of one head GROUP, so the plain 'mp' shard
    of the last weight dim is exactly one device's heads."""
    nhl = num_heads // tp
    return np.arange(3 * num_heads * head_dim).reshape(
        3, tp, nhl, head_dim).transpose(1, 0, 2, 3).reshape(-1)


def _lora_delta(p_l, key, x_t, slots_t):
    """Batched per-token adapter delta for one target GEMM: gather
    each token's [in, r] / [r, out] halves by its row's adapter slot,
    then two rank-r einsums — ``(x @ A_g) @ B_g`` with the alpha/rank
    scale pre-folded into the stored B.  Slot 0 is all-zero, so base
    rows (and dead warmup rows) contribute exact float zeros.  Under TP
    the halves carry their base GEMM's sharding (:func:`_block_specs`):
    column targets produce the local output shard directly, row
    targets produce a partial summed by the caller's psum."""
    a = p_l[lora_key(key, "A")][slots_t]      # [Tb, in, r]
    b_ = p_l[lora_key(key, "B")][slots_t]     # [Tb, r, out]
    h = jnp.einsum("ti,tir->tr", x_t, a)
    return jnp.einsum("tr,tro->to", h, b_)


class GPT2ServingModel:
    """A ``GPTForCausalLM``-compatible model (anything with
    ``functional_decompose``) as the engine runs it.  The engine takes
    the decomposed pytree ``{"embed", "blocks" (stacked [L, ...]),
    "head"}`` once (:meth:`take_params`), casts it, applies int8
    weights and adapter pools to :data:`GEMM_LEAVES`, and hands the
    result back through :meth:`shard_params`."""

    GEMM_LEAVES = GEMM_LEAVES

    def __init__(self, model, dtype):
        d = model.functional_decompose()
        cfg = model.config
        self._params = d["params"]
        self.num_layers = d["num_layers"]
        self.num_heads = cfg.num_attention_heads
        self.head_dim = cfg.head_dim
        self.hidden = cfg.hidden_size
        self.eps = cfg.layer_norm_epsilon
        self.vocab_size = int(cfg.vocab_size)  # noqa: H001 (config attr, not a tensor)
        # learned positions: the table's length bounds every sequence
        self.max_positions = int(cfg.max_position_embeddings)  # noqa: H001 (config attr, not a tensor)
        self.dtype = dtype
        self.tp = 1
        self._qkv_perm = None

    # ------------------------------------------------- layout and sizes --
    def take_params(self):
        """The decomposed parameters, handed over ONCE and not kept:
        the engine's cast (quantized, sharded) copy is the live one,
        and this object lives as long as the jitted step."""
        params, self._params = self._params, None
        return params

    def shard_params(self, params, tp):
        """Lay ``params`` out for ``tp`` shards: regroup the fused-qkv
        columns head-major so the contiguous 'mp' shard of the last dim
        is one device's (q, k, v) head group (bias and per-output-
        channel int8 scales ride their columns through the same
        regrouping).  The permutation is kept: adapter loads apply it
        to a qkv-target LoRA B half (:meth:`adapter_layout`)."""
        if self.num_heads % tp:
            raise ValueError(
                f"num_attention_heads {self.num_heads} not divisible by "
                f"tensor_parallel {tp} (head-axis sharding)")
        self.tp = tp
        if tp == 1:
            return params
        blocks = dict(params["blocks"])
        inter = blocks["mlp.fc_in.weight"].shape[-1]
        if inter % tp:
            raise ValueError(
                f"intermediate_size {inter} not divisible by "
                f"tensor_parallel {tp}")
        perm = self._qkv_perm = _qkv_head_permutation(
            self.num_heads, self.head_dim, tp)
        for key in (_QKV, _bias_key(_QKV), scale_key(_QKV)):
            if key in blocks:
                blocks[key] = blocks[key][..., perm]
        return {**params, "blocks": blocks}

    def adapter_layout(self, key, a, b):
        """One adapter's (A [L, in, r], B [L, r, out]) halves as the
        pools store them: a qkv target's B columns are base qkv
        columns, so they take the head-major regrouping too."""
        if key == _QKV and self._qkv_perm is not None:
            b = b[:, :, self._qkv_perm]
        return a, b

    def param_specs(self, params):
        """PartitionSpecs mirroring ``params`` (embed, head and the
        layernorms are replicated)."""
        specs = _block_specs()
        return {group: {k: specs.get(k, P()) if group == "blocks" else P()
                        for k in sub}
                for group, sub in params.items()}

    def params_bytes_per_chip(self, params):
        """Per-chip weight bytes under the layout :meth:`shard_params`
        set: leaves whose spec names 'mp' hold 1/tp of the global
        tensor, the rest are replicated."""
        total = 0
        specs = self.param_specs(params)
        for group, sub in params.items():
            for key, w in sub.items():
                nbytes = int(np.prod(w.shape)) * jnp.dtype(w.dtype).itemsize
                sharded = "mp" in tuple(specs[group][key])
                total += nbytes // self.tp if sharded else nbytes
        return total

    def cache_spec(self, block_size, quantized, mesh=None):
        """K and V of every head of every layer (no GQA in this
        block: Nkv == num_heads), heads over 'mp'."""
        return KVCacheSpec(self.num_layers, self.num_heads, block_size,
                           self.head_dim, self.dtype, quantized, mesh)

    def draft_params(self, params, draft_layers, shardings=None):
        """The draft model: the target's first ``draft_layers`` blocks
        followed by ZERO blocks.  With every leaf of a padded layer
        zeroed (weights AND biases), qkv is zero, so attention reads
        all-zero values, projection and MLP emit zero, and the residual
        stream passes through bit-exactly — a zeroed layer of THIS
        block is an exact identity.  Leaf shapes match the target's,
        so the draft rides the already-jitted step (params are its
        first operand) with ZERO new compiles; embed/head dicts are
        shared by reference."""
        dl = draft_layers
        blocks = {}
        for k, w in params["blocks"].items():
            if dl >= self.num_layers or k.startswith(LORA_PREFIX):
                # full-depth draft degenerates to the target; LoRA
                # pools are reused as-is — draft rows always pass
                # slot 0, the all-zero base identity, so stale pool
                # contents can never leak into a draft
                blocks[k] = w
                continue
            pad = jnp.concatenate([w[:dl], jnp.zeros_like(w[dl:])], axis=0)
            if shardings is not None:
                pad = jax.device_put(pad, shardings["blocks"][k])
            blocks[k] = pad
        return {**params, "blocks": blocks}

    # -------------------------------------------------------------- math --
    def _psum_mp(self, y):
        """Row-parallel reduction; identity on the single-device path
        (keeps the tp=1 graph bitwise identical to the pre-TP one)."""
        return jax.lax.psum(y, "mp") if self.tp > 1 else y

    def _wmat(self, p_l, key):
        """A GEMM's weight operand.  An int8 leaf (one with a
        ``<key>_scale`` sibling) dequantizes fused into the operand
        load: XLA folds the convert+multiply into the weight stream, so
        the matmul runs in the activation dtype while HBM pays 1
        byte/param (+ the per-column f32 scale row)."""
        sk = scale_key(key)
        if sk in p_l:
            return (p_l[key].astype(self.dtype)
                    * p_l[sk].astype(self.dtype))
        return p_l[key]

    @staticmethod
    def _adapted(p_l, key, y, x, slots_t):
        """``y`` plus the per-token adapter delta of GEMM ``key`` over
        its input ``x`` [1, Tb, in], where the engine holds an adapter
        pool for it."""
        if slots_t is None or lora_key(key, "A") not in p_l:
            return y
        return y + _lora_delta(p_l, key, x[0], slots_t)[None]

    def embed(self, params, ids, positions):
        emb = params["embed"]
        x = (emb["word_embeddings.weight"][ids]
             + emb["position_embeddings.weight"][positions])
        return x.astype(self.dtype)[None]            # [1, Tb, hidden]

    def attn_proj(self, p_l, x, slots_t=None):
        """LN -> fused QKV, the FusedMultiTransformer block head.
        Under TP the local qkv columns are this shard's head group
        (see _qkv_head_permutation), so num_heads / tp heads come out;
        a column-parallel LoRA target's (permuted) B columns shard like
        the base columns, so its delta IS the local shard — added
        before the head reshape."""
        hh = _layernorm(x, p_l["ln_1.weight"], p_l["ln_1.bias"], self.eps)
        qkv = hh @ self._wmat(p_l, _QKV) + p_l["attn.qkv.bias"]
        qkv = self._adapted(p_l, _QKV, qkv, hh, slots_t)
        b, t = x.shape[0], x.shape[1]
        qkv = qkv.reshape(b, t, 3, self.num_heads // self.tp,
                          self.head_dim)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    def mlp_residual(self, p_l, x, att_out, slots_t=None):
        """Row-parallel proj/fc_out: partial matmul + psum, bias added
        once AFTER the reduction (replicated).  A row-parallel LoRA
        delta is a PARTIAL too (A shards the input rows), so it joins
        the base partial INSIDE the psum — linearity keeps tp>1
        bit-identical to tp=1."""
        part = att_out @ self._wmat(p_l, "attn.proj.weight")
        part = self._adapted(p_l, "attn.proj.weight", part, att_out,
                             slots_t)
        x = x + self._psum_mp(part) + p_l["attn.proj.bias"]
        h2 = _layernorm(x, p_l["ln_2.weight"], p_l["ln_2.bias"], self.eps)
        pre = h2 @ self._wmat(p_l, "mlp.fc_in.weight") \
            + p_l["mlp.fc_in.bias"]
        pre = self._adapted(p_l, "mlp.fc_in.weight", pre, h2, slots_t)
        ff = jax.nn.gelu(pre, approximate=True)
        part = ff @ self._wmat(p_l, "mlp.fc_out.weight")
        part = self._adapted(p_l, "mlp.fc_out.weight", part, ff, slots_t)
        return x + self._psum_mp(part) + p_l["mlp.fc_out.bias"]

    def block(self, p_l, x, cache_l, slots, paged, slots_t=None):
        """One layer over one layer's cache view: project, write this
        step's K/V through the block tables (every query's K/V lands
        before attention reads), attend THROUGH the pool, then the
        residual MLP half.  Returns (x, the written view)."""
        q, k, v = self.attn_proj(p_l, x, slots_t)   # [1, Tb, nh_l, hd]
        cache_l = write_tokens(cache_l, slots, k[0], v[0])
        out = paged_ragged_attention(q[0], cache_l, *paged)
        out = out.astype(x.dtype).reshape(1, x.shape[1], -1)
        return self.mlp_residual(p_l, x, out, slots_t), cache_l

    def head(self, params, x):
        x = _layernorm(x, params["head"]["weight"],
                       params["head"]["bias"], self.eps)
        w = params["embed"]["word_embeddings.weight"]
        return x @ w.T.astype(self.dtype)

    def forward(self, params, ids, positions, cache, block_tables, rows,
                row_start, row_qlen, row_pos0, adapter_rows=None):
        """Logits [Tb, V] of one packed ragged token batch, and the
        cache with the batch's K/V written.  ids/positions/rows [Tb]
        and the [R] row descriptors are the engine's packing (see
        ``LLMEngine._build_step``); ``adapter_rows`` [R], on a LoRA
        engine, is each row's resident adapter slot, gathered to
        per-token slots through the same token->row map.  The layer
        scan carries the cache as ``xs``/``ys`` beside the stacked
        block params.

        Every per-element reduction (projections, attention scores,
        softmax, layernorm, head) matches the retired per-phase
        graphs', so outputs are bitwise the chunk/decode/verify steps
        the old engine ran — the retired decode/verify bodies'
        pre-scale dance (q times ``scale * sqrt(hd)``, exactly 1.0) is
        dropped outright."""
        x = self.embed(params, ids, jnp.maximum(positions, 0))
        num_blocks, block_size = cache["k"].shape[1], cache["k"].shape[3]
        slots, ctx = token_slots(block_tables, positions, rows,
                                 num_blocks, block_size)
        paged = (block_tables, ctx, rows, row_start, row_qlen, row_pos0)
        slots_t = None if adapter_rows is None else adapter_rows[rows]

        def layer(x, xs):
            p_l, cache_l = xs
            return self.block(p_l, x, cache_l, slots, paged, slots_t)

        x, cache = jax.lax.scan(layer, x, (params["blocks"], cache))
        return self.head(params, x[0]), cache
