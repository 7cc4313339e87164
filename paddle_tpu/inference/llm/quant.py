"""Serving-side int8 quantization — weights and the paged K/V pool.

Two independent halves behind one ``LLMEngine(quantize=)`` knob:

- **Weight-only int8 GEMM**: the block matmul leaves of the stacked
  params (the served block's GEMM table, ``gpt2_block.GEMMS``) are
  stored int8 with
  per-output-channel float32 scales as sibling leaves
  (``<key>_scale``, shape [L, 1, out]).  Dequant happens at the GEMM
  operand load in the activation dtype — XLA fuses the
  ``int8 -> dtype * scale`` chain into the matmul's weight stream, so
  the HBM traffic for weights is 1 byte/param.  The scale leaves ride
  the same Megatron PartitionSpecs as their weights: a column-parallel
  weight's per-column scales shard with the columns, a row-parallel
  weight's scales are replicated (its output axis is not sharded), so
  ``shard(q) * scale`` is exactly the shard of the dequantized weight
  and tp>1 stays bit-identical to dequant-then-shard.

- **Int8 paged K/V pool**: the pool stores int8 slots with one float32
  scale per (layer, page, head, slot) — quantization happens at append
  time per WRITTEN token row (absmax over head_dim / 127), so a page
  never needs requantizing, and dequant happens at read time inside
  the ragged attention kernel (Pallas) or its masked-XLA fallback.
  A slot costs head_dim + 4 bytes instead of head_dim * itemsize.

Weight-only int8 is exact in the serving sense people expect (the
matmul still runs in the activation dtype); int8 KV is approximate —
outputs are NOT token-exact vs the full-precision engine, which is why
``quality.py`` exists (perplexity + top-k agreement gates).
"""

import jax.numpy as jnp

QMAX = 127.0
# smallest representable scale: keeps all-zero rows well-defined
# (q = 0 / eps = 0) without ever dividing by zero
_EPS = 1e-9

def __getattr__(name):
    """``QUANT_BLOCK_LEAVES``: the stacked-block weight leaves that
    quantize — the served block's GEMM table (gpt2_block.GEMMS), the
    O(hidden^2) matmuls; embeddings (tied to the head gather),
    layernorms and biases stay in the activation dtype.  Read on use,
    not at import: gpt2_block.py imports this module."""
    if name == "QUANT_BLOCK_LEAVES":
        from .gpt2_block import GEMM_LEAVES
        return GEMM_LEAVES
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def scale_key(key):
    """Sibling leaf name holding a quantized weight's dequant scales."""
    return key + "_scale"


class ServingQuantConfig:
    """Resolved form of ``LLMEngine(quantize=)``.

    Accepts ``None`` (off), the string ``"int8"`` (weights + KV pool),
    a dict (``{"weights": bool, "kv_cache": bool}``), another
    ServingQuantConfig, or a :class:`paddle_tpu.quantization.QuantConfig`
    (the QAT/PTQ config object — serving reads it as "quantize the
    weights int8"; its per-layer quanter choices are a training-side
    concern)."""

    def __init__(self, weights=True, kv_cache=True, bits=8):
        if int(bits) != 8:
            raise ValueError(
                f"serving quantization is int8-only, got bits={bits!r}")
        self.weights = bool(weights)
        self.kv_cache = bool(kv_cache)
        self.bits = 8
        if not (self.weights or self.kv_cache):
            raise ValueError(
                "quantize= resolved to a no-op config (weights=False, "
                "kv_cache=False) — pass None to disable quantization")

    @classmethod
    def resolve(cls, spec):
        if spec is None:
            return None
        if isinstance(spec, cls):
            return spec
        if isinstance(spec, str):
            if spec.lower() != "int8":
                raise ValueError(
                    f"unknown quantize= mode {spec!r} (only 'int8')")
            return cls()
        if isinstance(spec, dict):
            return cls(**spec)
        # duck-typed QuantConfig (quantization/__init__.py): weight-only
        if hasattr(spec, "factory_for"):
            return cls(weights=True, kv_cache=True)
        raise TypeError(
            f"quantize= accepts None, 'int8', a dict, a "
            f"ServingQuantConfig, or a QuantConfig; got {type(spec)}")

    def __repr__(self):
        return (f"ServingQuantConfig(weights={self.weights}, "
                f"kv_cache={self.kv_cache}, bits={self.bits})")


def quantize_weight(w):
    """Per-output-channel symmetric int8: ``w`` [..., in, out] ->
    (int8 qweight, float32 scales [..., 1, out]) with
    ``q * s ~= w``.  The absmax runs over the INPUT axis so each output
    column owns one scale — the layout that survives both Megatron
    shardings (see module docstring)."""
    w32 = jnp.asarray(w, jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w32), axis=-2, keepdims=True),
                    _EPS) / QMAX
    q = jnp.clip(jnp.round(w32 / s), -QMAX, QMAX).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def quantize_block_weights(blocks, keys):
    """Quantize the GEMM leaves ``keys`` of the stacked block params in
    place (a copy), adding ``<key>_scale`` sibling leaves."""
    out = dict(blocks)
    for key in keys:
        q, s = quantize_weight(out[key])
        out[key] = q
        out[scale_key(key)] = s
    return out


def quantize_kv_rows(values):
    """Quantize K/V rows at append time: ``values`` [..., D] ->
    (int8 [..., D], float32 scales [...]) — one symmetric absmax scale
    per (token, head) row.  All-zero rows quantize to exact zeros."""
    v32 = values.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(v32), axis=-1), _EPS) / QMAX
    q = jnp.clip(jnp.round(v32 / s[..., None]), -QMAX, QMAX)
    return q.astype(jnp.int8), s.astype(jnp.float32)


def dequantize_kv_rows(q, s):
    """Read-side inverse of :func:`quantize_kv_rows` (float32)."""
    return q.astype(jnp.float32) * s[..., None].astype(jnp.float32)
