"""Multi-LoRA serving — packed per-tenant adapter pools.

One base model serves many tenants in a single mixed batch: every
request carries an ``adapter_id``, and the jitted ragged step gathers a
per-token adapter slot through the existing token→row map and applies
``y += (x @ A_g) @ B_g`` as a batched rank-``r`` einsum beside each of
the four block GEMMs.  Nothing about the step graph depends on WHICH
adapters are resident — the pools are ordinary params leaves and the
slot indices are an ordinary int32 operand — so the single ragged
executable family stays intact (one extra replicated operand, zero
extra executables).

**Pool layout.**  The conceptual pool of the design is
``[A, L, in, r]`` / ``[A, L, r, out]`` (A adapter slots); on device it
is stored layer-major as stacked BLOCK leaves ``lora.<key>.A``
``[L, A, in, r]`` and ``lora.<key>.B`` ``[L, A, r, out]`` so the leaves
ride the same ``lax.scan`` over ``params["blocks"]`` as every base
weight.  Slot 0 is reserved and stays all-zero forever: it is the
EXACT base-model identity (``(x @ 0) @ 0`` contributes float zeros),
so requests with ``adapter_id=None`` — and the dead warmup rows — run
bit-identical to a LoRA-free engine.

**Sharding.**  Adapters shard with the Megatron 'mp' layout of their
base GEMM (``gpt2_block.GEMMS`` says which is which): a
column-parallel target splits its B pool on the output axis like the
base columns (A replicated), and a row-parallel target splits its A
pool on the input axis like the base rows (B replicated) — the partial per-device
deltas are summed by the SAME psum as the base partial products
(psum(base + delta) == psum(base) + psum(delta)), so tp=2 stays
bit-identical to tp=1.

**Load/evict.**  :class:`AdapterManager` is pure host bookkeeping: an
LRU over the device pool slots.  A slot swap is a host-staged
``device_get -> numpy row write -> device_put`` of the pool leaves
(the migration-path idiom) — no jit anywhere on the path, so an armed
CompileWatcher sees zero new compiles no matter how hot the eviction
churn runs.
"""
# noqa-module: H001 (the manager is host bookkeeping by design — slot
# assignment, LRU ticks, and registration shapes are python state; the
# device-side einsum lives in gpt2_block.py)

import numpy as np

__all__ = [
    "LORA_TARGET_LEAVES", "LoRAConfig", "AdapterManager", "lora_key",
    "init_adapter_pools",
]

LORA_PREFIX = "lora."


def _target_leaves():
    """The targetable leaves: the served block's GEMM table
    (gpt2_block.GEMMS) — the same set the int8 weight path quantizes,
    because they are the O(hidden^2) matmuls.  Read on use, not at
    import: gpt2_block.py imports this module."""
    from .gpt2_block import GEMM_LEAVES
    return GEMM_LEAVES


def __getattr__(name):
    if name == "LORA_TARGET_LEAVES":
        return _target_leaves()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def lora_key(key, side):
    """Pool-leaf name for a target GEMM: ``lora.<key>.A`` / ``.B``."""
    return f"{LORA_PREFIX}{key}.{side}"


class LoRAConfig:
    """Resolved form of ``LLMEngine(lora=)``.

    Accepts ``None`` (off), an int (``max_adapters`` with default
    rank), a dict (keyword form), or another LoRAConfig.

    ``max_adapters`` counts device POOL SLOTS including the reserved
    all-zero base slot 0, so it must be >= 2 and the engine can hold at
    most ``max_adapters - 1`` distinct adapters resident at once (the
    scheduler's admission gate).  ``alpha`` defaults to ``rank`` (scale
    1.0); the ``alpha / rank`` scale is folded into the stored B half
    at registration so the jitted step never multiplies by it.
    ``tenant_quota`` bounds live same-adapter requests at admission —
    the per-tenant fairness knob on top of bounded admission/shed."""

    def __init__(self, rank=8, max_adapters=8, targets=None, alpha=None,
                 tenant_quota=None):
        self.rank = int(rank)
        if self.rank < 1:
            raise ValueError(f"lora rank must be >= 1, got {rank!r}")
        self.max_adapters = int(max_adapters)
        if self.max_adapters < 2:
            raise ValueError(
                f"lora max_adapters must be >= 2 (slot 0 is the "
                f"reserved base-model identity), got {max_adapters!r}")
        leaves = _target_leaves()
        # None: every GEMM of the served block
        targets = leaves if targets is None else tuple(targets)
        bad = [t for t in targets if t not in leaves]
        if bad or not targets:
            raise ValueError(
                f"lora targets must be a non-empty subset of "
                f"{leaves}, got {targets!r}")
        # canonical order (the base-leaf order), deduped
        self.targets = tuple(t for t in leaves if t in targets)
        self.alpha = float(alpha) if alpha is not None \
            else float(self.rank)
        self.tenant_quota = None if tenant_quota is None \
            else int(tenant_quota)
        if self.tenant_quota is not None and self.tenant_quota < 1:
            raise ValueError(
                f"lora tenant_quota must be >= 1 or None, "
                f"got {tenant_quota!r}")

    @property
    def scale(self):
        return self.alpha / self.rank

    @classmethod
    def resolve(cls, spec):
        if spec is None:
            return None
        if isinstance(spec, cls):
            return spec
        if isinstance(spec, bool):
            raise TypeError(
                "lora= accepts None, an int (max_adapters), a dict, "
                "or a LoRAConfig; got a bool")
        if isinstance(spec, int):
            return cls(max_adapters=spec)
        if isinstance(spec, dict):
            return cls(**spec)
        raise TypeError(
            f"lora= accepts None, an int (max_adapters), a dict, or "
            f"a LoRAConfig; got {type(spec)}")

    def __repr__(self):
        return (f"LoRAConfig(rank={self.rank}, "
                f"max_adapters={self.max_adapters}, "
                f"targets={self.targets}, alpha={self.alpha}, "
                f"tenant_quota={self.tenant_quota})")


def init_adapter_pools(blocks, config, dtype):
    """Zero pool leaves for the stacked block params.

    Reads each target's [L, in, out] base-weight shape (shape-stable
    under int8 weight quantization — the int8 leaf keeps the float
    leaf's shape) and returns ``{lora.<key>.A: zeros[L, A, in, r],
    lora.<key>.B: zeros[L, A, r, out]}``.  All-zero pools make EVERY
    slot the base identity until an adapter is loaded into it."""
    import jax.numpy as jnp

    out = {}
    for key in config.targets:
        L, d_in, d_out = blocks[key].shape
        out[lora_key(key, "A")] = jnp.zeros(
            (L, config.max_adapters, d_in, config.rank), dtype)
        out[lora_key(key, "B")] = jnp.zeros(
            (L, config.max_adapters, config.rank, d_out), dtype)
    return out


class AdapterManager:
    """Host-side adapter registry + LRU over the device pool slots.

    ``register`` validates and keeps a host copy of each adapter's
    stacked A/B halves (the ``alpha/rank`` scale folded into B);
    ``acquire`` maps an adapter_id to a resident slot, evicting the
    least-recently-used non-pinned resident when the pool is full.
    The manager never touches the device — the engine performs the
    actual slot write when ``acquire`` reports a load is needed —
    which is what keeps failover/restart cheap: re-registering the
    host copies fully reconstitutes a rebuilt replica."""

    _BASE = object()          # sentinel occupying reserved slot 0

    def __init__(self, config, shapes):
        self.config = config
        # target key -> (L, d_in, d_out) expected base-weight dims
        self._shapes = dict(shapes)
        self._adapters = {}   # adapter_id -> {key: (A f32, B f32)}
        self._slot_of = {}    # adapter_id -> resident slot
        self._slots = [None] * config.max_adapters
        self._slots[0] = self._BASE
        self._tick = 0        # LRU clock
        self._last_used = {}  # adapter_id -> tick
        self.stats = {"loads": 0, "evictions": 0, "hits": 0}

    # -- registry ------------------------------------------------------
    def known(self, adapter_id):
        return adapter_id in self._adapters

    def ids(self):
        return sorted(self._adapters, key=repr)

    def register(self, adapter_id, weights):
        """Validate and store one adapter's stacked halves.

        ``weights`` maps every configured target key to ``(A, B)``
        arrays of shape [L, in, r] / [L, r, out].  Stored as float32
        numpy host copies with the LoRA scale folded into B."""
        if adapter_id is None:
            raise ValueError(
                "adapter_id None is the implicit base model — it "
                "cannot be registered")
        try:
            hash(adapter_id)
        except TypeError:
            raise ValueError(
                f"adapter_id must be hashable, got "
                f"{type(adapter_id).__name__}")
        if adapter_id in self._adapters:
            raise ValueError(
                f"adapter {adapter_id!r} is already registered")
        missing = [k for k in self.config.targets if k not in weights]
        extra = [k for k in weights if k not in self.config.targets]
        if missing or extra:
            raise ValueError(
                f"adapter {adapter_id!r} must provide exactly the "
                f"configured targets {self.config.targets}; "
                f"missing={missing} extra={extra}")
        stored = {}
        r = self.config.rank
        for key in self.config.targets:
            L, d_in, d_out = self._shapes[key]
            a, b = weights[key]
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            if a.shape != (L, d_in, r) or b.shape != (L, r, d_out):
                raise ValueError(
                    f"adapter {adapter_id!r} target {key!r}: expected "
                    f"A{(L, d_in, r)} / B{(L, r, d_out)}, got "
                    f"A{a.shape} / B{b.shape}")
            stored[key] = (a, b * np.float32(self.config.scale))
        self._adapters[adapter_id] = stored

    # -- residency -----------------------------------------------------
    def slot_of(self, adapter_id):
        """Resident slot for an adapter, or None (slot 0 for base)."""
        if adapter_id is None:
            return 0
        return self._slot_of.get(adapter_id)

    def resident(self):
        return dict(self._slot_of)

    def acquire(self, adapter_id, pinned=()):
        """Map an adapter_id to a resident slot.

        Returns ``(slot, weights)`` where ``weights`` is None when the
        adapter is already resident (LRU hit) and the host copy to
        write into the slot otherwise.  ``pinned`` adapters (the ones
        a launch is about to index) are never evicted; the scheduler's
        distinct-adapter admission gate guarantees the pinned set
        always fits, so a full pool always has an evictable victim."""
        if adapter_id is None:
            return 0, None
        if adapter_id not in self._adapters:
            raise ValueError(f"unknown adapter {adapter_id!r}")
        self._tick += 1
        slot = self._slot_of.get(adapter_id)
        if slot is not None:
            self._last_used[adapter_id] = self._tick
            self.stats["hits"] += 1
            return slot, None
        slot = next((s for s in range(1, self.config.max_adapters)
                     if self._slots[s] is None), None)
        if slot is None:
            pinned = set(pinned)
            victims = [aid for aid in self._slot_of
                       if aid not in pinned]
            if not victims:
                raise RuntimeError(
                    f"no evictable adapter slot: all "
                    f"{self.config.max_adapters - 1} slots are pinned "
                    f"by the current batch (the admission gate should "
                    f"make this unreachable)")
            victim = min(victims,
                         key=lambda aid: self._last_used.get(aid, 0))
            slot = self._slot_of.pop(victim)
            self._slots[slot] = None
            self.stats["evictions"] += 1
        self._slots[slot] = adapter_id
        self._slot_of[adapter_id] = slot
        self._last_used[adapter_id] = self._tick
        self.stats["loads"] += 1
        return slot, self._adapters[adapter_id]

    def lora_stats(self):
        return {**self.stats, "registered": len(self._adapters),
                "resident": len(self._slot_of),
                "slots": self.config.max_adapters}
