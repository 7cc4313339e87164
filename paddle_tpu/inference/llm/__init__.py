"""paddle_tpu.inference.llm — continuous-batching LLM serving.

The serving-shaped subsystem over the round-4 ragged decode kernel:

- block_manager:  paged KV-cache allocator (free list, block tables,
                  refcounted fork / copy-on-write) with automatic
                  prefix caching (content-hash-addressed full pages,
                  LRU eviction of cached-but-unreferenced pages)
- scheduler:      iteration-level continuous batching with a per-step
                  token budget, chunked prefill mixed with decodes,
                  preempt-on-OOM and power-of-two shape bucketing
- paged_attention: block-table ragged attention dispatch — ONE entry
                  point (paged_ragged_attention) covers decode, verify,
                  and prefill-chunk rows via per-row descriptors
                  (Pallas ragged kernel on TPU, masked-XLA gather
                  fallback everywhere), against one layer's view of
                  the cache
- kv_cache:       the paged KV cache as ONE pytree ({k, v} and, under
                  int8 KV, {k_scale, v_scale}): allocation (device,
                  sharded, the simulator's numpy), token writes,
                  copy-on-write, page gather/scatter, 'mp' specs
- gpt2_block:     the ONE serving model — GPT-2's embed/block/head
                  over the cache, its parameter names, the table of
                  its four GEMMs (column-/row-parallel) every int8,
                  LoRA and tensor-parallel spec derives from, the
                  cache's shape, the draft model's construction
- spec:           model-free speculative decoding — prompt-lookup
                  n-gram drafter (NgramDrafter / SpeculativeConfig);
                  the engine scores K drafts + 1 bonus position per
                  sequence in one jitted verify step
- faults:         request-lifecycle vocabulary (FinishReason) and the
                  deterministic fault-injection harness (FaultInjector,
                  RetryPolicy, StepWatchdog) — seeded fault schedules
                  at the device-step / allocator / socket boundaries
- sampling:       the per-request sampling suite — a jit-compatible
                  per-row logits pipeline (top-k/top-p/min-p,
                  repetition/presence/frequency penalties, logit bias)
                  riding the one ragged executable as batched device
                  operands, plus host-side stop strings and logprobs
- structured:     grammar/JSON-constrained decoding — vocab masks
                  compiled per grammar state on the host, applied in
                  the device step through the sampling bias channel,
                  exact under speculative verify
- http_server:    HttpLLMServer — HTTP/SSE front end (beside the
                  socket PredictorServer) streaming token deltas with
                  the full sampling/constraint parameter set on the
                  wire, backed by an engine or a Fleet
- lora:           multi-LoRA serving — packed per-tenant adapter pools
                  (LoRAConfig / AdapterManager) batched through the one
                  ragged executable as a per-row slot gather + rank-r
                  einsum beside each block GEMM; host-LRU slot
                  load/evict with zero recompiles, slot 0 the exact
                  base-model identity
- interleave:     InterleavingScheduler — seeded deterministic
                  cooperative-checkpoint scheduler that drives the
                  AsyncLLMEngine / Fleet threads through adversarial
                  interleavings, replayable from its seed (the runtime
                  half of framework/concurrency_lint.py's R-rules)
- events:         the frozen, versioned event-log record schema
                  (named fields per kind, wall-clock-free by
                  construction) shared by engines, fleets and the
                  discrete-event simulator's calibration gate
- engine:         LLMEngine (add_request/step/generate, bucketed
                  donated jitted executables; ``tensor_parallel=N``
                  shards params Megatron-style and the paged pool along
                  the head axis over an 'mp' device mesh;
                  ``speculative=K`` adds the verify family;
                  ``abort_request``/``deadline_ms``/``max_queue``/
                  ``faults=`` for lifecycle hardening)
                  + AsyncLLMEngine for servers
- fleet:          Fleet — N engine replicas behind a prefix-affinity
                  Router with heartbeat health checking (HealthConfig
                  hysteresis), token-exact failover of a dead
                  replica's requests onto survivors, fleet-level
                  bounded admission and rolling drain/restart; the
                  replicas share one compiled executable set.  KV page
                  migration (MigrationPolicy) hands running sequences
                  between replicas mid-generation token-exactly —
                  drain and engine-alive failover migrate instead of
                  recomputing, and ``disaggregate=True`` splits
                  prefill-role from decode-role replicas with handoff
                  at the prefill→decode boundary

See docs/LLM_SERVING.md for design notes and a quickstart.
"""

from .block_manager import (  # noqa: F401
    BlockManager,
    NoFreeBlocksError,
    hash_block_tokens,
    prefix_block_hashes,
)
from .engine import AsyncLLMEngine, LLMEngine, RequestOutput  # noqa: F401
from .http_server import HttpLLMServer  # noqa: F401
from .lora import (  # noqa: F401
    LORA_TARGET_LEAVES,
    AdapterManager,
    LoRAConfig,
)
from .sampling import (  # noqa: F401
    FILTERED,
    StopStringWatcher,
    apply_logits_pipeline,
    neutral_row_params,
    token_counts,
    top_logprobs,
    validate_sampling,
)
from .structured import (  # noqa: F401
    ConstraintState,
    DfaTokenGrammar,
    Grammar,
    grammar_from_spec,
    json_array_grammar,
)
from .events import (  # noqa: F401
    EVENT_FIELDS,
    SCHEMA_VERSION,
    assert_wall_clock_free,
    to_records,
)
from .fleet import (  # noqa: F401
    Fleet,
    HealthConfig,
    MigrationPolicy,
    Replica,
    Router,
)
from .interleave import (  # noqa: F401
    InterleavingScheduler,
    interleave_point,
    interleave_wait,
)
from .faults import (  # noqa: F401
    Fault,
    FaultInjector,
    FinishReason,
    InjectedFault,
    MigrationError,
    PoolLostError,
    RetryPolicy,
    StepWatchdog,
)
from .paged_attention import (  # noqa: F401
    paged_ragged_attention,
    paged_ragged_attention_xla,
)
from .scheduler import (  # noqa: F401
    PrefillChunk,
    RaggedRow,
    Request,
    ScheduledBatch,
    Scheduler,
)
from .spec import (  # noqa: F401
    DraftModelDrafter,
    NgramDrafter,
    SpeculativeConfig,
    rollback_draft_reservation,
)

__all__ = ["BlockManager", "NoFreeBlocksError", "hash_block_tokens",
           "prefix_block_hashes", "Scheduler", "Request", "PrefillChunk",
           "RaggedRow", "ScheduledBatch", "LLMEngine", "AsyncLLMEngine",
           "RequestOutput", "HttpLLMServer",
           "LORA_TARGET_LEAVES", "AdapterManager", "LoRAConfig",
           "FILTERED", "StopStringWatcher", "apply_logits_pipeline",
           "neutral_row_params", "token_counts", "top_logprobs",
           "validate_sampling",
           "ConstraintState", "DfaTokenGrammar", "Grammar",
           "grammar_from_spec", "json_array_grammar",
           "DraftModelDrafter", "NgramDrafter", "SpeculativeConfig",
           "rollback_draft_reservation",
           "Fleet", "HealthConfig", "MigrationPolicy", "Replica", "Router",
           "InterleavingScheduler", "interleave_point", "interleave_wait",
           "Fault", "FaultInjector", "FinishReason", "InjectedFault",
           "MigrationError", "PoolLostError", "RetryPolicy", "StepWatchdog",
           "EVENT_FIELDS", "SCHEMA_VERSION", "assert_wall_clock_free",
           "to_records",
           "paged_ragged_attention", "paged_ragged_attention_xla"]
