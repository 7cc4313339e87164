"""LLMEngine — continuous-batching generation over a paged KV cache.

The engine schedules, packs, owns the pools and launches; WHAT it
launches — the served model's math, parameter names and tensor-parallel
layout — is gpt2_block.py's, and the KV cache is kv_cache.py's one
pytree ([L, num_blocks, Nkv, block_size, D] per K and V — head-major,
the layout the ragged Pallas kernel's page block needs — plus scale
pools under int8 KV) shared by every in-flight request, so the engine
runs MANY requests of ragged lengths through exactly ONE family of
jitted executables:

- ragged: the step's query tokens — prefill chunks, plain decodes, and
  speculative-verify rows alike — packed back-to-back into one flat
  token batch padded to a power-of-two TOKEN bucket (floor 8, cap
  token_budget), with per-row ``(query_start, query_len, context_len)``
  descriptors (scheduler.RaggedRow) saying which tokens belong to whom.
  Each token writes its K/V through its row's block table and attends
  over every earlier position THROUGH THE POOL (prior chunks and
  prefix-cache hits are read back, not recomputed; on TPU the Pallas
  ragged kernel, masked XLA gather elsewhere).  A decode row is a
  one-token chunk; a verify row carries its n-gram DRAFT tokens (see
  spec.py) plus one bonus position, with greedy acceptance (longest
  draft prefix matching the target argmax) keeping speculative output
  bitwise identical to plain decode; a prefill chunk's final slice
  yields the request's first generated token.  The executable family
  is O(log token_budget) — it grows with neither prompt length, batch
  size, nor draft depth, and one device step genuinely MIXES phases:
  decodes keep flowing inside the same launch that advances a long
  prompt's chunks.

Prefix caching rides on the block manager: every page a sequence
completes is registered under its prefix-chain hash, and admission
adopts matching pages at zero compute.

The executable donates the cache buffers (the pool is updated in place
in HBM) and contains no host round-trip between launch and the sampled
token ids — the only sync is fetching the step's token vector to drive
the scheduler (plus the logits ROWS of requests that actually sample;
greedy-only batches transfer exactly the per-token argmax vector).
Compiles are bounded by the token buckets; steady-state serving reuses
warm executables regardless of traffic mix.

Tensor parallelism (``mesh=`` / ``tensor_parallel=``): the same
executable spans a device mesh with an ``'mp'`` axis.  Params shard
Megatron-style — qkv/fc_in column-parallel, proj/fc_out row-parallel
with an explicit psum — and the paged K/V pools shard along the HEAD
axis ([L, NB, Nkv/mp, bs, D] per device), so each device runs its head
slice of paged_ragged_attention against its LOCAL pool shard.
The whole step body runs under ``jax.shard_map`` (the paged Pallas
kernels index the pool through scalar-prefetched block tables, which
GSPMD cannot partition, so the kernel always sees a fully local pool),
jitted with NamedSharding ``in_shardings``/``out_shardings`` and the
same cache donation.  Host-side scheduling is UNCHANGED: one scheduler
and one BlockManager drive every shard, block tables / token ids /
positions ride replicated, and page accounting is therefore
shard-invariant by construction (asserted every step in TP mode).
Activations stay replicated between the two psums per layer — at these
batch sizes the win is HBM: the pool and the qkv/mlp weights split mp
ways, serving models whose KV pool doesn't fit one chip.
"""

import threading
import time
import warnings

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ... import profiler
from .block_manager import BlockManager, NoFreeBlocksError
from .faults import (
    FinishReason,
    InjectedFault,
    MigrationError,
    PoolLostError,
    RetryPolicy,
    StepWatchdog,
)
from .interleave import interleave_point, interleave_wait, masked
from .gpt2_block import GPT2ServingModel
from .kv_cache import PAYLOAD_KEYS, copy_pages, gather_pages, scatter_pages
from .kv_tier import KVTierConfig
from .lora import AdapterManager, LoRAConfig, init_adapter_pools, lora_key
from .quant import ServingQuantConfig, quantize_block_weights
from .sampling import (
    StopStringWatcher,
    apply_logits_pipeline,
    neutral_row_params,
    token_counts,
    top_logprobs,
    validate_sampling,
)
from .scheduler import (
    FINISHED,
    RUNNING,
    WAITING,
    RaggedRow,
    Request,
    Scheduler,
    bucket_size,
)
from .structured import ConstraintState
from .spec import (
    DraftModelDrafter,
    NgramDrafter,
    SpeculativeConfig,
    rollback_draft_reservation,
)

class RequestOutput:
    """One finished request: ids are plain python/numpy on the host.

    ``finish_reason`` is one of :class:`~.faults.FinishReason.ALL`;
    ``ok`` is True for the "done" family (stop/length) — aborted,
    deadline-missed, shed, and quarantined requests carry a truncated
    (possibly empty) ``output_ids`` and, for ``error``, the failing
    step's message in ``error``."""

    def __init__(self, request_id, prompt_ids, output_ids, finish_reason,
                 num_preemptions, error=None, logprobs=None,
                 matched_stop=None):
        self.request_id = request_id
        self.prompt_ids = np.asarray(prompt_ids)  # noqa: H001 (host output contract)
        self.output_ids = np.asarray(output_ids)  # noqa: H001 (host output contract)
        self.finish_reason = finish_reason
        self.num_preemptions = num_preemptions
        self.error = error
        # per-token [(chosen_logprob, [(tid, lp), ...]), ...] when the
        # request asked for logprobs=N; the stop string that ended a
        # stop-string finish (None otherwise)
        self.logprobs = logprobs
        self.matched_stop = matched_stop

    @property
    def ok(self):
        return FinishReason.is_done(self.finish_reason)

    @property
    def all_ids(self):
        if self.output_ids.size == 0:    # shed/aborted before any token
            return np.array(self.prompt_ids)
        return np.concatenate([self.prompt_ids, self.output_ids])


class LLMEngine:
    """add_request()/step()/generate() over a GPTForCausalLM-compatible
    model (anything with ``functional_decompose``).

    >>> eng = LLMEngine(model, block_size=16, max_batch=8)
    >>> rid = eng.add_request([5, 6, 7], max_new_tokens=16)
    >>> while eng.has_unfinished():
    ...     for out in eng.step():
    ...         print(out.request_id, out.output_ids)

    ``tensor_parallel=N`` (or an explicit ``mesh=`` with an 'mp' axis)
    shards the executables over N devices — see the module docstring.
    ``seed=`` seeds the sampling RNG (temperature > 0); per-request
    ``seed=`` in add_request overrides it with an independent stream.
    ``speculative=K`` (or a SpeculativeConfig / dict) turns on n-gram
    speculative decoding with up to K draft tokens per sequence per
    step — same tokens, fewer device steps on repetitive output.
    ``memory_budget=`` (bytes, or '16GiB'-style) declares the per-chip
    HBM capacity: the admissible ``max_batch`` is then derived from the
    static pages+weights model (framework.cost) and clamps the
    requested one, the defaulted page pool is sized to the clamped
    batch, and ``graph-lint cost`` flags any bucket whose estimated
    peak exceeds the budget (M001).
    ``quantize="int8"`` (or a dict / ServingQuantConfig / QuantConfig)
    turns on int8 serving: the four block GEMM weights store int8 with
    per-output-channel scales dequantized at the operand load, and the
    paged K/V pool stores int8 slots with per-(page, head, slot)
    scales dequantized inside the ragged attention kernel.  Both
    residency terms shrink, so under a ``memory_budget=`` the derived
    admissible max_batch grows (see inference/llm/quant.py); int8 KV
    output is approximate — quality.py measures the delta.
    ``lora=LoRAConfig(rank, max_adapters, targets)`` (or a dict / int)
    turns on multi-LoRA serving: the engine holds packed adapter pools
    (slot 0 the exact base-model identity), requests carry
    ``adapter_id=`` (register with :meth:`add_adapter` first), and the
    jitted step applies each row's adapter as a batched rank-r einsum
    beside the four block GEMMs — one extra int32 operand, zero extra
    executables (see inference/llm/lora.py).
    """

    def __init__(self, model, *, block_size=16, num_blocks=None,
                 max_model_len=None, max_batch=8, dtype=None,
                 enable_prefix_caching=True, token_budget=64,
                 mesh=None, tensor_parallel=None, seed=None,
                 speculative=None, memory_budget=None, quantize=None,
                 lora=None, faults=None, retry=None, max_queue=None,
                 step_timeout_s=None, clock=None,
                 record_step_gauges=False, detokenizer=None,
                 lookahead=False, kv_tier=None):
        # ----------------------------------------- lifecycle hardening ----
        # validate the robustness knobs FIRST (mirrors max_new_tokens):
        # a bad config must fail loudly at construction, not mid-traffic
        if max_queue is not None:
            if not isinstance(max_queue, (int, np.integer)) \
                    or isinstance(max_queue, bool) or max_queue < 1:
                raise ValueError(
                    f"max_queue must be a positive int (waiting-queue "
                    f"depth before load-shedding), got {max_queue!r}")
            max_queue = int(max_queue)
        self.max_queue = max_queue
        self.faults = faults
        self.retry = RetryPolicy.resolve(retry)
        if step_timeout_s is not None:
            if isinstance(step_timeout_s, bool) or \
                    not isinstance(step_timeout_s,
                                   (int, float, np.integer, np.floating)) \
                    or step_timeout_s <= 0:
                raise ValueError(
                    f"step_timeout_s must be a positive number of "
                    f"seconds, got {step_timeout_s!r}")
        self._clock = clock if clock is not None else time.monotonic
        # step timing, retry backoff, and the watchdog share the
        # injected clock when one is given (a simulator's VirtualClock
        # makes backoff and wedge detection cost VIRTUAL seconds);
        # wall serving keeps perf_counter / time.sleep
        self._timer = clock if clock is not None else time.perf_counter
        self._sleep = getattr(clock, "sleep", time.sleep)
        if self.faults is not None:
            # injected "delay" faults stall on the same clock the
            # watchdog measures with — virtual delays trip a virtual
            # watchdog without any wall waiting
            self.faults.sleep = self._sleep
        self.watchdog = (StepWatchdog(step_timeout_s, clock=self._timer)
                         if step_timeout_s is not None else None)
        self._early = []         # outputs finished without a device step
        self._draining = False
        self._step_index = -1
        self._last_step_ms = None   # wall ms of the latest step() (gauge)
        # deterministic lifecycle event log: (step, kind, *detail)
        # tuples with no wall-times, so two replays of the same fault
        # seed produce IDENTICAL logs (the chaos determinism contract);
        # events.py freezes the per-kind record schema
        self.events = []
        # (kind, bucket) of every executable launch the CURRENT step
        # issued — the simulator's virtual clock advances by the cost
        # model's estimate of exactly these launches
        self.last_launches = []
        # opt-in per-step cumulative lifecycle gauges (lifecycle_stats)
        self.record_step_gauges = bool(record_step_gauges)
        self.step_gauges = []

        self.dtype = jnp.dtype(dtype) if dtype else jnp.float32
        # what is served: the model's math, parameter names and 'mp'
        # layout are the serving model's (gpt2_block.py); the engine
        # keeps the sizes its scheduling and memory math read
        sm = self.serving_model = GPT2ServingModel(model, self.dtype)
        self.num_layers = sm.num_layers
        self.num_heads = sm.num_heads
        self.head_dim = sm.head_dim
        self.hidden = sm.hidden
        self.eps = sm.eps
        self.vocab_size = sm.vocab_size
        # ids -> text, for stop-string matching (sampling.py); requests
        # carrying stop= are rejected up front when no detokenizer is
        # configured, so the failure is a loud add_request ValueError
        if detokenizer is not None and not callable(detokenizer):
            raise ValueError(
                f"detokenizer must be a callable(ids) -> str, "
                f"got {detokenizer!r}")
        self.detokenizer = detokenizer
        self.block_size = int(block_size)
        self.max_batch = int(max_batch)
        self.max_model_len = int(min(max_model_len or sm.max_positions,  # noqa: H001 (static config int)
                                     sm.max_positions))
        self.max_pages = -(-self.max_model_len // self.block_size)
        # int8 serving (None | "int8" | dict | ServingQuantConfig |
        # QuantConfig): weight-only int8 GEMM and/or the int8 KV pool
        self.quant = ServingQuantConfig.resolve(quantize)
        self._w_quant = bool(self.quant and self.quant.weights)
        self._kv_quant = bool(self.quant and self.quant.kv_cache)
        # multi-LoRA serving (None | int | dict | LoRAConfig): packed
        # per-tenant adapter pools applied inside the ragged step
        self.lora = LoRAConfig.resolve(lora)
        # speculative decoding (None | K | method str | dict |
        # SpeculativeConfig): an n-gram drafter — or, for
        # method="draft-model"/"tree", the hybrid model-based drafter
        # whose params/pools come up in _init_draft_model below — plus
        # the bucketed verify executable family
        self.spec = SpeculativeConfig.resolve(speculative)
        if self.spec is None:
            self.drafter = None
        elif self.spec.uses_draft_model:
            self.drafter = DraftModelDrafter(self.spec)
        else:
            self.drafter = NgramDrafter(self.spec)
        # async lookahead: while step N's launch runs on device, plan
        # and pack step N+1's operands (see _stage_next/_claim_staged)
        self.lookahead = bool(lookahead)
        self._staged = None          # (plan_rows, packed operands)
        self._staged_epoch = -1
        self._plan_epoch = 0         # bumped by every plan-invalidating
                                     # lifecycle mutation
        # timing gauges are read cross-thread (Fleet._beat health checks,
        # fleet lifecycle_stats) while the stepping thread writes them, so
        # they get their own leaf lock; everything else in the engine stays
        # single-threaded by the AsyncLLMEngine contract.  Never block or
        # take another lock while holding it (R002/R003).
        self._gauge_lock = threading.Lock()
        self._host_plan_s = 0.0      # critical-path schedule+pack time
        self._step_wall_s = 0.0      # total step() wall time
        self._launch_count = 0

        # ------------------------------------------------ mesh resolution --
        if mesh is None and tensor_parallel and int(tensor_parallel) > 1:
            devs = jax.devices()
            if int(tensor_parallel) > len(devs):
                raise ValueError(
                    f"tensor_parallel={tensor_parallel} exceeds the "
                    f"{len(devs)} visible devices")
            mesh = Mesh(np.array(devs[:int(tensor_parallel)]), ("mp",))
        if mesh is not None and "mp" not in mesh.axis_names:
            raise ValueError("serving mesh needs an 'mp' axis "
                             f"(got axes {mesh.axis_names})")
        self.tp = int(mesh.shape["mp"]) if mesh is not None else 1
        if tensor_parallel is not None and mesh is not None and \
                int(tensor_parallel) != self.tp:
            raise ValueError(
                f"tensor_parallel={tensor_parallel} disagrees with the "
                f"mesh 'mp' extent {self.tp}")
        self.mesh = mesh if self.tp > 1 else None

        cast = (lambda x: jnp.asarray(x, self.dtype)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                else jnp.asarray(x))
        params = jax.tree_util.tree_map(cast, sm.take_params())
        if self._w_quant:
            # int8 weight storage BEFORE the budget math below, so the
            # admissible-batch derivation prices 1 byte/param (+ the
            # f32 per-output-channel scale leaves) for the block GEMMs
            params = dict(params)
            params["blocks"] = quantize_block_weights(
                params["blocks"], sm.GEMM_LEAVES)
        self._lora_mgr = None
        if self.lora is not None:
            # adapter pools join the BLOCK leaves before the budget
            # math below, so adapter residency is priced into the
            # admissible-batch derivation and the memory model (M001);
            # zero pools make every slot the base identity until an
            # adapter is loaded, and they scan with params["blocks"]
            params = dict(params)
            params["blocks"] = dict(params["blocks"])
            self._lora_shapes = {
                k: tuple(params["blocks"][k].shape)
                for k in self.lora.targets}
            params["blocks"].update(init_adapter_pools(
                params["blocks"], self.lora, self.dtype))
            self._lora_mgr = AdapterManager(self.lora,
                                            self._lora_shapes)
        # the 'mp' layout (adapter pools start zero: nothing of theirs
        # to lay out yet) and the cache the block computes on
        params = sm.shard_params(params, self.tp)
        self._param_specs = sm.param_specs(params)
        self.kv_spec = sm.cache_spec(self.block_size, self._kv_quant,
                                     self.mesh)

        # ---------------------------------------------- HBM budget --------
        # pages + weights bound max_batch (ROADMAP item 3): under a
        # declared per-chip budget the admissible batch is derived from
        # the static memory model, and the defaulted page pool is sized
        # for THAT batch so the pool itself cannot overrun the budget.
        from ...framework.cost import derive_max_batch, parse_bytes
        self.memory_budget = parse_bytes(memory_budget)
        weights_per_chip = sm.params_bytes_per_chip(params)
        # per-chip K+V bytes of one page — the migration cost model's
        # bytes-moved unit (global payload = page_bytes * tp)
        page_bytes = self.page_bytes = self.kv_spec.page_bytes(self.tp)
        if self.memory_budget is not None:
            seq_bytes = self.max_pages * page_bytes
            admissible = derive_max_batch(self.memory_budget,
                                          weights_per_chip, seq_bytes)
            if self.max_batch > admissible:
                self.max_batch = admissible
        if num_blocks is None:
            # default: the full batch at full length fits -> no preemption
            num_blocks = self.max_batch * self.max_pages
        if num_blocks < self.max_pages:
            raise ValueError(
                f"num_blocks {num_blocks} cannot hold one max_model_len "
                f"sequence ({self.max_pages} pages)")
        self.num_blocks = int(num_blocks)
        if self.memory_budget is not None and \
                weights_per_chip + self.num_blocks * page_bytes \
                > self.memory_budget:
            raise ValueError(
                f"num_blocks {self.num_blocks} puts the per-chip paged "
                f"pool ({self.num_blocks * page_bytes} bytes) plus "
                f"weights ({weights_per_chip} bytes) over "
                f"memory_budget {self.memory_budget}")
        # one decode token per running sequence must fit in the budget
        self.token_budget = max(int(token_budget), self.max_batch)

        self.block_manager = BlockManager(
            self.num_blocks, self.block_size,
            enable_prefix_caching=enable_prefix_caching)
        self.block_manager.fault_hook = self.faults
        self.scheduler = Scheduler(self.block_manager,
                                   max_batch=self.max_batch,
                                   token_budget=self.token_budget,
                                   drafter=self.drafter,
                                   lora_slots=(
                                       self.lora.max_adapters - 1
                                       if self.lora is not None
                                       else None))
        # ------------------------------------------- hierarchical KV ------
        # kv_tier= (None | bytes | dict | KVTierConfig) attaches the
        # host-RAM page tier (kv_tier.py): preemption demotes chains to
        # a bounded host pool instead of discarding them, re-admission
        # swaps them back in instead of re-prefilling, and full pages
        # evicted from the HBM prefix cache promote into a content-
        # addressed host store any engine sharing it (a Fleet) can
        # adopt from.  A TierPolicy prices swap bytes vs replay FLOPs.
        self.kv_tier = KVTierConfig.resolve(kv_tier)
        self.host_pool = None
        self.prefix_store = None
        self.tier_policy = None
        # host bytes moved by THIS step's tier traffic (demote + swap +
        # promote + store adoption) — the simulator's virtual clock
        # charges the step-time model's link term for exactly these
        self.last_tier_bytes = 0
        if self.kv_tier is not None:
            self.host_pool, self.prefix_store = self.kv_tier.build()
            self.tier_policy = self.kv_tier.policy
            if self.host_pool is not None:
                self.scheduler.demote_hook = self._tier_demote
                self.scheduler.swap_in_hook = self._tier_swap_in
            if self.prefix_store is not None:
                self.scheduler.prefix_fetch_hook = self._tier_prefix_fetch
                self.block_manager.evict_hook = self._promote_evicted
        self._requests = {}
        self._next_id = 0
        self.seed = 0 if seed is None else int(seed)
        self._rng = np.random.RandomState(self.seed)
        # per-bucket cached all-zero [tb, V] bias/counts channel, so
        # the common no-pipeline step re-uses one device array instead
        # of uploading a fresh vocab-sized zero block every launch
        self._neutral_chan = {}
        self.stats = {"steps": 0, "prefill_steps": 0, "decode_steps": 0,
                      "chunk_launches": 0, "tokens_generated": 0,
                      "spec_steps": 0, "draft_tokens": 0,
                      "accepted_tokens": 0, "mixed_steps": 0,
                      # async lookahead: plans staged under device
                      # time / staged plans that survived to launch
                      "staged_steps": 0, "staged_hits": 0,
                      # tree speculation: sibling branches taken
                      "tree_hits": 0,
                      # lifecycle/fault counters (lifecycle_stats())
                      "aborted": 0, "deadline_missed": 0, "shed": 0,
                      "retries": 0, "quarantined": 0, "step_faults": 0}

        # params and cache go to the device (sharded over the mesh
        # under TP; the single-device path skips device_put)
        self._param_shardings = None
        if self.tp > 1:
            self._param_shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec),
                self._param_specs, is_leaf=lambda x: isinstance(x, P))
            params = jax.tree_util.tree_map(
                jax.device_put, params, self._param_shardings)
        self.params = params
        self.kv_cache = self._alloc_cache()
        self._ragged = self._build_step()

        # model-based drafting: draft params (leading target layers +
        # identity blocks, see GPT2ServingModel.draft_params) and a
        # second cache of the same spec ride the SAME executable family
        # — zero extra compiles.  The draft gets its own BlockManager
        # (prefix caching off — draft state is disposable) sized like
        # the target's.
        self._draft_params = None
        self._draft_bm = None
        if self.spec is not None and self.spec.uses_draft_model:
            dl = min(int(self.spec.draft_layers), self.num_layers)
            self._draft_params = sm.draft_params(
                self.params, dl, self._param_shardings)
            self._draft_cache = self._alloc_cache()
            self._draft_bm = BlockManager(self.num_blocks, self.block_size,
                                          enable_prefix_caching=False)
            self.events.append((self._step_index, "draft_model_load", dl,
                                self.num_blocks))

    def _alloc_cache(self):
        """Allocate one KV cache of this engine's spec — the target's
        pools and the draft model's alike.  The seam the discrete-event
        simulator overrides (numpy pools)."""
        return self.kv_spec.zeros(self.num_blocks)

    def _build_step(self):
        """Jit THE executable: one ragged token batch covers every
        serving phase.  Traced once per token bucket; shared by the
        target and the draft model (params are an operand)."""
        serving = self.serving_model

        def ragged_fn(params, ids, cache, block_tables, positions, rows,
                      row_start, row_qlen, row_pos0, cow_src, cow_dst,
                      top_k, top_p, min_p, rep_pen, pres_pen, freq_pen,
                      bias, counts, *lora_args):
            """ids [Tb] — the step's query tokens packed back-to-back
            and padded to the token bucket; positions [Tb] is each
            token's absolute position (-1 for padding: page writes
            drop, outputs are never read); rows [Tb] maps each token
            to its block-table row.  row_start/row_qlen/row_pos0
            [R = max_batch] are the per-row ragged descriptors the
            Pallas kernel consumes (see paged_attention.py for the
            dual-descriptor contract; R is FIXED, so only the token
            axis buckets).  ``cache`` is the KV cache pytree
            (kv_cache.py), donated: the pools update in place.

            A decode row is one query token, a speculative-verify row
            is 1 + K draft tokens, a prefill chunk is a C-token slice —
            identical causal semantics: the token at position p
            attends over pool positions 0..p through its row's table
            (the serving model's ``forward``).

            The request-surface operands (sampling.py): ``cow_src`` /
            ``cow_dst`` [R] are fork COW page copies applied up front;
            the six [R] knob vectors plus the [Tb, V] bias/counts
            channels drive the per-row logits pipeline applied AFTER
            the head — the returned argmax and logits are the
            PROCESSED ones, so greedy-under-mask and speculative
            acceptance see exactly what the sampler samples from.
            Neutral operand values are bitwise identities.

            A LoRA engine appends ONE operand: ``adapter_rows`` [R],
            each row's resident adapter slot — the multi-tenant batch
            costs one int32 vector, not an executable.
            Returns (argmax [Tb], logits [Tb, V], cache)."""
            cache = copy_pages(cache, cow_src, cow_dst)
            logits, cache = serving.forward(
                params, ids, positions, cache, block_tables, rows,
                row_start, row_qlen, row_pos0, *lora_args)
            logits = apply_logits_pipeline(
                logits, rows, top_k, top_p, min_p, rep_pen, pres_pen,
                freq_pen, bias, counts)
            return jnp.argmax(logits, -1), logits, cache

        if self.tp == 1:
            return jax.jit(ragged_fn, donate_argnums=(2,))
        # shard_map: each device runs the SAME program on its local
        # head slice — local qkv/fc columns, local pool shard, the
        # two explicit psums per layer; block tables / ids /
        # positions / activations ride replicated.  The jit wrapper
        # pins NamedShardings so host operands are placed without
        # resharding and the donated cache keeps its layout.
        # Replicated operands after the cache: tables, positions, rows,
        # row_start, row_qlen, row_pos0, cow_src, cow_dst, then the
        # eight sampling operands (six per-row knob vectors + the two
        # [Tb, V] channels), like every host-packed descriptor — and,
        # on a LoRA engine, the per-row adapter_rows slot vector.
        n_extra = 17 if self.lora is not None else 16
        rep, rsh = P(), NamedSharding(self.mesh, P())
        spec = self.kv_spec
        mapped = jax.shard_map(
            ragged_fn, mesh=self.mesh,
            in_specs=(self._param_specs, rep, spec.specs)
            + (rep,) * n_extra,
            out_specs=(rep, rep, spec.specs), check_vma=False)
        return jax.jit(
            mapped,
            in_shardings=(self._param_shardings, rsh, spec.shardings)
            + (rsh,) * n_extra,
            out_shardings=(rsh, rsh, spec.shardings),
            donate_argnums=(2,))

    # ----------------------------------------------------------- requests --
    def add_request(self, prompt_ids, max_new_tokens=16, eos_token_id=None,
                    temperature=0.0, request_id=None, seed=None,
                    deadline_ms=None, top_k=0, top_p=1.0, min_p=0.0,
                    repetition_penalty=1.0, presence_penalty=0.0,
                    frequency_penalty=0.0, logit_bias=None, logprobs=0,
                    stop=None, grammar=None, n=1, adapter_id=None):
        interleave_point("add")
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]  # noqa: H001 (host request boundary)
        if not prompt:
            raise ValueError("empty prompt")
        # adapter validation FIRST among tenant-facing knobs: an
        # unknown adapter must leave the engine completely untouched
        # (no request id burned, no queue entry) so the HTTP layer can
        # turn it into a clean 400
        if adapter_id is not None:
            if self.lora is None:
                raise ValueError(
                    "adapter_id= needs a LoRA-enabled engine — "
                    "construct with lora=LoRAConfig(...)")
            if not self._lora_mgr.known(adapter_id):
                raise ValueError(
                    f"unknown adapter {adapter_id!r} — register it "
                    f"with add_adapter() first")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {temperature}")
        logit_bias, stop = validate_sampling(
            top_k, top_p, min_p, repetition_penalty, presence_penalty,
            frequency_penalty, logit_bias, logprobs, stop, n,
            vocab_size=self.vocab_size)
        if stop and self.detokenizer is None:
            raise ValueError(
                "stop strings need a detokenizer — construct the "
                "engine with detokenizer=callable(ids) -> str")
        if grammar is not None and not all(
                hasattr(grammar, a)
                for a in ("start_state", "allowed", "advance")):
            raise ValueError(
                f"grammar must implement start_state/allowed/advance "
                f"(see inference.llm.structured.Grammar), "
                f"got {grammar!r}")
        if n > 1:
            if seed is None:
                raise ValueError(
                    "n > 1 parallel sampling needs an explicit seed — "
                    "each fork k samples under seed + k, which is what "
                    "makes fork-vs-replay exactness checkable")
            if n > self.max_batch:
                raise ValueError(
                    f"n={n} exceeds max_batch {self.max_batch}: the "
                    f"whole fork family must fit one running set")
        if deadline_ms is not None and \
                (isinstance(deadline_ms, bool)
                 or not isinstance(deadline_ms, (int, float, np.integer,
                                                 np.floating))
                 or deadline_ms <= 0):
            raise ValueError(
                f"deadline_ms must be a positive number of "
                f"milliseconds, got {deadline_ms!r}")
        if len(prompt) + max_new_tokens > self.max_model_len:
            raise ValueError(
                f"prompt {len(prompt)} + new {max_new_tokens} exceeds "
                f"max_model_len {self.max_model_len}")
        if request_id is None:
            request_id = self._next_id
            self._next_id += 1
        now = self._clock()
        req = Request(request_id=request_id, prompt_ids=tuple(prompt),
                      max_new_tokens=int(max_new_tokens),
                      eos_token_id=eos_token_id,
                      temperature=float(temperature),
                      seed=None if seed is None else int(seed),
                      deadline=(None if deadline_ms is None
                                else now + float(deadline_ms) / 1e3),
                      top_k=int(top_k), top_p=float(top_p),
                      min_p=float(min_p),
                      repetition_penalty=float(repetition_penalty),
                      presence_penalty=float(presence_penalty),
                      frequency_penalty=float(frequency_penalty),
                      logit_bias=logit_bias, logprobs=int(logprobs),
                      stop=stop, grammar=grammar, n=int(n),
                      adapter_id=adapter_id, arrival_time=now)
        if grammar is not None:
            req._constraint = ConstraintState(grammar)
        # bounded admission: past the configured waiting-queue depth
        # (or while draining) the request is SHED — it finishes
        # immediately with FinishReason.shed instead of growing an
        # unbounded queue whose tail can never meet a deadline.  The
        # per-tenant quota sheds the same way: a tenant already at its
        # live-request cap cannot crowd out the other adapters.
        quota = self.lora.tenant_quota if self.lora is not None else None
        over_quota = (
            quota is not None and adapter_id is not None
            and sum(1 for r in self._requests.values()
                    if r.adapter_id == adapter_id) >= quota)
        if over_quota or self._draining \
                or (self.max_queue is not None
                    and self.scheduler.queue_depth()
                    >= self.max_queue):
            self.stats["shed"] += 1
            self.events.append((self._step_index, "shed", request_id))
            req.status = FINISHED
            req.finish_reason = FinishReason.SHED
            self._early.append(RequestOutput(
                request_id, req.prompt_ids, req.output_ids,
                FinishReason.SHED, 0))
            return request_id
        self._requests[request_id] = req
        self.scheduler.add(req)
        self._invalidate_plan()
        self.events.append((self._step_index, "add", request_id))
        return request_id

    def abort_request(self, request_id):
        """Cancel a request in ANY state — waiting, chunk-prefilling,
        decoding, holding a speculative reservation, or preempted —
        reclaiming its pages refcount-correctly (COW-shared pages drop
        one reference; prefix-cache registrations survive on the LRU
        list).  The RequestOutput (FinishReason.aborted, whatever
        tokens were already emitted) is delivered by the next step().
        Returns True if the request existed and was aborted, False if
        it was unknown or already finished."""
        interleave_point("abort")
        req = self._requests.get(request_id)
        if req is None or req.status == FINISHED:
            return False
        rollback_draft_reservation(self.block_manager, req)
        self.scheduler.abort(req)
        self._invalidate_plan()
        self.stats["aborted"] += 1
        self.events.append((self._step_index, "abort", request_id))
        self._finish_early(req, FinishReason.ABORTED)
        return True

    def _finish_early(self, req, reason, error=None):
        """Terminal bookkeeping for a request that exits WITHOUT a
        device step (abort / deadline / quarantine): pages are already
        reclaimed by the caller; the output joins the next step()'s
        finished list."""
        self._invalidate_plan()
        self._drafter_forget(req.request_id)
        self._tier_forget(req.request_id)
        req.status = FINISHED
        req.finish_reason = reason
        self._requests.pop(req.request_id, None)
        self._early.append(RequestOutput(
            req.request_id, req.prompt_ids, req.output_ids, reason,
            req.num_preemptions, error=error,
            logprobs=req.logprobs_content if req.logprobs else None,
            matched_stop=req.matched_stop))

    def _expire_deadlines(self, finished):
        """Scheduler-enforced deadlines: pop every request past its
        ``deadline_ms`` (waiting or running — pages freed either way)
        and emit its output with FinishReason.deadline."""
        expired = self.scheduler.expire_deadlines(self._clock())
        for req in expired:
            self.stats["deadline_missed"] += 1
            self.events.append(
                (self._step_index, "deadline", req.request_id))
            self._finish_early(req, FinishReason.DEADLINE)
        if expired:
            finished.extend(self._drain_early())

    def _drain_early(self):
        early, self._early = self._early, []
        return early

    def _invalidate_plan(self):
        """Mark every staged lookahead plan stale: any lifecycle
        mutation that could change what the scheduler would pick for
        the next step (admission, abort, finish, fork, quarantine,
        migration import/release) bumps the epoch, and _claim_staged
        discards a plan staged under an older one."""
        self._plan_epoch += 1

    def has_unfinished(self):
        return bool(self._early) or self.scheduler.has_unfinished()

    def drain(self, timeout_s=None):
        """Graceful shutdown: stop admitting (new requests are shed),
        step until every in-flight request finishes, and return their
        outputs.  ``timeout_s`` bounds the wall-clock wait — requests
        still running when it expires are aborted, so drain() always
        terminates with zero pages leaked."""
        self._draining = True
        deadline = (None if timeout_s is None
                    else self._clock() + float(timeout_s))
        outs = []
        try:
            while self.has_unfinished():
                if deadline is not None and self._clock() >= deadline:
                    for rid in list(self._requests):
                        self.abort_request(rid)
                outs.extend(self.step())
        finally:
            self._draining = False
        return outs

    def lifecycle_stats(self):
        """Failure-path counters (chaos bench artifact rows) plus the
        live gauges a fleet health checker polls between steps:
        ``queue_depth`` (admitted, not yet running), ``inflight``
        (running set size), ``free_pages`` (allocatable right now,
        LRU-parked cached pages included), and ``last_step_ms`` (wall
        time of the most recent step(); None before the first step —
        the one wall-clock value here, and it never enters ``events``,
        so seed replays still produce identical logs)."""
        s = self.stats
        with self._gauge_lock:
            last_step_ms = self._last_step_ms
            host_plan_s = self._host_plan_s
            step_wall_s = self._step_wall_s
        return {"aborted": s["aborted"],
                "deadline_missed": s["deadline_missed"],
                "shed": s["shed"], "retries": s["retries"],
                "quarantined": s["quarantined"],
                "step_faults": s["step_faults"],
                "preemptions": self.scheduler.num_preemptions,
                "wedged_steps": (self.watchdog.num_wedged
                                 if self.watchdog else 0),
                "queue_depth": self.scheduler.queue_depth(),
                "inflight": len(self.scheduler.running),
                "free_pages": self.block_manager.num_free_blocks,
                "last_step_ms": last_step_ms,
                # async lookahead gauges: staged/claimed plan counts
                # and the measured fraction of step wall time the host
                # spends planning+packing ON the critical path (plans
                # claimed from a lookahead stage contribute ~0 — their
                # packing ran under the previous step's device window).
                # Wall-clock floats live HERE, never in events.
                "staged_steps": s["staged_steps"],
                "staged_hits": s["staged_hits"],
                "host_plan_s": host_plan_s,
                "host_overhead_fraction": (
                    host_plan_s / step_wall_s
                    if step_wall_s > 0 else None),
                # per-step cumulative counter trajectory (empty unless
                # record_step_gauges=True; see _record_step_gauges)
                "step_gauges": self.step_gauges}

    def _bucket_grid(self):
        """The complete executable family: every (kind, bucket) pair
        serving can ever launch.  Single source of truth for warmup(),
        executable_grid(), and the static-analysis sweep.

        ONE family now — "ragged" over total query tokens, powers of
        two from 8 up to the token budget.  The batch axis is fixed at
        max_batch rows of descriptors, the draft depth folds into the
        token count, so the grid is O(log token_budget) where the
        retired per-phase grid was O(log chunks + log batches
        + log batches * log K)."""
        tb = min(8, self.token_budget)
        while True:
            yield ("ragged", tb)
            if tb >= self.token_budget:
                break
            tb = min(tb * 2, self.token_budget)

    def executable_grid(self):
        """Yield ``(kind, bucket, jitted_fn, abstract_args)`` covering
        the warmup grid with ``ShapeDtypeStruct`` stand-ins for the K/V
        pools — framework.analysis traces these without executing (or
        donating) anything, so a lint pass never touches cache state."""
        sds = jax.ShapeDtypeStruct
        cache = self.kv_spec.abstract(self.num_blocks)
        i32, f32 = jnp.int32, jnp.float32
        rmax, v = self.max_batch, self.vocab_size
        for kind, tb in self._bucket_grid():
            args = (self.params, sds((tb,), i32), cache,
                    sds((rmax, self.max_pages), i32), sds((tb,), i32),
                    sds((tb,), i32), sds((rmax,), i32),
                    sds((rmax,), i32), sds((rmax,), i32),
                    # cow_src, cow_dst
                    sds((rmax,), i32), sds((rmax,), i32),
                    # top_k, top_p, min_p, rep/pres/freq penalties
                    sds((rmax,), i32), sds((rmax,), f32),
                    sds((rmax,), f32), sds((rmax,), f32),
                    sds((rmax,), f32), sds((rmax,), f32),
                    # bias + counts channels bucket with the token axis
                    sds((tb, v), f32), sds((tb, v), f32))
            if self.lora is not None:
                # the single extra LoRA operand: per-row adapter slots
                args = args + (sds((rmax,), i32),)
            yield kind, tb, self._ragged, args

    def memory_model(self, memory_budget=None):
        """Static per-chip HBM breakdown — weight bytes (sharding-
        aware), page/pool/sequence bytes, and, under a budget (the
        engine's own ``memory_budget=`` or an override), the admissible
        ``max_batch`` it supports.  Delegates to
        :func:`paddle_tpu.framework.cost.engine_memory_model`."""
        from ...framework.cost import engine_memory_model
        return engine_memory_model(self, memory_budget=memory_budget)

    def warmup(self):
        """Compile every bucketed executable before traffic arrives.

        No-op on cache contents: every dummy row is dead (row_qlen 0,
        position -1), so every page write lands on the dropped
        out-of-range slot.  Serving processes call this at startup so
        no client pays a compile stall.  The ragged family is
        O(log token_budget) — neither prompt length, batch size, nor
        draft depth enters the executable count.  Under TP the same
        walk compiles the sharded executables over the mesh (the bucket
        grid is identical: shapes are global, only shardings differ).

        Returns a :class:`~paddle_tpu.framework.analysis.CompileWatcher`
        armed over the freshly-warm ragged executable, so callers can
        assert the serving window compiles nothing; the watcher also
        carries ``compile_ms`` — wall-clock per warmed bucket (compile
        + one dummy run), keyed ``"ragged[<bucket>]"`` and mirrored on
        ``engine.warmup_compile_ms`` — so the family collapse is a
        measured claim::

            watcher = eng.warmup()
            serve_traffic()
            watcher.assert_no_new_compiles()
            watcher.compile_ms       # {"ragged[8]": ..., ...}
        """
        timings = {}
        rmax = self.max_batch
        with profiler.RecordEvent("llm_engine::warmup"):
            for kind, tb in self._bucket_grid():
                t0 = time.perf_counter()
                ids = jnp.zeros((tb,), jnp.int32)
                tables = jnp.zeros((rmax, self.max_pages), jnp.int32)
                positions = jnp.full((tb,), -1, jnp.int32)
                rows = jnp.zeros((tb,), jnp.int32)
                zr = jnp.zeros((rmax,), jnp.int32)
                # neutral sampling operands: no-COW (dst = num_blocks
                # drops the copy), identity knobs, zero channels
                cow_dst = jnp.full((rmax,), self.num_blocks, jnp.int32)
                knobs = tuple(jnp.asarray(k)
                              for k in neutral_row_params(rmax))
                chan = jnp.zeros((tb, self.vocab_size), jnp.float32)
                # slot 0 (the all-zero base identity) for every dead
                # warmup row — the LoRA operand's bitwise-neutral value
                lora_ops = (zr,) if self.lora is not None else ()
                _, _, self.kv_cache = self._ragged(
                    self.params, ids, self.kv_cache, tables,
                    positions, rows, zr, zr, zr, zr, cow_dst,
                    *knobs, chan, chan, *lora_ops)
                jax.block_until_ready(self.kv_cache)  # noqa: H001 (warmup timing sync — never on the serving step path)
                timings[f"{kind}[{tb}]"] = \
                    (time.perf_counter() - t0) * 1e3
        from ...framework.analysis import CompileWatcher
        self.warmup_compile_ms = dict(timings)
        watcher = CompileWatcher(self._ragged, labels=("ragged",))
        watcher.compile_ms = dict(timings)
        return watcher

    # --------------------------------------------------------------- step --
    def step(self):
        """Run one scheduling iteration; returns RequestOutputs finished
        by this step (possibly empty) — including requests that exited
        through a failure path (aborted / deadline / shed / error)
        since the previous step."""
        t0 = self._timer()
        try:
            return self._step_impl()
        finally:
            # the last_step_ms health gauge: time of the whole
            # iteration (schedule + launches + commit) on the injected
            # timer, kept OUT of the deterministic event log
            dt = self._timer() - t0
            with self._gauge_lock:
                self._step_wall_s += dt
                self._last_step_ms = dt * 1e3

    def _step_impl(self):
        interleave_point("step")
        self._step_index += 1
        self.last_launches = []
        self.last_tier_bytes = 0
        if self.faults is not None:
            self.faults.begin_step(self._step_index)
        finished = self._drain_early()
        self._expire_deadlines(finished)
        staged = self._claim_staged()
        if staged is not None:
            # the whole plan+pack for this step already ran under the
            # PREVIOUS step's device window — only the (cheap) claim
            # validation sits on this step's critical path, which is
            # what the host_overhead_fraction gauge measures dropping
            plan_rows, pk = staged
            self.stats["steps"] += 1
            self.stats["staged_hits"] += 1
            self.stats["decode_steps"] += 1
            self._launch_packed(plan_rows, pk, finished)
        else:
            if isinstance(self.drafter, DraftModelDrafter):
                self._draft_phase()
            t0 = self._timer()
            pre_preempt = self.scheduler.num_preemptions
            with profiler.RecordEvent("llm_engine::schedule"):
                batch = self.scheduler.schedule()
            if self.scheduler.num_preemptions > pre_preempt:
                self.events.append(
                    (self._step_index, "preempt",
                     self.scheduler.num_preemptions - pre_preempt))
            if batch.kind == "idle":
                with self._gauge_lock:
                    self._host_plan_s += self._timer() - t0
                self._record_step_gauges()
                return finished
            self.stats["steps"] += 1
            self._ragged_step(batch, finished, t_sched=t0)
        if self.tp > 1 or self.kv_tier is not None:
            # ONE host-side allocator drives every shard (tables ride
            # replicated), so page accounting must be shard-invariant:
            # assert the books balance after each TP step.  With a
            # host tier configured the engine-level check (HBM + host
            # pool + prefix store conservation) runs EVERY step — zero
            # page leaks across tiers is the hierarchical-KV contract.
            self.check_invariants()
        finished.extend(self._drain_early())
        self._record_step_gauges()
        return finished

    def _record_step_gauges(self):
        """Per-step CUMULATIVE lifecycle counters (opt-in via
        ``record_step_gauges=``): one wall-clock-free snapshot per
        step(), so a policy experiment can plot preemption/shed/abort
        trajectories over the run instead of only end totals.  The
        list rides ``lifecycle_stats()["step_gauges"]``."""
        if not self.record_step_gauges:
            return
        s = self.stats
        self.step_gauges.append({
            "step": self._step_index,
            "preemptions": self.scheduler.num_preemptions,
            "shed": s["shed"], "aborted": s["aborted"],
            "deadline_missed": s["deadline_missed"],
            "retries": s["retries"], "quarantined": s["quarantined"],
            "queue_depth": self.scheduler.queue_depth(),
            "inflight": len(self.scheduler.running),
            "free_pages": self.block_manager.num_free_blocks,
        })

    # ------------------------------------------------- step isolation ----
    def _launch(self, kind, reqs, launch):
        """Run one jitted launch behind the isolation boundary: fault
        injection fires first (so injected failures never consume the
        donated pool), the RetryPolicy absorbs transient faults with
        seeded backoff, the watchdog clocks every attempt, and a launch
        that still fails is quarantined — the responsible request(s)
        finish with FinishReason.error, the rest of the engine keeps
        serving.  Returns the launch outputs, or None after a
        quarantine (callers skip their commit phase)."""
        attempt = 0
        while True:
            t0 = (self.watchdog.started()
                  if self.watchdog is not None else None)
            try:
                if self.faults is not None:
                    self.faults.device_step(kind)
                return launch()
            except Exception as e:   # noqa: BLE001 — isolation boundary
                self.stats["step_faults"] += 1
                if self._pool_lost():
                    # the failing call consumed the donated K/V pool:
                    # nothing to retry INTO — surface it, don't limp
                    raise PoolLostError(
                        f"device step consumed the donated KV pool "
                        f"before failing; cache unrecoverable: {e}"
                    ) from e
                attempt += 1
                if attempt < self.retry.max_attempts:
                    self.stats["retries"] += 1
                    self.events.append(
                        (self._step_index, "retry", kind, attempt))
                    delay = self.retry.backoff(attempt - 1)
                    if delay > 0:
                        self._sleep(delay)
                    continue
                self._quarantine(kind, reqs, e)
                return None
            finally:
                if self.watchdog is not None:
                    self.watchdog.observe_since(self._step_index, kind,
                                                t0)

    def _pool_lost(self):
        deleted = getattr(self.kv_cache["k"], "is_deleted", None)
        return bool(deleted and deleted())

    def _quarantine(self, kind, reqs, exc):
        """A launch failed after every retry: quarantine the
        responsible request(s) with FinishReason.error instead of
        killing the batch.  An injected fault names its victim row;
        unattributable (real) failures quarantine every row of the
        failing launch.  Non-victim rows roll back their outstanding
        slot reservation and STAY RUNNING — the failed launch never
        executed, so their K/V state is untouched and the next step
        re-reserves and re-launches them token-exactly."""
        self._invalidate_plan()
        victim = getattr(exc, "victim", None)
        victims = (list(reqs) if victim is None or not reqs
                   else [reqs[victim % len(reqs)]])
        msg = f"{type(exc).__name__}: {exc}"
        warnings.warn(f"quarantining {len(victims)} request(s) after "
                      f"failed {kind} step: {msg}", RuntimeWarning,
                      stacklevel=3)
        for req in reqs:
            # decode rows reserved 1 slot, verify rows 1 + K; give them
            # back so survivors' books read exactly num_cached.  Chunk
            # rows of the ragged launch hold a PROMPT allocation, not a
            # step reservation — rollback_draft_reservation no-ops on
            # them (mid-prefill sequences are never prefill_done)
            rollback_draft_reservation(self.block_manager, req)
        for req in victims:
            self.scheduler.abort(req)
            self.stats["quarantined"] += 1
            self.events.append(
                (self._step_index, "quarantine", req.request_id))
            self._finish_early(req, FinishReason.ERROR, error=msg)

    def _register_full_blocks(self, req):
        """Make every completed full page of ``req`` hash-addressable
        (register_full_block skips pages that already carry a hash)."""
        bm = self.block_manager
        if not bm.enable_prefix_caching:
            return
        hashes = bm.prefix_chain_hashes(
            req.all_ids, limit=req.num_cached // self.block_size,
            salt=req.adapter_id)
        for i, h in enumerate(hashes):
            bm.register_full_block(req.request_id, i, h)

    def prefix_cache_stats(self):
        """Host-side prefix-cache counters (for benches and tests)."""
        sch, bm = self.scheduler, self.block_manager
        hit = sch.prefix_hit_tokens
        return {"prompt_tokens": sch.prompt_tokens,
                "prefix_hit_tokens": hit,
                "hit_rate": hit / sch.prompt_tokens
                if sch.prompt_tokens else 0.0,
                "reused_blocks": bm.prefix_reused_blocks,
                "evictions": bm.prefix_evictions,
                "cached_blocks": bm.num_cached_blocks}

    # ----------------------------------------------------------- multi-LoRA --
    def add_adapter(self, adapter_id, weights):
        """Register one tenant adapter: ``weights`` maps each
        configured target leaf to ``(A [L, in, r], B [L, r, out])``.
        Host-only — the device pool slot is written lazily the first
        time a step actually batches the adapter, so registering ten
        thousand tenants costs host RAM, not HBM or compiles."""
        if self.lora is None:
            raise ValueError(
                "add_adapter() needs a LoRA-enabled engine — "
                "construct with lora=LoRAConfig(...)")
        self._lora_mgr.register(adapter_id, weights)
        self.events.append((self._step_index, "adapter_register",
                            adapter_id))

    def lora_stats(self):
        """Host-side adapter residency counters (benches and tests):
        loads/evictions/hits plus registered/resident/slot gauges."""
        if self.lora is None:
            raise ValueError("lora_stats() needs a LoRA-enabled engine")
        return self._lora_mgr.lora_stats()

    def _lora_slot(self, req, pinned):
        """Resident pool slot for one row's adapter, loading it into a
        (possibly LRU-evicted) slot first when absent."""
        slot, weights = self._lora_mgr.acquire(req.adapter_id,
                                               pinned=pinned)
        if weights is not None:
            self._load_adapter_slot(slot, weights)
            self.events.append((self._step_index, "adapter_load",
                                req.adapter_id, slot))
        return slot

    def _load_adapter_slot(self, slot, weights):
        """Write one adapter into pool slot ``slot`` — the host-staged
        migration idiom (``device_get`` → numpy row write →
        ``device_put``): no jit anywhere on the path, so an armed
        CompileWatcher sees adapter churn as zero compiles.  Under TP
        the rebuilt leaves go back with their pool shardings, and the
        qkv B half is permuted to the head-blocked column layout the
        base qkv weight was loaded in."""
        interleave_point("adapter-load")
        blocks = dict(self.params["blocks"])
        for key, halves in weights.items():
            a_h, b_h = self.serving_model.adapter_layout(key, *halves)
            for side, val in (("A", a_h), ("B", b_h)):
                lk = lora_key(key, side)
                host = np.array(jax.device_get(blocks[lk]))  # noqa: H001 (host-staged slot swap by design)
                host[:, slot] = val.astype(host.dtype)
                if self.tp > 1:
                    blocks[lk] = jax.device_put(
                        host, self._param_shardings["blocks"][lk])
                else:
                    blocks[lk] = jax.device_put(host)
        self.params = {**self.params, "blocks": blocks}

    # ------------------------------------------------------------ migration --
    def _gather_payload(self, block_ids):
        """The pages ``block_ids`` of every cache leaf as host numpy
        arrays, under the migration / host-tier payload's names
        (``k_pages``, ``v_pages`` and, for an int8 pool, ``k_scales``,
        ``v_scales`` — kv_cache.PAYLOAD_KEYS): the pytree is flattened
        to that format here, at the edge that crosses replicas."""
        pages = gather_pages(self.kv_cache, block_ids)
        return {PAYLOAD_KEYS[n]: p for n, p in pages.items()}

    def _scatter_payload(self, block_ids, payload, lo=0, hi=None):
        """Write pages ``[lo:hi]`` of a payload (same names as
        :meth:`_gather_payload`) into pool rows ``block_ids``."""
        pages = {n: payload[PAYLOAD_KEYS[n]][:, lo:hi]
                 for n in self.kv_cache}
        self.kv_cache = scatter_pages(
            self.kv_cache, block_ids, pages, self.kv_spec.shardings)

    def export_request(self, request_id):
        """Serialize one RUNNING request for migration to a peer
        engine: the live Request object, the BlockManager's page-chain
        export, and the host-gathered K/V page payload.  Read-only —
        the request keeps serving here until :meth:`release_request`,
        so a failed import on the destination costs nothing."""
        req = self._requests.get(request_id)
        if req is None:
            raise KeyError(f"unknown request {request_id!r}")
        if req.status != RUNNING or \
                not self.block_manager.has_seq(request_id):
            raise ValueError(
                f"request {request_id!r} is {req.status}; only running "
                f"sequences with resident pages export (waiting/"
                f"preempted ones requeue from scratch instead)")
        seq = self.block_manager.export_seq(request_id)
        payload = self._gather_payload(seq["block_ids"])
        self.events.append((self._step_index, "export", request_id,
                            len(seq["block_ids"])))
        return {"request": req, "seq": seq, **payload}

    def import_request(self, req, seq, k_pages, v_pages,
                       fault_hook=None, k_scales=None, v_scales=None):
        """Adopt a migrated-in request mid-generation: allocate a
        private page chain, scatter the payload into this engine's
        pools, re-register full pages in this prefix cache, and insert
        the request into the running set — decode resumes next step,
        token-exactly (``num_cached`` / ``output_ids`` / the
        per-request sampling stream ride the Request object).

        All-or-nothing: any failure after allocation (``fault_hook`` —
        the injected mid-import fault — a shape mismatch, anything)
        frees exactly the pages allocated here and re-raises, leaving
        this engine untouched.  Raises MigrationError up front when the
        running set is full (the decode batch is sized by max_batch)."""
        rid = req.request_id
        if rid in self._requests:
            raise ValueError(f"request {rid!r} already live here")
        if len(self.scheduler.running) >= self.max_batch:
            raise MigrationError(
                f"destination running set is full "
                f"({self.max_batch} sequences)", reason="capacity")
        aid = getattr(req, "adapter_id", None)
        if aid is not None and (
                self.lora is None or not self._lora_mgr.known(aid)):
            # up-front, before any allocation: a destination that
            # cannot serve the tenant's adapter must refuse the
            # migration token-exactly intact on the source
            raise MigrationError(
                f"destination cannot serve adapter {aid!r} — "
                f"{'no lora= configured' if self.lora is None else 'adapter not registered'}",
                reason="adapter")
        payload = {"k_pages": k_pages, "v_pages": v_pages,
                   "k_scales": k_scales, "v_scales": v_scales}
        if self._kv_quant:
            if k_scales is None or v_scales is None:
                raise ValueError(
                    "this engine's KV pool is int8 — the migration "
                    "payload must carry k_scales/v_scales (export from "
                    "an identically quantized engine)")
        elif k_scales is not None or v_scales is not None:
            raise ValueError(
                "scale payload offered to a full-precision pool — "
                "migration requires identically configured engines")
        for name in self.kv_cache:
            pages = payload[PAYLOAD_KEYS[name]]
            expect = self.kv_spec.shape(name, len(seq["block_ids"]))
            if tuple(pages.shape) != expect:
                raise ValueError(
                    f"{PAYLOAD_KEYS[name]} payload {pages.shape} does "
                    f"not fit this pool (expected {expect}) — migration "
                    f"requires identically configured engines")
        table = self.block_manager.import_seq(rid, seq)
        try:
            if fault_hook is not None:
                fault_hook()
            self._scatter_payload(table, payload)
            self.block_manager.register_imported(rid, seq["hashes"])
        except BaseException:
            # exact reclamation: every page import_seq allocated goes
            # back; nothing was registered before the payload landed
            self.block_manager.free(rid)
            raise
        req.status = RUNNING
        req.draft_tokens = []
        self._requests[rid] = req
        self.scheduler.running.append(req)
        self._invalidate_plan()
        self.events.append((self._step_index, "import", rid,
                            len(table)))

    def release_request(self, request_id):
        """Forget a migrated-away request WITHOUT emitting an output:
        pages are reclaimed refcount-correctly (prefix-cache
        registrations survive on the LRU list) and ownership is now the
        importing engine's.  The mirror of :meth:`import_request` —
        call it only after the import succeeded."""
        req = self._requests.pop(request_id)
        self.scheduler.abort(req)
        self._invalidate_plan()
        self._drafter_forget(request_id)
        self._tier_forget(request_id)
        self.events.append((self._step_index, "release", request_id))

    # -------------------------------------------------- hierarchical KV --
    def _tier_demote(self, victim):
        """Scheduler preempt hook (kv_tier.py): stage the victim's page
        chain into the host pool BEFORE its pages are freed, so
        re-admission swaps it back in instead of re-prefilling.  Gated
        three ways — the chain must be fully committed (a mid-prefill
        chain holds garbage beyond ``num_cached``), the TierPolicy must
        price swap-in bytes under replay FLOPs, and the chain must fit
        the pool budget.  A demote-site fault aborts the stage with
        NOTHING stored (both tiers exactly as before) — the preemption
        falls back to plain recompute.  Never raises."""
        rid = victim.request_id
        pool, bm = self.host_pool, self.block_manager
        if not victim.prefill_done or victim.num_cached <= 0 or \
                bm.num_tokens(rid) != victim.num_cached:
            return
        seq = bm.export_seq(rid)
        npages = len(seq["block_ids"])
        nbytes = npages * self.page_bytes * self.tp
        if rid in pool or not pool.fits(nbytes):
            return
        if self.tier_policy.decide(self, victim.num_cached,
                                   npages) != "swap":
            return
        try:
            if self.faults is not None:
                self.faults.tier_fault("demote")
            payload = self._gather_payload(seq["block_ids"])
        except InjectedFault:
            return
        entry = {"seq": seq, "k_scales": None, "v_scales": None,
                 **payload}
        for old in pool.put(rid, entry):
            # chains LRU-evicted to make room lose their swap-in, but
            # their FULL pages still promote into the prefix store
            self._promote_chain(old)
        self.last_tier_bytes += nbytes
        self.events.append((self._step_index, "demote", rid, npages))

    def _tier_swap_in(self, req, margin):
        """Scheduler admission hook: swap a demoted chain back into
        HBM.  Returns None when the request has no demoted chain (the
        caller runs normal admission), "retry" when it does but cannot
        land this step (capacity, or an injected promote fault — the
        chain STAYS demoted for the next attempt), or the swapped-in
        token count on success.

        Pages still resident in the HBM prefix cache are adopted
        instead of re-scattered (the common case right after a
        preemption: the freed pages are parked on the LRU list), so
        only the genuinely evicted suffix moves bytes.  Registration
        happens strictly AFTER the payload lands (register-after-
        scatter, like import_request), so a mid-swap fault can never
        expose a garbage page through the prefix cache."""
        pool, bm = self.host_pool, self.block_manager
        rid = req.request_id
        entry = pool.get(rid)
        if entry is None:
            return None
        seq = entry["seq"]
        n = len(req.all_ids)
        cached = int(seq["num_tokens"])  # noqa: H001 (host export record field, not a tensor)
        if not 0 < cached < n:
            # stale chain (defensive — forget paths should have
            # dropped it); recompute from scratch
            self._promote_chain(pool.pop(rid))
            return None
        hashes = bm.prefix_chain_hashes(
            req.all_ids, limit=(n - 1) // bm.block_size,
            salt=req.adapter_id)
        k = bm.match_prefix(hashes)
        if not bm.can_allocate(n, margin=margin,
                               cached_hashes=hashes[:k]):
            return "retry"
        try:
            table = bm.allocate(rid, n, cached_hashes=hashes[:k])
        except NoFreeBlocksError:
            return "retry"
        npay = len(seq["block_ids"])
        moved = max(0, npay - k)
        try:
            if self.faults is not None:
                self.faults.tier_fault("promote")
            if moved:
                self._scatter_payload(table[k:npay], entry, k, npay)
            bm.register_imported(rid, seq["hashes"])
        except BaseException:
            # exact reclamation: every page allocated above goes back
            # (adopted pages re-park on the LRU with their contents
            # untouched — the scatter targeted fresh pages only), and
            # the chain stays demoted for the next attempt
            bm.free(rid)
            return "retry"
        pool.pop(rid, swapped=True)
        req.num_cached = cached
        self.last_tier_bytes += moved * self.page_bytes * self.tp
        self.events.append((self._step_index, "swap_in", rid, moved))
        return cached

    def _tier_prefix_fetch(self, req, hashes, k):
        """Scheduler admission hook (fleet-wide prefix store): after a
        normal admission adopted ``k`` HBM-resident pages, fetch the
        longest store-resident run of the REMAINING hashes into the
        already-allocated table and return the page count (the caller
        extends ``num_cached``).  Policy-gated like demote; a fault (or
        any failure) mid-fetch returns 0 with the fetched pages left
        unregistered — they hold garbage, and the unchanged
        ``num_cached`` means the prefill chunks recompute them."""
        store, bm = self.prefix_store, self.block_manager
        run = store.match(hashes[k:])
        if not run:
            return 0
        if self.tier_policy.decide(self, run * bm.block_size,
                                   run) != "swap":
            return 0
        rid = req.request_id
        table = bm.block_table(rid)
        entries = [store.get(h) for h in hashes[k:k + run]]
        try:
            if self.faults is not None:
                self.faults.tier_fault("promote")
            self._scatter_payload(table[k:k + run], {
                key: np.concatenate([e[key] for e in entries], axis=1)
                for key in (PAYLOAD_KEYS[n] for n in self.kv_cache)})
            for i, h in enumerate(hashes[k:k + run]):
                bm.register_full_block(rid, k + i, h)
        except BaseException:
            return 0
        self.last_tier_bytes += sum(
            e["k_pages"].nbytes + e["v_pages"].nbytes for e in entries)
        self.events.append((self._step_index, "store_adopt", rid, run))
        return run

    def _promote_evicted(self, blk, block_hash):
        """BlockManager evict hook: a FULL page is leaving the HBM
        prefix cache — promote its still-valid contents into the
        content-addressed host store before the block is reused.
        No-op when the page's hash is already stored, or while the
        pool buffers are donated to an in-flight launch."""
        store = self.prefix_store
        if block_hash in store or self._pool_lost():
            return
        store.put(block_hash, {"seq": {"block_ids": [blk]},
                               "k_scales": None, "v_scales": None,
                               **self._gather_payload([blk])})
        self.last_tier_bytes += self.page_bytes * self.tp
        self.events.append((self._step_index, "promote", 1))

    def _promote_chain(self, entry):
        """Promote every registered FULL page of one demoted chain into
        the prefix store (chain eviction / request exit: the swap-in is
        lost, the prefill work its full pages hold need not be)."""
        store = self.prefix_store
        if store is None or entry is None:
            return
        seq = entry["seq"]
        promoted = 0
        for i, h in enumerate(seq.get("hashes", ())):
            if h is None or h in store:
                continue
            page = {"seq": {"block_ids": [seq["block_ids"][i]]},
                    "k_pages": entry["k_pages"][:, i:i + 1],
                    "v_pages": entry["v_pages"][:, i:i + 1],
                    "k_scales": None, "v_scales": None}
            if entry.get("k_scales") is not None:
                page["k_scales"] = entry["k_scales"][:, i:i + 1]
                page["v_scales"] = entry["v_scales"][:, i:i + 1]
            store.put(h, page)
            promoted += 1
        if promoted:
            self.last_tier_bytes += promoted * self.page_bytes * self.tp
            self.events.append((self._step_index, "promote", promoted))

    def _tier_forget(self, request_id):
        """Drop a request's demoted chain (abort / deadline /
        quarantine / release): the swap-in can never happen, but the
        chain's full pages still promote into the prefix store."""
        if self.host_pool is not None:
            self._promote_chain(self.host_pool.pop(request_id))

    def adopt_waiting(self, req):
        """Adopt a foreign Request into this engine's WAITING queue —
        the fleet's tier-reroute drain path: the source demoted the
        chain into the SHARED host pool, and this engine's next
        admission swaps it in (or re-prefills ``all_ids`` from scratch
        if the pool evicted it first — token-exact either way).
        Unlike :meth:`import_request` this needs no free pages NOW, so
        a drain is never blocked on destination HBM headroom."""
        rid = req.request_id
        if rid in self._requests:
            raise ValueError(f"request {rid!r} already live here")
        aid = getattr(req, "adapter_id", None)
        if aid is not None and (
                self.lora is None or not self._lora_mgr.known(aid)):
            raise MigrationError(
                f"destination cannot serve adapter {aid!r} — "
                f"{'no lora= configured' if self.lora is None else 'adapter not registered'}",
                reason="adapter")
        req.status = WAITING
        req.num_cached = 0
        req.draft_tokens = []
        self._requests[rid] = req
        self.scheduler.add(req)
        self._invalidate_plan()
        self.events.append((self._step_index, "add", rid))

    def check_invariants(self):
        """Global page conservation across every tier: the HBM books
        (scheduler + BlockManager), the host pool's, and the prefix
        store's — plus the cross-tier exclusion that a demoted chain's
        request owns no HBM pages (the same K/V must never be resident
        twice).  Asserted every step when a tier is configured, and
        after every TP step regardless."""
        self.scheduler.check_invariants()
        if self.host_pool is not None:
            self.host_pool.check_invariants()
            for rid in self.host_pool._chains:
                if self.block_manager.has_seq(rid):
                    raise RuntimeError(
                        f"request {rid} owns HBM pages AND a demoted "
                        f"host-tier chain")
        if self.prefix_store is not None:
            self.prefix_store.check_invariants()

    def tier_stats(self):
        """Host-tier counters (benches and tests): per-tier residency
        and traffic plus the scheduler's swapped-in token total."""
        if self.kv_tier is None:
            raise ValueError("tier_stats() needs a kv_tier= engine")
        return {
            "swapped_in_tokens": self.scheduler.swapped_in_tokens,
            "host_pool": (self.host_pool.stats()
                          if self.host_pool is not None else None),
            "prefix_store": (self.prefix_store.stats()
                             if self.prefix_store is not None else None),
        }

    def _ragged_step(self, batch, finished, t_sched=None):
        """ONE unified launch for the whole scheduled step: every row —
        plain decode, speculative verify, prefill chunk — packs into a
        single flat token batch padded to the total-token bucket, and
        commits replay the retired engine's order exactly (decode/verify
        rows in scheduler order first, then chunks in schedule order),
        so seeded RNG streams and page bookkeeping are bitwise
        unchanged.  ``t_sched`` is the timer mark the scheduling pass
        started at — packing belongs to the same critical-path host
        window the host_overhead_fraction gauge measures."""
        rows = [row for row in batch.rows
                if row.request.status != FINISHED]
        if not rows:
            if t_sched is not None:
                with self._gauge_lock:
                    self._host_plan_s += self._timer() - t_sched
            return
        has_decode = any(row.kind != "chunk" for row in rows)
        has_chunk = any(row.kind == "chunk" for row in rows)
        if has_decode:
            self.stats["decode_steps"] += 1
        if has_chunk:
            self.stats["prefill_steps"] += 1
            self.stats["chunk_launches"] += \
                sum(1 for row in rows if row.kind == "chunk")
        if has_decode and has_chunk:
            self.stats["mixed_steps"] += 1
        pk = self._pack_ragged(rows, batch.cows)
        if t_sched is not None:
            with self._gauge_lock:
                self._host_plan_s += self._timer() - t_sched
        self._launch_packed(rows, pk, finished)

    def _pack_ragged(self, rows, cows):
        """Pack one step's RaggedRows into the executable's numpy
        operands.  Pure host work over scheduler/book state — shared
        verbatim by the synchronous step path and the lookahead stager
        (which runs it under the PREVIOUS step's device window), so a
        staged launch is operand-identical to the sync one.  Returns
        the packed-operand dict ``_launch_packed`` consumes."""
        total = sum(row.length for row in rows)
        tb = bucket_size(total, self.token_budget, floor=8)
        rmax = self.max_batch
        ids = np.zeros(tb, np.int32)
        positions = np.full(tb, -1, np.int32)
        tok_rows = np.zeros(tb, np.int32)
        tables = np.zeros((rmax, self.max_pages), np.int32)
        row_start = np.zeros(rmax, np.int32)
        row_qlen = np.zeros(rmax, np.int32)
        row_pos0 = np.zeros(rmax, np.int32)
        starts = []
        s = 0
        for ri, row in enumerate(rows):
            req = row.request
            starts.append(s)
            if row.kind == "chunk":
                toks = req.all_ids[row.start:row.start + row.length]
            elif row.kind == "tree":
                # sibling branch: re-write position T-1's K/V on the
                # fork's own COW chain, then score the second-best
                # first token at position T
                toks = [req.all_ids[-1], row.sibling]
            else:
                toks = [req.all_ids[-1]] + list(req.draft_tokens)
            ids[s:s + row.length] = toks
            positions[s:s + row.length] = np.arange(
                row.start, row.start + row.length)
            tok_rows[s:s + row.length] = ri
            bt = self.block_manager.block_table(
                req.request_id if row.table_id is None else row.table_id)
            tables[ri, :len(bt)] = bt
            row_start[ri] = s
            row_qlen[ri] = row.length
            row_pos0[ri] = row.start
            s += row.length

        # LoRA residency: map each row's adapter_id to its device pool
        # slot (loading/evicting host-side as needed — compile-free),
        # then ship the per-row slot vector as the ONE extra operand.
        # Adapters this batch is about to index are pinned so the LRU
        # never evicts under a launch's feet; the scheduler's
        # distinct-adapter admission gate guarantees they fit.
        adapter_rows = None
        if self.lora is not None:
            adapter_rows = np.zeros(rmax, np.int32)
            pinned = {row.request.adapter_id for row in rows
                      if row.request.adapter_id is not None}
            for ri, row in enumerate(rows):
                adapter_rows[ri] = self._lora_slot(row.request, pinned)

        # COW page copies + sampling operands — neutral identities
        # unless this batch carries fork COWs or pipeline rows, so
        # legacy traffic runs the same executable on the same values it
        # always did.  The [tb, V] channels are the only vocab-sized
        # operands; the all-zero channel is cached per bucket so the
        # common (no-pipeline) step never re-uploads it.
        cow_src = np.zeros(rmax, np.int32)
        cow_dst = np.full(rmax, self.num_blocks, np.int32)
        for i, (csrc, cdst) in enumerate(cows):
            cow_src[i] = csrc
            cow_dst[i] = cdst
        knobs = neutral_row_params(rmax)
        top_k, top_p, min_p, rep_pen, pres_pen, freq_pen = knobs
        pipe_rows = [(ri, row) for ri, row in enumerate(rows)
                     if row.request.uses_pipeline]
        bias = counts = None
        if pipe_rows:
            v = self.vocab_size
            bias = np.zeros((tb, v), np.float32)
            counts = np.zeros((tb, v), np.float32)
            for ri, row in pipe_rows:
                req = row.request
                top_k[ri] = req.top_k
                top_p[ri] = req.top_p
                min_p[ri] = req.min_p
                rep_pen[ri] = req.repetition_penalty
                pres_pen[ri] = req.presence_penalty
                freq_pen[ri] = req.frequency_penalty
                if row.kind == "chunk":
                    if not row.chunk.is_final:
                        continue       # no position samples this step
                    qpos = [starts[ri] + row.length - 1]
                    prefixes = [()]
                else:
                    # verify position j sees the draft prefix
                    # drafts[:j] as already-generated text — counts and
                    # grammar state advance PER POSITION, which is what
                    # makes constrained/penalized speculation exact
                    drafts = list(req.draft_tokens)
                    qpos = list(range(starts[ri],
                                      starts[ri] + row.length))
                    prefixes = [tuple(drafts[:j])
                                for j in range(len(qpos))]
                penal = (req.repetition_penalty != 1.0
                         or req.presence_penalty != 0.0
                         or req.frequency_penalty != 0.0)
                states = None
                if req._constraint is not None and len(qpos) > 1:
                    states = req._constraint.peek(prefixes[-1])
                for j, p in enumerate(qpos):
                    if penal:
                        counts[p] = token_counts(
                            list(req.all_ids) + list(prefixes[j]), v)
                    if req.logit_bias:
                        for t, b in req.logit_bias.items():
                            bias[p, t] += b
                    if req._constraint is not None:
                        st = req._constraint.state if j == 0 \
                            else states[j - 1]
                        if st is not None:
                            req._constraint.bias_row(bias[p], state=st)
        if bias is None:
            chan = self._neutral_chan.get(tb)
            if chan is None:
                chan = jnp.zeros((tb, self.vocab_size), jnp.float32)
                self._neutral_chan[tb] = chan
            bias = counts = chan
        return {"tb": tb, "starts": starts, "ids": ids,
                "tables": tables, "positions": positions,
                "tok_rows": tok_rows, "row_start": row_start,
                "row_qlen": row_qlen, "row_pos0": row_pos0,
                "cow_src": cow_src, "cow_dst": cow_dst, "knobs": knobs,
                "bias": bias, "counts": counts,
                "adapter_rows": adapter_rows}

    def _launch_packed(self, rows, pk, finished):
        """Launch one packed ragged step and commit its results — the
        shared back half of the sync path and a claimed lookahead
        plan."""
        starts = pk["starts"]
        self.last_launches.append(("ragged", pk["tb"]))
        self._launch_count += 1
        out = self._launch("ragged", [row.request for row in rows],
                           lambda: self._ragged_launch(
                               rows, pk["ids"], pk["tables"],
                               pk["positions"], pk["tok_rows"],
                               pk["row_start"], pk["row_qlen"],
                               pk["row_pos0"], pk["cow_src"],
                               pk["cow_dst"], pk["knobs"], pk["bias"],
                               pk["counts"], pk["adapter_rows"]))
        if out is None:
            # quarantined; reservations rolled back.  Tree fork chains
            # this step scheduled never launched — free them.
            for row in rows:
                if row.kind == "tree" and \
                        self.block_manager.has_seq(row.table_id):
                    self.block_manager.free(row.table_id)
            return
        nxt, logits, self.kv_cache = out
        # async lookahead: the launch above is dispatched but NOT yet
        # synced — np.asarray(nxt) below is the blocking pull.  Plan
        # and pack step N+1 here so that host work runs entirely under
        # step N's device window.
        self._stage_next(rows)
        # adversarial window: a staged plan exists but is not yet
        # claimed — exactly where stage-vs-abort races live
        interleave_point("staged")
        nxt = np.asarray(nxt)  # noqa: H001 (the one host pull per step)
        row_logits = self._fetch_sampling_rows(rows, starts, logits)

        # commit phase A: decode/verify rows, in scheduler order — the
        # same _commit_verified-if-any-drafts-else-vectorized split the
        # retired per-phase steps made, so gumbel draw order (and thus
        # seeded output) is bitwise preserved.  Tree sibling rows are
        # looked up by their main row's request and walked inside
        # _commit_verified.
        nonchunk = [(ri, row) for ri, row in enumerate(rows)
                    if row.kind not in ("chunk", "tree")]
        tree_rows = {row.request.request_id: (ri, row)
                     for ri, row in enumerate(rows)
                     if row.kind == "tree"}
        if any(row.request.draft_tokens for _, row in nonchunk):
            self.stats["spec_steps"] += 1
            for ri, row in nonchunk:
                s0 = starts[ri]
                tree = None
                tr = tree_rows.pop(row.request.request_id, None)
                if tr is not None:
                    tri, trow = tr
                    ts = starts[tri]
                    tree = (trow.table_id, trow.sibling,
                            nxt[ts:ts + 2], row_logits.get(tri))
                self._commit_verified(row.request,
                                      nxt[s0:s0 + row.length],
                                      row_logits.get(ri), finished,
                                      tree=tree)
            for _tri, trow in tree_rows.values():
                # defensive: a sibling row whose main row vanished
                if self.block_manager.has_seq(trow.table_id):
                    self.block_manager.free(trow.table_id)
        elif nonchunk:
            entries = []
            for ri, row in nonchunk:
                req = row.request
                req.num_cached += 1
                if req.num_cached % self.block_size == 0:
                    self._register_full_blocks(req)
                lg = row_logits.get(ri)
                entries.append((req, nxt[starts[ri]],
                                None if lg is None else lg[0]))
            self._commit_tokens(entries, finished)
        # commit phase B: chunks in schedule order; only the final
        # chunk's last token emits
        for ri, row in enumerate(rows):
            if row.kind != "chunk":
                continue
            req, ch = row.request, row.chunk
            req.num_cached = ch.start + ch.length
            self._register_full_blocks(req)
            if ch.is_final:
                lg = row_logits.get(ri)
                # n>1 forks split HERE — prompt fully cached, before
                # the first token commits — so every family member
                # samples its first token from this shared final-chunk
                # logits row under its own seeded stream
                fam = self._fork_family(req)
                tok = nxt[starts[ri] + row.length - 1]
                self._commit_tokens(
                    [(r, tok, None if lg is None else lg[0])
                     for r in fam], finished)

    # --------------------------------------------------- async lookahead --
    def _stage_next(self, rows):
        """Plan + pack step N+1 while step N's launch is in flight.

        Runs between dispatch and the blocking token pull, so the work
        hides under device time.  Staging only fires when the next
        step is PROVABLY a plain all-decode step whose schedule cannot
        depend on step N's outcome:

        - ``lookahead=True``, no fault injector (alloc-fault schedules
          are per step — claiming step N+1's slots at step N would
          misalign them), no model drafter (its draft phase launches
          device work per step);
        - no waiting requests (admission could change everything),
          every running request fully prefilled with no pending
          drafts, no sampling-pipeline rows (their bias/counts operands
          depend on the not-yet-committed token), and the current step
          itself all-decode (verify/chunk commits move row geometry);
        - no append would COW (a COW rewires the fork sibling's table,
          which a discard could not invert — and the page-copy pair
          must be issued by the launch that owns the append).

        One slot per running request is claimed NOW; the claim is
        validated (and the unknown query token patched in) by
        _claim_staged, or rolled back exactly by _discard_staged.
        With an n-gram drafter attached, claiming additionally
        requires every re-proposal to come back empty — a non-empty
        draft means the sync scheduler would have built a verify row
        instead."""
        if not self.lookahead or self.faults is not None \
                or self._draft_params is not None:
            return
        sch = self.scheduler
        running = sch.running
        if sch.waiting or not running:
            return
        for row in rows:
            if row.kind != "decode":
                return
        bm = self.block_manager
        for r in running:
            if not r.prefill_done or r.uses_pipeline \
                    or r.draft_tokens or bm.would_cow(r.request_id):
                return
        plan_rows, claimed = [], []
        try:
            for r in running:
                bm.append_slot(r.request_id)
                claimed.append(r)
                plan_rows.append(RaggedRow(
                    r, "decode", bm.num_tokens(r.request_id) - 1, 1))
        except NoFreeBlocksError:
            # exact inverse, newest claim first: the LIFO free list
            # ends up byte-identical to the never-staged state
            for r in reversed(claimed):
                bm.rollback_slots(r.request_id, 1)
            return
        pk = self._pack_ragged(plan_rows, [])
        self._staged = (plan_rows, pk)
        self._staged_epoch = self._plan_epoch
        self.stats["staged_steps"] += 1
        self.events.append(
            (self._step_index, "step_staged", len(plan_rows)))

    def _claim_staged(self):
        """Validate and take the staged step-N+1 plan, or discard it.

        The plan epoch catches every lifecycle mutation since staging
        (add/abort/finish/fork/quarantine/migration); the per-row
        checks pin the running set and its book state to exactly what
        the stager assumed; the drafter re-proposal check keeps
        speculation intact (any non-empty draft → the sync scheduler
        must build this step).  On success the one operand staging
        couldn't know — each row's query token, committed by step N —
        is patched into the packed ids and the plan launches as-is."""
        staged, self._staged = self._staged, None
        if staged is None:
            return None
        t0 = self._timer()
        try:
            plan_rows, pk = staged
            running = self.scheduler.running
            valid = (self._staged_epoch == self._plan_epoch
                     and not self.scheduler.waiting
                     and len(running) == len(plan_rows))
            if valid:
                for row, r in zip(plan_rows, running):
                    if row.request is not r or r.status != RUNNING \
                            or not r.prefill_done or r.draft_tokens \
                            or r.uses_pipeline \
                            or row.start != r.num_cached:
                        valid = False
                        break
            if valid and self.drafter is not None:
                spare = self.token_budget - len(running)
                if spare > 0:
                    for r in running:
                        cap = min(spare, r.max_new_tokens
                                  - len(r.output_ids) - 1)
                        if cap > 0 and self.drafter.propose(
                                r.all_ids, cap,
                                request_id=r.request_id):
                            valid = False
                            break
            if not valid:
                self._discard_staged(plan_rows)
                return None
            for ri, row in enumerate(plan_rows):
                pk["ids"][pk["row_start"][ri]] = \
                    row.request.all_ids[-1]
            return plan_rows, pk
        finally:
            with self._gauge_lock:
                self._host_plan_s += self._timer() - t0

    def _discard_staged(self, plan_rows):
        """Roll back the staged slot claims exactly — one slot per
        still-live staged row, newest first (LIFO free-list inverse) —
        so the subsequent sync schedule allocates the very pages the
        never-staged engine would have."""
        bm = self.block_manager
        for row in reversed(plan_rows):
            req = row.request
            if req.status == RUNNING and req.prefill_done \
                    and bm.has_seq(req.request_id):
                extra = bm.num_tokens(req.request_id) - req.num_cached
                if extra > 0:
                    bm.rollback_slots(req.request_id, extra)

    # ------------------------------------------------- model drafting --
    def _draft_phase(self):
        """Fill the model drafter's proposals for this step.

        Runs BEFORE scheduling: for every fully-prefilled running
        request whose prompt-lookup draft comes up empty (the hybrid
        contract — n-gram hits are free and win), the draft model runs
        through the SAME ragged executable against its own pools:

        1. catch-up — the valid draft-KV prefix is the longest common
           prefix of the drafter's fed-token history and the real
           ``all_ids`` (K/V at p depends on tokens [0, p] only);
           everything past it is re-fed in token_budget-bounded
           chunks, and the final fed position's argmax is the first
           greedy draft token (for ``method="tree"``, the runner-up of
           that same logits row becomes the sibling branch);
        2. chain — up to ``min(K, cap) - 1`` batched one-token greedy
           decode launches extend every candidate's chain in lockstep.

        Draft-pool OOM for a request just skips drafting it this step
        (its draft state is dropped and rebuilt later); plain decode
        correctness never depends on this phase."""
        dr = self.drafter
        dbm = self._draft_bm
        K = self.spec.num_tokens
        dr.proposals = {}
        dr.siblings = {}
        live = {r.request_id for r in self.scheduler.running}
        live.update(r.request_id for r in self.scheduler.waiting)
        for rid in [r for r in dr.history if r not in live]:
            dr.forget(rid)
            if dbm.has_seq(rid):
                dbm.free(rid)
        cands = []
        for r in self.scheduler.running:
            if not r.prefill_done:
                continue
            cap = min(K, r.max_new_tokens - len(r.output_ids) - 1)
            if cap <= 0:
                continue
            if dr._ngram.propose(r.all_ids, cap):
                continue            # free n-gram draft wins this row
            cands.append((r, cap))
        if not cands:
            return
        # -- draft-pool bookkeeping + catch-up work list
        feeds = []
        for r, cap in cands:
            rid = r.request_id
            H = r.all_ids
            hist = dr.history.get(rid, [])
            lcp = 0
            hmax = min(len(hist), len(H) - 1)
            while lcp < hmax and hist[lcp] == H[lcp]:
                lcp += 1
            try:
                if not dbm.has_seq(rid):
                    lcp = 0
                    dbm.allocate(rid, len(H))
                else:
                    extra = dbm.num_tokens(rid) - lcp
                    if extra > 0:
                        dbm.rollback_slots(rid, extra)
                    dbm.append_slots(rid, len(H) - lcp)
            except NoFreeBlocksError:
                if dbm.has_seq(rid):
                    dbm.free(rid)
                dr.history.pop(rid, None)
                continue
            feeds.append((r, cap, lcp, H))
            dr.history[rid] = list(H)
        if not feeds:
            return
        # -- catch-up launches: chunk every pending feed through the
        # token budget; a row's FINAL fed position yields g0 (and,
        # for trees, the runner-up sibling)
        chains = {}
        want_sib = self.spec.method == "tree"
        work = [[r, cap, lcp, H] for r, cap, lcp, H in feeds]
        while work:
            entries, meta, used = [], [], 0
            for w in work:
                if len(entries) >= self.max_batch \
                        or used >= self.token_budget:
                    break
                r, cap, start, H = w
                c = min(len(H) - start, self.token_budget - used)
                entries.append((r.request_id, H[start:start + c],
                                start))
                w[2] = start + c
                used += c
                meta.append((r, w[2] == len(H)))
            work = [w for w in work if w[2] < len(w[3])]
            nxt, logits, starts = self._draft_launch(entries)
            done = [(i, starts[i] + len(entries[i][1]) - 1)
                    for i, (_r, fin) in enumerate(meta) if fin]
            lg = None
            if want_sib and done:
                lg = np.asarray(logits[np.asarray(  # draft logits rows for the tree sibling, by design
                    [p for _i, p in done], np.int32)])
            for k, (i, p) in enumerate(done):
                r = meta[i][0]
                g0 = int(nxt[p])  # host argmax, already fetched
                chains[r.request_id] = [g0]
                if lg is not None:
                    row = np.array(lg[k], np.float64)
                    row[g0] = -np.inf
                    dr.siblings[r.request_id] = int(np.argmax(row))  # host math on fetched row
        # -- greedy chain: K-1 batched one-token decode launches
        act = [(r, cap) for r, cap, _lcp, _H in feeds
               if chains.get(r.request_id)]
        for _depth in range(1, K):
            act = [(r, cap) for r, cap in act
                   if len(chains[r.request_id]) < cap]
            if not act:
                break
            entries, kept = [], []
            for r, cap in act:
                rid = r.request_id
                try:
                    dbm.append_slot(rid)
                except NoFreeBlocksError:
                    continue        # freeze this chain at its depth
                entries.append((rid, [chains[rid][-1]],
                                dbm.num_tokens(rid) - 1))
                kept.append((r, cap))
            if not entries:
                break
            nxt, _logits, starts = self._draft_launch(entries)
            for i, (r, _cap) in enumerate(kept):
                chains[r.request_id].append(int(nxt[starts[i]]))  # host argmax, already fetched
            act = kept
        # the last chain token was predicted but never FED, so the
        # history (what the draft pool encodes) excludes it
        for r, cap, _lcp, H in feeds:
            rid = r.request_id
            chain = chains.get(rid)
            if not chain:
                continue
            dr.proposals[rid] = list(chain[:cap])
            dr.history[rid] = list(H) + chain[:-1]

    def _draft_launch(self, entries):
        """One ragged launch of the DRAFT model: the same jitted
        executable (params are its first operand — zero new compiles),
        the draft pools, neutral sampling operands, LoRA slot 0 (the
        zero base identity).  ``entries`` are ``(seq_id, tokens,
        pos0)`` rows over the draft BlockManager's tables.  Returns
        (argmax np [Tb], logits device [Tb, V], starts)."""
        total = sum(len(toks) for _sid, toks, _p in entries)
        tb = bucket_size(total, self.token_budget, floor=8)
        rmax = self.max_batch
        ids = np.zeros(tb, np.int32)
        positions = np.full(tb, -1, np.int32)
        tok_rows = np.zeros(tb, np.int32)
        tables = np.zeros((rmax, self.max_pages), np.int32)
        row_start = np.zeros(rmax, np.int32)
        row_qlen = np.zeros(rmax, np.int32)
        row_pos0 = np.zeros(rmax, np.int32)
        starts = []
        s = 0
        for ri, (sid, toks, p0) in enumerate(entries):
            n = len(toks)
            starts.append(s)
            ids[s:s + n] = toks
            positions[s:s + n] = np.arange(p0, p0 + n)
            tok_rows[s:s + n] = ri
            bt = self._draft_bm.block_table(sid)
            tables[ri, :len(bt)] = bt
            row_start[ri] = s
            row_qlen[ri] = n
            row_pos0[ri] = p0
            s += n
        zr = np.zeros(rmax, np.int32)
        cow_dst = np.full(rmax, self.num_blocks, np.int32)
        knobs = neutral_row_params(rmax)
        chan = self._neutral_chan.get(tb)
        if chan is None:
            chan = jnp.zeros((tb, self.vocab_size), jnp.float32)
            self._neutral_chan[tb] = chan
        lora_ops = ((jnp.asarray(zr),)
                    if self.lora is not None else ())
        self.last_launches.append(("ragged", tb))
        self._launch_count += 1
        with profiler.RecordEvent("llm_engine::draft"):
            nxt, logits, self._draft_cache = self._ragged(
                self._draft_params, jnp.asarray(ids),
                self._draft_cache, jnp.asarray(tables),
                jnp.asarray(positions), jnp.asarray(tok_rows),
                jnp.asarray(row_start), jnp.asarray(row_qlen),
                jnp.asarray(row_pos0), jnp.asarray(zr),
                jnp.asarray(cow_dst),
                *(jnp.asarray(k) for k in knobs), chan, chan,
                *lora_ops)
        return np.asarray(nxt), logits, starts  # noqa: H001 (draft argmax pull, one per draft launch by design)

    def _ragged_launch(self, rows, ids, tables, positions, tok_rows,
                       row_start, row_qlen, row_pos0, cow_src, cow_dst,
                       knobs, bias, counts, adapter_rows=None):
        """Execute ONE packed ragged launch — the device-step seam.
        Numpy operands in, the executable's output tuple out.  ``rows``
        is the host-side schedule the operands were packed from: the
        real engine ignores it; the discrete-event simulator's
        SimEngine overrides this method and reads ``rows`` to
        synthesize the argmax vector from its token oracle instead of
        running the device.  ``knobs`` is the six-tuple of per-row
        sampling vectors; ``bias``/``counts`` the [tb, V] channels
        (possibly the cached neutral device array); ``adapter_rows``
        the per-row LoRA slot vector (None on a LoRA-free engine — the
        operand, and hence the executable signature, only exists when
        lora= is configured)."""
        del rows  # the real launch needs only the packed operands
        lora_ops = (() if adapter_rows is None
                    else (jnp.asarray(adapter_rows),))
        with profiler.RecordEvent("llm_engine::ragged"):
            return self._ragged(
                self.params, jnp.asarray(ids), self.kv_cache,
                jnp.asarray(tables), jnp.asarray(positions),
                jnp.asarray(tok_rows), jnp.asarray(row_start),
                jnp.asarray(row_qlen), jnp.asarray(row_pos0),
                jnp.asarray(cow_src), jnp.asarray(cow_dst),
                *(jnp.asarray(k) for k in knobs),
                jnp.asarray(bias), jnp.asarray(counts), *lora_ops)

    def _fetch_sampling_rows(self, rows, starts, logits):
        """Fetch ONLY the logits of tokens that sample: greedy batches
        transfer just the argmax vector, and a mixed batch pays for its
        sampling tokens, not the whole [Tb, V] logits.  Returns
        {row_index: [n, V] host array} — a decode row's single token, a
        verify row's 1 + K tokens, a FINAL chunk's last token.
        Greedy rows that asked for ``logprobs`` fetch too — the
        report is computed on the host from the processed row."""
        idx, spans = [], {}
        for ri, row in enumerate(rows):
            if row.request.temperature <= 0.0 \
                    and not row.request.logprobs:
                continue
            if row.kind == "chunk":
                if not row.chunk.is_final:
                    continue
                lo, n = starts[ri] + row.length - 1, 1
            else:
                lo, n = starts[ri], row.length
            spans[ri] = (len(idx), n)
            idx.extend(range(lo, lo + n))
        if not spans:
            return {}
        sel = np.asarray(logits[np.asarray(idx, np.int32)])  # noqa: H001 (fetches only the sampling rows)
        return {ri: sel[o:o + n] for ri, (o, n) in spans.items()}

    def _sample_token(self, req, logits):
        """Gumbel-max sample of one host logits row from the request's
        stream (``seed=``) or the engine stream."""
        z = np.asarray(logits, np.float64) / req.temperature  # noqa: H001 (host row, already fetched)
        if req.seed is not None:
            if req._sample_rng is None:
                req._sample_rng = np.random.RandomState(req.seed)
            rng = req._sample_rng
        else:
            rng = self._rng
        return int(np.argmax(z + rng.gumbel(size=z.shape)))  # noqa: H001 (host sampling math)

    def _check_stop(self, req):
        """Stop-string check after an emitted token (host work by
        design — sampling.StopStringWatcher).  Returns the matched
        string (also recorded on the request) or None."""
        if not req.stop:
            return None
        if req._stop_watcher is None:
            req._stop_watcher = StopStringWatcher(
                req.stop, self.detokenizer)
        hit = req._stop_watcher.check(req.output_ids)
        if hit is not None:
            req.matched_stop = hit
        return hit

    def _fork_family(self, req):
        """Split an ``n>1`` request into its fork family, returning the
        members in sampling order (parent first).  Called at final-
        chunk commit, AFTER the whole prompt's K/V landed but BEFORE
        the first token samples: BlockManager.fork refcounts the
        parent's pages (zero data copied now — a child's first private
        page materializes later as a COW pair inside the ragged
        executable), and child ``k`` samples under ``seed + k``, which
        is exactly the stream an independent replay with that seed
        would use — the fork-vs-replay exactness gate."""
        if req.n <= 1 or req._forked:
            return [req]
        req._forked = True
        self._invalidate_plan()
        fam = [req]
        for k in range(1, req.n):
            cid = f"{req.request_id}.{k}"
            self.block_manager.fork(req.request_id, cid)
            child = Request(
                request_id=cid, prompt_ids=req.prompt_ids,
                max_new_tokens=req.max_new_tokens,
                eos_token_id=req.eos_token_id,
                temperature=req.temperature,
                seed=req.seed + k, deadline=req.deadline,
                top_k=req.top_k, top_p=req.top_p, min_p=req.min_p,
                repetition_penalty=req.repetition_penalty,
                presence_penalty=req.presence_penalty,
                frequency_penalty=req.frequency_penalty,
                logit_bias=req.logit_bias, logprobs=req.logprobs,
                stop=req.stop, grammar=req.grammar,
                n=1, parent_id=req.request_id, fork_index=k,
                adapter_id=req.adapter_id,
                arrival_time=req.arrival_time,
                num_cached=req.num_cached,
                num_prefill_tokens=req.num_prefill_tokens,
                status=RUNNING)
            if req.grammar is not None:
                child._constraint = ConstraintState(req.grammar)
            self._requests[cid] = child
            self.scheduler.running.append(child)
            self.events.append(
                (self._step_index, "fork", req.request_id, cid))
            fam.append(child)
        return fam

    def _commit_tokens(self, entries, finished):
        """Commit one token per (req, argmax, logits) entry, in order.
        Engine-stream sampling rows share ONE vectorized gumbel draw:
        the legacy RandomState fills an (n, V) array in C order, so the
        batch is bitwise identical to the n sequential per-row draws it
        replaces — seeded outputs don't move.  Per-request streams
        (``seed=``) draw row-by-row as before (each owns one row here).
        """
        eng_rows = [j for j, (r, _t, _lg) in enumerate(entries)
                    if r.temperature > 0.0 and r.seed is None]
        picked = {}
        if eng_rows:
            z = np.stack([np.asarray(entries[j][2], np.float64)  # noqa: H001 (host rows, already fetched)
                          / entries[j][0].temperature for j in eng_rows])
            g = self._rng.gumbel(size=z.shape)
            for j, t in zip(eng_rows, np.argmax(z + g, axis=-1)):
                picked[j] = int(t)  # noqa: H001 (host sampling math)
        for j, (req, argmax_token, logits) in enumerate(entries):
            if req.temperature > 0.0:
                tok = picked[j] if j in picked \
                    else self._sample_token(req, logits)
            else:
                tok = int(argmax_token)  # noqa: H001 (host token, already fetched)
            req.output_ids.append(tok)
            self.stats["tokens_generated"] += 1
            if req.logprobs and logits is not None:
                req.logprobs_content.append(
                    top_logprobs(logits, req.logprobs, tok))
            if req._constraint is not None:
                req._constraint.advance(tok)  # intentional host grammar-state advance
            if self._check_stop(req) is not None:
                self._finish(req, "stop", finished)
            elif (req.eos_token_id is not None
                    and tok == req.eos_token_id):
                self._finish(req, "stop", finished)
            elif len(req.output_ids) >= req.max_new_tokens:
                self._finish(req, "length", finished)

    def _commit_verified(self, req, argmax_row, logits_row, finished,
                         tree=None):
        """Acceptance + bulk commit for one verified row.

        Tokens emit in position order; a sampled request consumes
        exactly one gumbel draw per EMITTED token (the draft is a
        point-mass proposal, so sample-and-match is exact rejection
        sampling), keeping its stream bitwise aligned with the
        non-speculative engine.  Unaccepted slots roll back BEFORE
        prefix-cache registration, so the cache only ever sees pages
        full of accepted tokens.

        ``tree`` — ``(tmp_id, sibling_token, sib_argmax, sib_logits)``
        — is the request's 2-token sibling row (tree speculation): if
        the FIRST emitted token misses the chain draft but equals the
        sibling token, the sibling row already holds that branch's K/V
        and its position-1 logits, so a SECOND token commits from them
        (one extra gumbel draw, same per-emitted-token stream
        discipline) and the fork chain is promoted to be the request's
        table.  Any other outcome frees the fork chain; either way the
        books end the step exactly like a non-tree commit of the same
        emitted count."""
        drafts = req.draft_tokens
        req.draft_tokens = []
        d = len(drafts)
        self.stats["draft_tokens"] += d
        tmp_id = sib_tok = sib_argmax = sib_logits = None
        if tree is not None:
            tmp_id, sib_tok, sib_argmax, sib_logits = tree
            self.stats["draft_tokens"] += 1  # the sibling proposal
        promoted = False
        reason = None
        emitted = 0
        for j in range(d + 1):
            if req.temperature > 0.0:
                tok = self._sample_token(req, logits_row[j])
            else:
                tok = int(argmax_row[j])  # noqa: H001 (host row, already fetched)
            req.output_ids.append(tok)
            emitted += 1
            self.stats["tokens_generated"] += 1
            if req.logprobs and logits_row is not None:
                req.logprobs_content.append(
                    top_logprobs(logits_row[j], req.logprobs, tok))
            if req._constraint is not None:
                # the emitted token came from MASKED logits (position
                # j's mask was packed from the state after drafts[:j],
                # which is exactly the path walked so far), so the
                # transition always exists
                req._constraint.advance(tok)  # intentional host grammar-state advance
            matched = j < d and tok == drafts[j]
            if matched:
                self.stats["accepted_tokens"] += 1
            if self._check_stop(req) is not None:
                reason = "stop"
                break
            if req.eos_token_id is not None and tok == req.eos_token_id:
                reason = "stop"
                break
            if len(req.output_ids) >= req.max_new_tokens:
                reason = "length"
                break
            if not matched:
                if j == 0 and tmp_id is not None and tok == sib_tok:
                    # tree hit: the target's real first token is the
                    # sibling branch — its K/V and next-token scores
                    # are already on the fork chain
                    self.stats["accepted_tokens"] += 1
                    self.stats["tree_hits"] += 1
                    promoted = True
                    if req.temperature > 0.0:
                        tok2 = self._sample_token(req, sib_logits[1])
                    else:
                        tok2 = int(sib_argmax[1])  # host row, already fetched
                    req.output_ids.append(tok2)
                    emitted += 1
                    self.stats["tokens_generated"] += 1
                    if req.logprobs and sib_logits is not None:
                        req.logprobs_content.append(top_logprobs(
                            sib_logits[1], req.logprobs, tok2))
                    if self._check_stop(req) is not None:
                        reason = "stop"
                    elif req.eos_token_id is not None \
                            and tok2 == req.eos_token_id:
                        reason = "stop"
                    elif len(req.output_ids) >= req.max_new_tokens:
                        reason = "length"
                break
        pages_before = req.num_cached // self.block_size
        req.num_cached += emitted
        if promoted:
            # the fork chain holds the branch's K/V for positions
            # 0..num_cached-1 and carries exactly num_cached slots (2
            # appends on a fork of the T-1-token chain) — adopt it and
            # drop the main chain with its now-stale reservation
            self.block_manager.promote_fork(req.request_id, tmp_id)
        else:
            # the scheduler reserved 1 + d slots; keep the emitted
            # ones.  K/V through position num_cached + emitted - 1
            # stays valid: every kept position's token matched its
            # draft (the last emitted token's slot is the first one
            # rolled back, preserving the num_cached == len(all_ids)
            # - 1 decode invariant).
            self.block_manager.rollback_slots(req.request_id,
                                              1 + d - emitted)
            if tmp_id is not None and \
                    self.block_manager.has_seq(tmp_id):
                self.block_manager.free(tmp_id)
        if req.num_cached // self.block_size > pages_before:
            self._register_full_blocks(req)
        if reason is not None:
            self._finish(req, reason, finished)

    def spec_stats(self):
        """Speculative-decoding counters (acceptance rate for benches)."""
        s = self.stats
        prop = s["draft_tokens"]
        out = {"spec_steps": s["spec_steps"],
               "draft_tokens": prop,
               "accepted_tokens": s["accepted_tokens"],
               "acceptance_rate":
                   s["accepted_tokens"] / prop if prop else 0.0}
        if self.spec is not None:
            out["method"] = self.spec.method
        if isinstance(self.drafter, DraftModelDrafter):
            out["model_drafts"] = self.drafter.model_drafts
            out["ngram_drafts"] = self.drafter.ngram_drafts
            out["tree_hits"] = s["tree_hits"]
        return out

    def _drafter_forget(self, request_id):
        """Drop model-drafter state (and the draft pool's pages) for a
        request leaving the engine by any path."""
        if isinstance(self.drafter, DraftModelDrafter):
            self.drafter.forget(request_id)
            if self._draft_bm is not None \
                    and self._draft_bm.has_seq(request_id):
                self._draft_bm.free(request_id)

    def _finish(self, req, reason, finished):
        self._invalidate_plan()
        self._drafter_forget(req.request_id)
        self.scheduler.remove_running(req)
        req.status = FINISHED
        req.finish_reason = reason
        del self._requests[req.request_id]
        self.events.append(
            (self._step_index, "finish", req.request_id, reason))
        finished.append(RequestOutput(
            req.request_id, req.prompt_ids, req.output_ids, reason,
            req.num_preemptions,
            logprobs=req.logprobs_content if req.logprobs else None,
            matched_stop=req.matched_stop))

    # ----------------------------------------------------------- generate --
    def generate(self, prompts, max_new_tokens=32, eos_token_id=None,
                 temperature=0.0, seed=None, deadline_ms=None,
                 top_k=0, top_p=1.0, min_p=0.0, repetition_penalty=1.0,
                 presence_penalty=0.0, frequency_penalty=0.0,
                 logit_bias=None, logprobs=0, stop=None, grammar=None,
                 n=1, adapter_id=None):
        """Batch convenience: returns one [T+new] int array per prompt
        (ragged list, request order preserved) — or, for ``n > 1``,
        one LIST of n arrays per prompt (parent first, then forks
        1..n-1).  ``seed`` gives every request of this call its own
        deterministic sampling stream (independent of arrival
        interleaving); default None keeps the engine-level RNG.
        ``deadline_ms`` applies per request; a request past it finishes
        with FinishReason.deadline and returns whatever tokens it
        emitted.  The sampling suite (top_k/top_p/min_p, penalties,
        logit_bias, logprobs, stop, grammar) applies to every request
        of the call — see :mod:`.sampling` for semantics."""
        # validate shared knobs BEFORE any request is queued, so a bad
        # call leaves the engine empty instead of half-submitted
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {temperature}")
        if deadline_ms is not None and \
                (isinstance(deadline_ms, bool)
                 or not isinstance(deadline_ms, (int, float, np.integer,
                                                 np.floating))
                 or deadline_ms <= 0):
            raise ValueError(
                f"deadline_ms must be a positive number of "
                f"milliseconds, got {deadline_ms!r}")
        validate_sampling(top_k, top_p, min_p, repetition_penalty,
                          presence_penalty, frequency_penalty,
                          logit_bias, logprobs, stop, n,
                          vocab_size=self.vocab_size)
        if isinstance(prompts, np.ndarray) and prompts.ndim == 2:
            prompts = list(prompts)
        elif not isinstance(prompts, (list, tuple)):
            prompts = [prompts]
        order = [self.add_request(p, max_new_tokens=max_new_tokens,
                                  eos_token_id=eos_token_id,
                                  temperature=temperature, seed=seed,
                                  deadline_ms=deadline_ms,
                                  top_k=top_k, top_p=top_p, min_p=min_p,
                                  repetition_penalty=repetition_penalty,
                                  presence_penalty=presence_penalty,
                                  frequency_penalty=frequency_penalty,
                                  logit_bias=logit_bias,
                                  logprobs=logprobs, stop=stop,
                                  grammar=grammar, n=n,
                                  adapter_id=adapter_id)
                 for p in prompts]
        outs = {}
        while self.has_unfinished():
            for fo in self.step():
                outs[fo.request_id] = fo
        if n == 1:
            return [outs[rid].all_ids.astype(np.int64) for rid in order]
        fams = []
        for rid in order:
            group = [outs[rid].all_ids.astype(np.int64)]
            for k in range(1, n):
                cid = f"{rid}.{k}"
                if cid in outs:        # absent only if shed pre-fork
                    group.append(outs[cid].all_ids.astype(np.int64))
            fams.append(group)
        return fams


class AsyncLLMEngine:
    """Thread-safe front of an LLMEngine: callers submit from any thread
    (one per socket connection in PredictorServer) and block on their own
    result while a single background thread steps the engine — concurrent
    callers batch into one decode executable automatically.

    The device call runs OUTSIDE the condition lock, so ``submit()``
    returns while a step is in flight — a request arriving mid-step is
    admitted by the NEXT schedule() pass, which is the whole point of
    continuous batching.  This is safe because ``add_request`` only
    appends to the scheduler's waiting queue and the request dict (both
    GIL-atomic list/dict ops); all other engine state is touched solely
    by the stepping thread.

    Lifecycle: ``abort(request_id)`` queues a cancel that the stepping
    thread applies between device calls (engine state stays
    single-threaded); ``result(timeout=)`` expiring ABORTS the request
    — a caller that gave up must not leave its request generating (and
    holding pages) forever.  ``drain(timeout_s=)`` quiesces without
    stopping: in-flight work completes, racing submits shed (their
    callers still get a per-request FinishReason), and admission
    reopens afterwards.  ``close()`` aborts everything still in
    flight, reclaims the pages, joins the worker, and raises if the
    thread survives — a close that silently leaks a live stepping
    thread is how a "drained" replica keeps touching the device.
    """

    _worker_seq = 0     # deterministic worker thread names (interleave)

    def __init__(self, engine):
        self.engine = engine
        # drain deadlines ride the ENGINE's injected clock, so a
        # VirtualClock simulation drains in virtual seconds (satellite
        # of the clock-injection audit: no raw time.monotonic here)
        self._clock = getattr(engine, "_clock", time.monotonic)
        self._cond = threading.Condition()
        self._results = {}          # request_id -> RequestOutput
        self._aborts = set()        # rids to cancel, applied by the loop
        self._abandoned = set()     # rids whose caller gave up (timeout)
        self._draining = False
        self._stopped = False
        AsyncLLMEngine._worker_seq += 1
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"llm-async-worker-{AsyncLLMEngine._worker_seq}")
        self._thread.start()

    def _loop(self):
        while True:
            with self._cond:
                while not self._stopped and not self._aborts and \
                        not self.engine.has_unfinished():
                    interleave_wait(self._cond, 0.5)
                if self._stopped:
                    break
                aborts, self._aborts = self._aborts, set()
            # engine state is touched ONLY on this thread: queued
            # aborts apply here, between device calls
            interleave_point("loop")
            for rid in aborts:
                self.engine.abort_request(rid)
            finished = self.engine.step()    # device call: lock NOT held
            self._publish(finished)
        # stopped: abort whatever is still in flight so pages are
        # reclaimed and blocked result() callers get a terminal output
        # instead of waiting on a dead thread (getattr: stub engines
        # without the lifecycle surface just stop stepping)
        abort = getattr(self.engine, "abort_request", None)
        if abort is not None:
            for rid in list(getattr(self.engine, "_requests", ())):
                abort(rid)
            while self.engine.has_unfinished():
                self._publish(self.engine.step())
        with self._cond:
            self._cond.notify_all()

    def _publish(self, finished):
        if not finished:
            return
        with self._cond:
            for fo in finished:
                if fo.request_id in self._abandoned:
                    self._abandoned.discard(fo.request_id)
                    continue        # caller timed out and walked away
                self._results[fo.request_id] = fo
            self._cond.notify_all()

    def submit(self, prompt_ids, **kwargs):
        interleave_point("submit")
        with self._cond:
            if self._stopped:
                raise RuntimeError("engine stopped")
            # masked: points inside add_request must not deschedule a
            # thread that HOLDS _cond (token-vs-lock deadlock)
            with masked():
                rid = self.engine.add_request(prompt_ids, **kwargs)
            self._cond.notify_all()
            return rid

    def abort(self, request_id):
        """Queue a cancel for ``request_id``; the stepping thread
        applies it before its next device call and the aborted output
        (FinishReason.aborted) arrives like any other result."""
        interleave_point("abort-queue")
        with self._cond:
            self._aborts.add(request_id)
            self._cond.notify_all()

    def result(self, request_id, timeout=None):
        """Block until the request finishes; returns its RequestOutput.
        On timeout the request is ABORTED (pages reclaimed, output
        discarded) before TimeoutError is raised — an abandoned request
        never keeps generating."""
        with self._cond:
            # explicit predicate loop (not wait_for): the wait chunks
            # ride interleave_wait, so a blocked caller participates in
            # a deterministic schedule, and the deadline rides the
            # engine's injected clock
            deadline = (None if timeout is None
                        else self._clock() + float(timeout))
            while not (request_id in self._results or self._stopped):
                if deadline is not None and self._clock() >= deadline:
                    break
                chunk = 0.1 if deadline is None else \
                    max(0.0, min(0.1, deadline - self._clock()))
                interleave_wait(self._cond, chunk)
            ok = request_id in self._results or self._stopped
            if not ok:
                self._abandoned.add(request_id)
                self._aborts.add(request_id)
                self._cond.notify_all()
                raise TimeoutError(
                    f"request {request_id} timed out and was aborted")
            if request_id in self._results:
                return self._results.pop(request_id)
            # stopped before this request ever produced an output
            raise RuntimeError("engine stopped")

    def generate(self, prompt_ids, timeout=None, **kwargs):
        return self.result(self.submit(prompt_ids, **kwargs),
                           timeout=timeout)

    def drain(self, timeout_s=None):
        """Graceful quiesce WITHOUT stopping the worker: admission is
        closed (the engine sheds, so a submit racing the drain still
        gets a terminal output — its ``result()`` returns
        FinishReason.shed; nothing is silently dropped), every
        in-flight request runs to completion, and admission reopens on
        return.  ``timeout_s`` bounds the wait: requests still running
        when it expires are aborted (their callers see
        FinishReason.aborted), so drain() always terminates with zero
        pages leaked.  Safe to call from any thread; the stepping
        thread keeps publishing results throughout."""
        with self._cond:
            if self._stopped:
                raise RuntimeError("engine stopped")
            self._draining = True
            # the engine-level flag makes add_request shed: a submit
            # that loses the race still finishes with a FinishReason
            # (shed) instead of queueing into a closing engine
            self.engine._draining = True
            self._cond.notify_all()
        deadline = (None if timeout_s is None
                    else self._clock() + float(timeout_s))
        try:
            with self._cond:
                while not self._stopped:
                    if not self._aborts and \
                            not self.engine.has_unfinished():
                        break
                    if deadline is not None and \
                            self._clock() >= deadline:
                        deadline = None     # abort once, then wait
                        for rid in list(getattr(self.engine,
                                                "_requests", ())):
                            self._aborts.add(rid)
                        self._cond.notify_all()
                        continue
                    interleave_wait(self._cond, 0.02)
        finally:
            with self._cond:
                self.engine._draining = False
                self._draining = False

    def close(self, join_timeout=5.0):
        """Stop the worker: pending requests are aborted (pages
        reclaimed, outputs published with FinishReason.aborted), the
        thread is joined, and a worker that outlives the join raises —
        silently leaking a live stepping thread leaves a 'stopped'
        engine still issuing device calls."""
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        self._thread.join(timeout=join_timeout)
        if self._thread.is_alive():
            warnings.warn(
                "AsyncLLMEngine worker thread survived close(); a device "
                "step is wedged", RuntimeWarning, stacklevel=2)
            raise RuntimeError(
                f"AsyncLLMEngine worker thread failed to stop within "
                f"{join_timeout}s (wedged device step?)")

    # historical name; close() is the documented surface
    stop = close
