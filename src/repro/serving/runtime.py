"""Continuous-batching runtime over the paged multi-LoRA engine.

The decode loop is ONE jitted function with fixed shapes — (num_slots,)
tokens/positions/adapters and a (num_slots, max_blocks) block table — so it
compiles exactly once; requests join and leave by mutating host-side numpy
mirrors, never the compiled program.  Decode runs in chunks of
``decode_chunk`` tokens (a ``lax.scan``) to amortize dispatch overhead;
slots join/leave at chunk boundaries, which is the standard multi-step
scheduling granularity trade-off.

Join path (chunked paged prefill): each prompt is processed in fixed
``prefill_chunk``-sized slices by ONE compiled step that writes K/V
straight into the slot's pool blocks through the block table — no
contiguous bucket cache, no second scatter pass, no padded-bucket FLOPs,
and prompt length is capped only by the block table (max_blocks_per_slot
* block_size), not by a bucket set.  The step's fixed row width
(``prefill_rows``) lets a group admission advance several prompts' chunk
loops in one dispatch (partial groups pad with garbage rows); items that
share blocks a groupmate registers in the same admit run in their own
dispatch afterwards (their reads depend on the groupmate's writes).  The
chunk loop starts at the first prefix-cache-*uncovered* token, so blocks
shared with earlier requests skip COMPUTE, not just insert.  Chunk-tail
padding junk lands either in blocks the decode loop overwrites before it
can be attended, or in the reserved garbage block.  Exactly one prefill
shape compiles — cold-start warmup no longer pays one compile per bucket.

Leave path: EOS / token budget exhausted -> block refcounts drop; the last
holder actually frees (prefix-shared blocks survive their first owner).
If the pool runs dry mid-flight a slot *stalls*: it still runs the chunk
from its current (token, pos) — writes into allocated blocks are identical
to what the eventual resume writes, overflow writes clip to the garbage
block — but its outputs are discarded and it does not advance.  If every
slot stalls the runtime force-evicts the stalled slot closest to
completion so the system always makes progress.

Cross-request prefix sharing (``ServingConfig.prefix_sharing``): admission
matches the longest chain of *full* prompt blocks already in the pool for
the same adapter (``serving.prefix.PrefixCache``) and maps those physical
blocks into the new slot's table with refcount bumps instead of allocating
them.  The chunk loop then starts past the covered tokens: shared blocks
are neither re-inserted NOR recomputed (the bucketed path could only skip
the insert).  Only full prompt blocks are ever shared, so the
partially-filled tail block — the only block decode could still write
inside the prompt range — is always a private copy (copy-on-write by
construction; decode writes land at pos >= prompt_len, past every shared
block).

Sliding-window reclamation (``ServingConfig.window_reclamation``): after
each decode chunk, blocks whose entire [j*bs, (j+1)*bs) token range slid
out of the window are released back to the pool and the slot's table entry
set to -1 — legal because every decode path already masks both -1 entries
and positions <= pos - window, so those keys can never be read again.
Per-slot *live* working set shrinks to O(window); the block table still
caps total sequence length (logical index == absolute position).

Both features are host-side block-table/lifecycle work: the compiled
decode step is untouched (block tables stay host-side arguments).

Hybrid REC/SSD slot state: attention-free and hybrid stacks (mamba2,
recurrentgemma) carry, per REC/SSD layer, dense ``(num_slots + 1, ...)``
recurrent-state rows beside the paged pools (conv tail + hidden/SSM
state; last row = garbage, the state analogue of the garbage block).
Every prefill/decode dispatch carries a ``state_rows`` vector mapping
dispatch rows to state rows: prefill initializes a recycled row in-step
(a row starting at position 0 reads zero state), chunked prefill chunks
continue the recurrent scan from the carried row, and decode updates it
in the same compiled step as the KV write.  Two invariants differ from
the ATTN paths: (1) recurrent state summarizes the WHOLE prefix, so
prefix-shared admissions skip block *writes* but never compute — the
chunk loop starts at token 0 for stacks with state (ATTN layers still
map shared blocks: memory dedup survives, compute dedup does not); and
(2) a stalled slot's state row is redirected to the garbage row for the
stalled chunk — KV writes are re-written identically by the resume, but
a recurrent row would advance twice, so the redirect is what keeps
stall-and-resume a no-op.  State rows are per-request and never shared:
a row is mutated by every decode step, and its value at position t
depends on the entire prefix, so (unlike immutable per-position KV
blocks) there is nothing safely shareable.

Observability: every runtime owns a ``serving.metrics.MetricsRegistry``
(``self.metrics``; ``self.stats`` is the legacy int-dict view over its
counters) and samples occupancy gauges at each scheduling boundary.
When a ``serving.telemetry.Telemetry`` recorder is attached
(``self.telemetry``), every step of ``try_admit`` and ``decode()`` runs
inside a named span on the recorder's own clock (``runtime.admit.plan``,
``runtime.prefill.round``, ``runtime.decode.sync``, ...), and Python
collections are recorded as ``runtime.gc`` spans.  Dispatch latencies
use an injectable ``timer``, and the runtime makes the SAME timer calls
whether or not a recorder is attached, so telemetry can never perturb
replay results (see docs/observability.md).
"""
from __future__ import annotations

import dataclasses
import math
import time
import warnings
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (make_chunked_prefill_step,
                               make_sampled_serve_step)
from repro.core.sampling import (GREEDY, MODES, SamplingParams,
                                 sample_tokens)
from repro.models.cache import (GARBAGE_BLOCK, has_slot_state,
                                init_paged_cache, paging_unsupported_reason)
from repro.models.config import ATTN, ModelConfig
from repro.serverless.batching import Request
from repro.serving.kv_pool import BlockPool, blocks_for_tokens
from repro.serving.metrics import MetricsRegistry
from repro.serving.prefix import PrefixCache
from repro.serving.slots import SlotState, SlotTable
from repro.serving import telemetry as tm
from repro.serving.telemetry import (EVT_ABORT, EVT_PREEMPT, NO_SPAN,
                                     Telemetry)


@dataclasses.dataclass(frozen=True)
class PrefillConfig:
    """Chunked paged prefill — the join path's ONE compiled shape."""
    chunk: int = 32                  # tokens per chunked-prefill dispatch
    #   (must be a multiple of block_size; ONE compiled prefill shape
    #   serves every prompt length)
    rows: int = 4                    # fixed row width of that one shape:
    #   group admissions advance their chunk loops side by side in one
    #   dispatch; partial groups pad with garbage rows (NOT a bucket — the
    #   chunk dimension never changes and still compiles exactly once)


@dataclasses.dataclass(frozen=True)
class DecodeConfig:
    """The fixed-shape jitted decode loop."""
    chunk: int = 4                   # tokens per jitted decode dispatch
    eos_id: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class RobustConfig:
    """Overload robustness: preemption, requeue, and artifact-retry policy
    (docs/robustness.md)."""
    preemption: bool = False     # pool exhaustion with EVERY slot stalled:
    #   True = preempt the lowest-priority slot to the cached-LRU tier so
    #   the caller can requeue it (cheap resume through the prefix cache);
    #   False (default) = the legacy terminal force-evict (aborted_oom).
    #   Off by default so existing replays stay bitwise-identical.
    retry_budget: int = 3        # preemptions one request may absorb before
    #   the replay declares it terminally abandoned (abandoned_retries)
    backoff_s: float = 0.05      # virtual-clock requeue delay base; doubles
    #   with every further preemption of the same request
    artifact_retries: int = 2    # AdapterRegistry.load/swap retries on
    #   transient artifact-load failures (faults.retry_with_backoff)
    artifact_backoff_s: float = 0.0  # host-clock sleep base between
    #   artifact retries (0 = immediate; tests inject fake sleeps)


@dataclasses.dataclass(frozen=True)
class AdapterConfig:
    """Multi-LoRA serving: the stacked adapter bank and its dispatch."""
    max_live: Optional[int] = None   # AdapterRegistry capacity (bank slots);
    #   None = size the registry to the bank already in ``params``
    lora_rank: Optional[int] = None  # bank rank; adapters loaded with a
    #   smaller rank are zero-padded up to it (None = whatever the bank has)
    sgmv_kernel: Optional[bool] = None  # LoRA-delta dispatch: None = auto
    #   (Pallas SGMV on TPU, gather-BMM reference elsewhere — bitwise-equal
    #   oracle), True = force the kernel (interpret off-TPU), False = ref


@dataclasses.dataclass(frozen=True, init=False)
class ServingConfig:
    """Runtime shape + policy knobs, grouped by subsystem.

    Construct either with nested groups (``ServingConfig(prefill=
    PrefillConfig(chunk=64))``) or with the legacy flat kwargs
    (``ServingConfig(prefill_chunk=64)``) — mixing a nested group object
    with a flat kwarg of the SAME group is an error, not a merge.  Flat
    reads (``scfg.prefill_chunk``) keep working as read-through
    properties, so existing call sites never see the nesting."""
    num_slots: int = 8
    block_size: int = 16
    num_blocks: int = 64             # physical blocks incl. the garbage block
    max_blocks_per_slot: int = 8
    use_kernel: bool = True          # in-kernel block-table walk for paged
    #   attention (Pallas on TPU, fused jnp block walk elsewhere); False =
    #   the gather-based reference path
    prefix_sharing: bool = True      # map full prompt blocks shared with
    #   earlier same-adapter requests into the slot table (refcounted)
    #   instead of allocating them; the chunk loop skips their compute
    window_reclamation: bool = True  # sliding-window configs: release
    #   blocks that slid fully out of the window after each decode chunk
    prefill: PrefillConfig = PrefillConfig()
    decode: DecodeConfig = DecodeConfig()
    adapters: AdapterConfig = AdapterConfig()
    robust: RobustConfig = RobustConfig()

    # legacy flat kwarg -> (group field, field inside the group)
    _FLAT = {
        "prefill_chunk": ("prefill", "chunk"),
        "prefill_rows": ("prefill", "rows"),
        "decode_chunk": ("decode", "chunk"),
        "eos_id": ("decode", "eos_id"),
        "max_live_adapters": ("adapters", "max_live"),
        "lora_rank": ("adapters", "lora_rank"),
        "sgmv_kernel": ("adapters", "sgmv_kernel"),
        "preemption": ("robust", "preemption"),
        "retry_budget": ("robust", "retry_budget"),
        "retry_backoff_s": ("robust", "backoff_s"),
        "artifact_retries": ("robust", "artifact_retries"),
        "artifact_backoff_s": ("robust", "artifact_backoff_s"),
    }
    _GROUPS = {"prefill": PrefillConfig, "decode": DecodeConfig,
               "adapters": AdapterConfig, "robust": RobustConfig}

    def __init__(self, num_slots: int = 8, block_size: int = 16,
                 num_blocks: int = 64, max_blocks_per_slot: int = 8,
                 use_kernel: bool = True, prefix_sharing: bool = True,
                 window_reclamation: bool = True,
                 prefill: Optional[PrefillConfig] = None,
                 decode: Optional[DecodeConfig] = None,
                 adapters: Optional[AdapterConfig] = None,
                 robust: Optional[RobustConfig] = None,
                 **flat: Any):
        groups: Dict[str, Any] = {"prefill": prefill, "decode": decode,
                                  "adapters": adapters, "robust": robust}
        over: Dict[str, Dict[str, Any]] = {g: {} for g in self._GROUPS}
        for k, v in flat.items():
            if k not in self._FLAT:
                raise TypeError(
                    f"ServingConfig got an unexpected keyword {k!r}")
            g, f = self._FLAT[k]
            if groups[g] is not None:
                raise ValueError(
                    f"pass {g}=... or the flat kwarg {k!r}, not both")
            over[g][f] = v
        for g, cls_ in self._GROUPS.items():
            if groups[g] is None:
                groups[g] = cls_(**over[g])
        for name, val in (("num_slots", num_slots),
                          ("block_size", block_size),
                          ("num_blocks", num_blocks),
                          ("max_blocks_per_slot", max_blocks_per_slot),
                          ("use_kernel", use_kernel),
                          ("prefix_sharing", prefix_sharing),
                          ("window_reclamation", window_reclamation),
                          ("prefill", groups["prefill"]),
                          ("decode", groups["decode"]),
                          ("adapters", groups["adapters"]),
                          ("robust", groups["robust"])):
            object.__setattr__(self, name, val)

    # flat read-through views (the pre-nesting field names)
    @property
    def prefill_chunk(self) -> int:
        return self.prefill.chunk

    @property
    def prefill_rows(self) -> int:
        return self.prefill.rows

    @property
    def decode_chunk(self) -> int:
        return self.decode.chunk

    @property
    def eos_id(self) -> Optional[int]:
        return self.decode.eos_id


@dataclasses.dataclass
class ServeRequest:
    """One admission-ready request — the typed unit ``try_admit`` takes.

    Replaces the ``(Request, prompt_tokens, adapter:int)`` tuples (still
    accepted for one release, with a DeprecationWarning).  ``adapter`` is
    a registry NAME (resolved to a bank slot at the API boundary by the
    runtime's ``AdapterRegistry``) or a raw bank slot int (validated
    against the bank); ``None`` means slot 0 — the backbone-default
    adapter every bank reserves in single-tenant runs.

    ``request`` carries the underlying trace record when the caller has
    one (``replay_trace``); otherwise a fresh ``Request`` is synthesized
    so lifecycle accounting (breakdown flags, SLO fields) keeps working.
    """
    prompt: Any                      # (L,) int token ids (np/list)
    adapter: Optional[Any] = None    # registry name (str) | bank slot (int)
    arrival: float = 0.0
    max_new_tokens: int = 1
    request: Optional[Request] = None
    slo_class: int = 0               # preemption priority: HIGHER classes
    #   may preempt lower ones when they would provably miss a deadline
    deadline_ttft: float = float("inf")  # hard first-token budget from
    #   arrival; inf (default) disables deadline shedding for this request
    deadline_e2e: float = float("inf")   # hard end-to-end budget
    sampling: Optional[SamplingParams] = None  # per-request sampling
    #   policy; None = greedy (``core.sampling.GREEDY``).  Rides the
    #   dispatch as per-row data vectors — mixing modes in one batch
    #   never recompiles.  A None seed resolves from the request id at
    #   admission, so trace replays stay deterministic.

    _auto_id = 0                     # class-level: synthesized req_id seq

    def ensure_request(self) -> Request:
        if self.request is None:
            ServeRequest._auto_id += 1
            self.request = Request(
                req_id=-ServeRequest._auto_id,  # negative: never collides
                #   with trace req_ids (traces number from 0 upward)
                fn_id=str(self.adapter), arrival=self.arrival,
                prompt_len=len(self.prompt),
                output_len=max(int(self.max_new_tokens), 1),
                slo_ttft=float("inf"), slo_class=int(self.slo_class),
                deadline_ttft=float(self.deadline_ttft),
                deadline_e2e=float(self.deadline_e2e))
        return self.request


@dataclasses.dataclass
class AdmitResult:
    slot_ids: List[int]              # bound slot per admitted item; -1 =
    #   finished at prefill (output_len == 1 / instant EOS), never bound
    first_tokens: List[int]
    finished: List[SlotState]        # output_len == 1 completes at prefill
    dt: float                        # total prefill device time this admit
    shared_blocks: List[int] = dataclasses.field(default_factory=list)
    #   per item: prompt blocks mapped from the prefix cache (not allocated)
    rejected: List[Request] = dataclasses.field(default_factory=list)
    #   items whose prompt/output exceed slot KV capacity — dropped and
    #   counted, never admitted; the per-item lists above align with the
    #   SURVIVING items (in input order)


@dataclasses.dataclass
class DecodeResult:
    emitted: Dict[int, List[int]]    # sid -> tokens accepted this chunk
    finished: List[SlotState]
    aborted: List[SlotState]         # force-evicted on pool exhaustion
    stalled: List[int]
    dt: float
    preempted: List[SlotState] = dataclasses.field(default_factory=list)
    #   released with completed KV demoted to the cached-LRU tier
    #   (``RobustConfig.preemption``); the CALLER owns requeue policy —
    #   replay_trace re-enters them with exponential backoff + retry budget


# terminal-state taxonomy: which breakdown flags put a request in which
# terminal class.  Every request ends in EXACTLY one class (or none while
# still in flight) — the conservation invariant check_invariants() audits.
_REJECT_FLAGS = ("rejected_too_long", "rejected_unknown_adapter",
                 "rejected_deadline")
_ABORT_FLAGS = ("aborted", "aborted_oom")
_ABANDON_FLAGS = ("abandoned", "abandoned_retries")


def terminal_state(req: Request) -> Optional[str]:
    """Terminal class of a request record — ``"finished"`` /
    ``"rejected"`` / ``"aborted"`` / ``"abandoned"`` — or None while
    unresolved.  ``"preempted"`` is deliberately NOT terminal: a
    preempted request is still in flight (requeued) until it finishes,
    exhausts its retry budget (``abandoned_retries``), or is aborted.
    Raises ValueError if the flags put the request in more than one
    class at once (a lifecycle accounting bug, never a workload
    property)."""
    rejected = any(f in req.breakdown for f in _REJECT_FLAGS)
    aborted = any(f in req.breakdown for f in _ABORT_FLAGS)
    abandoned = any(f in req.breakdown for f in _ABANDON_FLAGS)
    finished = (req.first_token >= 0 and req.done >= 0
                and not (aborted or abandoned))
    hit = [name for name, is_hit in (
        ("rejected", rejected), ("aborted", aborted),
        ("abandoned", abandoned), ("finished", finished)) if is_hit]
    if len(hit) > 1:
        raise ValueError(
            f"request {req.req_id} is in {len(hit)} terminal states at "
            f"once: {hit} (breakdown flags {sorted(req.breakdown)})")
    return hit[0] if hit else None


class ContinuousRuntime:
    def __init__(self, cfg: ModelConfig, params, scfg: ServingConfig, *,
                 telemetry: Optional[Telemetry] = None,
                 timer: Callable[[], float] = time.perf_counter):
        """``telemetry`` attaches an optional span recorder (the runtime
        records its steps into it; ``replay_trace`` stamps lifecycle
        spans).  ``timer`` is the clock of every dispatch latency the
        runtime acts on — injectable so tests can replay under a
        deterministic fake clock and assert bitwise-identical results
        with telemetry on vs off.  The recorder reads its own clock, so
        attaching one never changes the runtime's timer readings."""
        reason = paging_unsupported_reason(cfg)
        if reason is not None:
            raise ValueError(reason)
        if scfg.prefill_chunk < scfg.block_size \
                or scfg.prefill_chunk % scfg.block_size:
            raise ValueError(
                f"prefill_chunk {scfg.prefill_chunk} must be a positive "
                f"multiple of block_size {scfg.block_size}")
        if scfg.prefill_rows < 1:
            raise ValueError("prefill_rows must be >= 1")
        self.has_state = has_slot_state(cfg)
        # attention-free stacks (pure SSD/REC) have no K/V to page: no
        # blocks are charged or allocated, capacity is NOT bounded by the
        # block table (the families' O(1)-state selling point), prefix
        # sharing is off (there are no block contents to dedup), and
        # decode can never stall on pool exhaustion
        self.needs_kv = ATTN in (set(cfg.pattern)
                                 | set(cfg.remainder_layers))
        if self.has_state and scfg.prefill_chunk % cfg.ssm_chunk:
            raise ValueError(
                f"prefill_chunk {scfg.prefill_chunk} must be a multiple of "
                f"ssm_chunk {cfg.ssm_chunk} for REC/SSD stacks: recurrent "
                f"scans run in ssm_chunk-aligned blocks so chunk-at-a-time "
                f"prefill stays bitwise-equal to whole-prompt prefill")
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.pool = BlockPool(scfg.num_blocks, scfg.block_size)
        self.slots = SlotTable(scfg.num_slots, scfg.max_blocks_per_slot)
        # REC/SSD state rows: one per slot + the trailing garbage row
        self.garbage_state_row = scfg.num_slots
        self.cache = init_paged_cache(
            cfg, scfg.num_blocks, scfg.block_size,
            num_slots=scfg.num_slots if self.has_state else None)
        self.prefix: Optional[PrefixCache] = None
        if scfg.prefix_sharing and self.needs_kv:
            self.prefix = PrefixCache(scfg.block_size)
            # freed prompt blocks park in the pool's cached LRU while the
            # prefix index maps them; eviction drops the mapping
            self.pool.cache_hook = self.prefix.has_block
            self.pool.evict_hook = self.prefix.forget_block
        self._telemetry: Optional[Telemetry] = None
        self._detach_telemetry: Optional[weakref.finalize] = None
        self.telemetry = telemetry
        self._timer = timer
        # typed metrics registry; ``stats`` is the legacy int-dict
        # interface over the SAME counter objects (serving.metrics) — old
        # ``rt.stats["x"]`` callers and new snapshot consumers see one
        # state.  Units: _tokens/_blocks are counts, _s is seconds.
        self.metrics = MetricsRegistry()
        for name, help_ in (
            ("prompt_tokens", "tokens in admitted prompts"),
            ("prefill_tokens", "prompt tokens newly written into the pool "
             "(prompt_tokens minus prefix-shared coverage)"),
            ("recomputed_tokens", "prompt tokens actually run through "
             "prefill compute (the bucketed path recomputed ALL of "
             "prompt_tokens; chunked prefill skips covered tokens)"),
            ("shared_tokens", "prompt tokens covered by shared blocks"),
            ("shared_block_maps", "table entries mapped via sharing"),
            ("prefill_chunks", "chunked-prefill dispatches"),
            ("prefill_live_rows", "real (non-garbage) rows of every "
             "chunked-prefill dispatch"),
            ("decode_chunks", "jitted decode-chunk dispatches"),
            ("decode_kv_blocks", "K/V blocks the paged-decode kernel "
             "fetches per layer and kv head, summed over the rows and scan "
             "steps of every decode dispatch (its share of num_slots x "
             "max_blocks_per_slot x decode_chunk x decode_chunks is the "
             "share of the table the bounded walk covers)"),
            ("stall_steps", "slot-chunks discarded on pool exhaustion "
             "(one per stalled slot per decode dispatch)"),
            ("rejected_too_long", "requests dropped: prompt + output "
             "exceed slot KV capacity (graceful, never a raise mid-trace)"),
            ("rejected_unknown_adapter", "requests dropped at admission: "
             "adapter name not in the registry / bank slot out of range "
             "(the decode path would compute a zero delta, but serving an "
             "unloaded adapter silently is a correctness bug)"),
            ("reclaimed_blocks", "blocks returned mid-flight (window)"),
            ("admit_syncs", "deliberate device syncs during admission "
             "(one whole-batch logit transfer per final prefill "
             "round; the retired per-item loop paid one per prompt)"),
            # terminal-state + preemption counters (docs/robustness.md):
            # every request ends in exactly ONE of finished / rejected_* /
            # aborted / abandoned — check_invariants() audits the books
            ("rejected_deadline", "requests shed at admission: even the "
             "optimistic lower bound on remaining work misses their "
             "TTFT/e2e deadline"),
            ("aborted", "in-flight requests cancelled (runtime.abort, "
             "force-evict on pool exhaustion)"),
            ("abandoned", "requests terminally dropped after admission "
             "was attempted (SLO lapse in queue, retry budget exhausted)"),
            ("preemptions", "slots released mid-flight with completed KV "
             "demoted to the cached-LRU tier for cheap resume"),
            ("retries", "preempted requests re-entered into the admission "
             "queue (backoff requeues, not artifact retries)"),
            ("resume_prefix_hits", "re-admissions of preempted requests "
             "that recovered demoted blocks through the prefix cache"),
            ("demoted_blocks", "completed blocks re-indexed into the "
             "prefix trie at preempt/abort so they park cached, not free"),
            ("artifact_retries", "adapter/checkpoint load attempts "
             "retried after a transient artifact failure"),
            ("injected_pool_squeezes", "FaultPlan pool-squeeze windows "
             "that actually captured blocks"),
            # fused-sampling counters (docs/serving.md "Sampling"):
            # every accepted token lands in exactly one tokens_mode_*
            # bucket; sampled_tokens is the non-greedy total
            ("sampled_tokens", "accepted tokens drawn through the "
             "sampling epilogue (temperature > 0; greedy rows never "
             "consult the RNG)"),
        ):
            self.metrics.counter(name, help_)
        for m in MODES:
            self.metrics.counter(
                f"tokens_mode_{m}", f"accepted tokens emitted by rows in "
                f"sampling mode {m!r} (core.sampling.SamplingParams.mode)")
        self.stats = self.metrics.counter_view()
        # multi-LoRA: bank capacity N read off the params' stacked lora
        # leaves (adapter axis -3); None = no bank in the tree (backbone
        # only — every adapter id but 0/None is rejected at admission).
        # ``serving.adapters.AdapterRegistry`` attaches itself here and
        # takes over name resolution + slot lifecycle.
        from repro.core.lora import partition_lora
        _, bank = partition_lora(params)
        leaves = jax.tree_util.tree_leaves(bank)
        self.bank_slots: Optional[int] = (
            int(leaves[0].shape[-3]) if leaves else None)
        self.adapters = None         # Optional[AdapterRegistry]
        # deterministic fault injection (serving.faults.FaultPlan):
        # replay_trace attaches the active plan here so artifact loaders
        # (AdapterRegistry, checkpoint.store callers) can consult it; None
        # (the default) costs one attribute test per load
        self.faults = None           # Optional[faults.FaultPlan]

        sampled_serve = make_sampled_serve_step(cfg)
        chunk_step = make_chunked_prefill_step(cfg)

        def decode_chunk(params, tok, cache, pos, tbl, ai, srows,
                         temp, top_k, top_p, seed, counter):
            """The fixed-shape decode loop with the fused sampling
            epilogue: per-row temperature/top_k/top_p/seed vectors ride
            as DATA (same contract as ``ai``/``srows`` — mixed modes
            never re-jit), the scan carries the per-row RNG counter and
            advances it by one per emitted token, so the token at output
            position i is a pure function of (seed, i, logits) and a
            resumed request replays the identical key sequence."""
            def body(carry, _):
                tok, cache, pos, cnt = carry
                nxt, cache = sampled_serve(
                    params, tok, cache, pos, adapter_idx=ai, block_tbl=tbl,
                    use_paged_kernel=scfg.use_kernel,
                    lora_kernel=scfg.adapters.sgmv_kernel,
                    state_rows=srows, temperature=temp, top_k=top_k,
                    top_p=top_p, seed=seed, counter=cnt)
                return (nxt, cache, pos + 1, cnt + 1), nxt

            (_, cache, _, _), toks = jax.lax.scan(
                body, (tok, cache, pos, counter), None,
                length=scfg.decode_chunk)
            return toks.T, cache                       # (B, K)

        def prefill_chunk(params, tokens, start, last_idx, ai, pool_cache,
                          chunk_ids, tbl, srows, temp, top_k, top_p, seed):
            """ONE slice of the join path: write this chunk's K/V straight
            into pool blocks (REC/SSD layers: advance the slot-state rows
            named by ``srows``) and sample the first output token from the
            logit at ``last_idx`` with RNG counter 0 (only the final
            chunk's draw is kept — the first token is output position 0).
            Returning the sampled (G,) tokens instead of (G, V) logits
            also shrinks the admission D2H transfer to one int per row.
            Admission happens between decode chunks, so its dispatch
            overhead is pure decode stall — and there is exactly one such
            compiled shape for every prompt length."""
            logits, pool_cache = chunk_step(
                params, tokens, start, last_idx, pool_cache,
                chunk_ids, tbl, adapter_idx=ai,
                use_paged_kernel=scfg.use_kernel,
                lora_kernel=scfg.adapters.sgmv_kernel,
                state_rows=srows)
            first = sample_tokens(logits, temp, top_k, top_p, seed,
                                  jnp.zeros_like(seed))
            return first, pool_cache

        self._decode = jax.jit(decode_chunk, donate_argnums=(2,))
        self._prefill = jax.jit(prefill_chunk, donate_argnums=(5,))

    @property
    def telemetry(self) -> Optional[Telemetry]:
        """The attached span recorder, or None."""
        return self._telemetry

    @telemetry.setter
    def telemetry(self, tel: Optional[Telemetry]) -> None:
        """Attach (or with None, detach) a recorder; its ``runtime.gc``
        hook is installed while it is attached, and removed as well when
        the runtime is collected with the recorder still attached."""
        if tel is self._telemetry:
            return
        if self._detach_telemetry is not None:
            self._detach_telemetry()
            self._detach_telemetry = None
        self._telemetry = tel
        if tel is not None:
            tel.attach()
            self._detach_telemetry = weakref.finalize(self, tel.detach)

    # ------------------------------------------------------------ capacity
    def max_output_for(self, prompt_len: int) -> int:
        """Largest output_len a request with this prompt can be granted.
        Attention-free stacks are not KV-bounded: their whole decode state
        is a fixed-size slot row, so any int32-positionable length fits."""
        if not self.needs_kv:
            return 2 ** 31 - 1 - prompt_len
        cap = self.scfg.max_blocks_per_slot * self.scfg.block_size
        return cap - prompt_len + 1        # last KV write is L + out - 2

    def fits(self, prompt_len: int, output_len: int) -> bool:
        """Capacity is the block table, not a bucket set: the last KV
        write (position prompt_len + output_len - 2, or prompt_len - 1
        for single-token requests) must land inside max_blocks_per_slot.
        Attention-free stacks always fit (no KV to place)."""
        if prompt_len < 1 or output_len < 1:
            return False
        return output_len <= self.max_output_for(prompt_len)

    def admit_cost_blocks(self, prompt_len: int, output_len: int = 2) -> int:
        if not self.needs_kv:
            return 0                       # nothing to page for REC/SSD-only
        # blocks covering positions 0..prompt_len: the prompt plus the first
        # decode write at position L — which never happens for single-token
        # requests (they finish at prefill)
        extra = 1 if output_len > 1 else 0
        return blocks_for_tokens(prompt_len + extra, self.scfg.block_size)

    def reject_too_long(self, req: Request) -> None:
        """Count a capacity rejection exactly once per request (idempotent:
        retried batches must not inflate the counter) and flag the request
        so the replay reports it failed instead of crashing the trace."""
        if "rejected_too_long" not in req.breakdown:
            self.stats["rejected_too_long"] += 1
        req.breakdown["rejected_too_long"] = 1.0

    def reject_unknown_adapter(self, req: Request) -> None:
        """Count an unknown-adapter rejection once per request (same
        idempotency contract as ``reject_too_long``)."""
        if "rejected_unknown_adapter" not in req.breakdown:
            self.stats["rejected_unknown_adapter"] += 1
        req.breakdown["rejected_unknown_adapter"] = 1.0

    def reject_deadline(self, req: Request) -> None:
        """Count a deadline shed once per request (same idempotency
        contract as the other reject paths).  Shed requests were PROVABLY
        going to miss: even the optimistic lower bound on their remaining
        work exceeds the deadline, so admitting them would burn slot time
        on a guaranteed violation (docs/robustness.md)."""
        if "rejected_deadline" not in req.breakdown:
            self.stats["rejected_deadline"] += 1
        req.breakdown["rejected_deadline"] = 1.0

    # ------------------------------------------------- deadline estimation
    def _dispatch_floor(self, kind: str) -> Optional[float]:
        """Optimistic seconds per ``kind`` dispatch: the MINIMUM observed
        dispatch time (falling back to the warmup gauge before traffic
        exists), so every deadline bound built on it is a true lower
        bound — noise can delay real dispatches, never speed them up.
        None when no timing data exists yet (shedding then stands down:
        nothing is provable)."""
        h = self.metrics.histograms.get(f"{kind}_dispatch_s")
        if h is not None:
            v = h.min_observed()
            if v is not None:
                return v
        g = self.metrics.gauges.get(f"warmup_{kind}_chunk_s")
        if g is not None and g.count:
            return g.last
        return None

    def _prefill_rounds(self, prompt_len: int, covered_tokens: int) -> int:
        """Chunk-loop dispatch rounds a prompt needs — the exact loop
        bound ``_chunk_prefill`` runs (prefix-covered tokens skip rounds;
        stacks with recurrent state always start at token 0)."""
        bs, C = self.scfg.block_size, self.scfg.prefill_chunk
        if self.has_state:
            start = 0
        else:
            start = min((covered_tokens // bs) * bs,
                        ((prompt_len - 1) // bs) * bs)
        return max(-(-(prompt_len - start) // C), 1)

    def deadline_floors(self, prompt_len: int, output_len: int,
                        covered_tokens: int = 0
                        ) -> Optional[Tuple[float, float]]:
        """(TTFT floor, e2e floor): optimistic additional seconds to first
        token / last token if the request dispatched right now.  None when
        no prefill timing data exists (nothing provable, nothing shed).
        The decode term is omitted when decode has no floor yet — the
        bound just gets weaker, never wrong."""
        tp = self._dispatch_floor("prefill")
        if tp is None:
            return None
        ttft = self._prefill_rounds(prompt_len, covered_tokens) * tp
        e2e = ttft
        if output_len > 1:
            td = self._dispatch_floor("decode")
            if td is not None:
                k = max(self.scfg.decode_chunk, 1)
                e2e += -(-(output_len - 1) // k) * td
        return ttft, e2e

    def _resolve_adapter(self, adapter) -> Optional[int]:
        """Registry name / bank slot -> validated bank slot, or None if the
        id cannot be served.  Pure host dict/int work — admission planning
        calls this per item with no device interaction."""
        if adapter is None:
            adapter = 0
        if isinstance(adapter, str):
            if self.adapters is None:
                raise ValueError(
                    f"adapter name {adapter!r} needs an AdapterRegistry "
                    f"attached to the runtime (serving.adapters)")
            return self.adapters.resolve(adapter)   # None when unknown
        slot = int(adapter)
        if self.bank_slots is None:
            return slot if slot == 0 else None      # backbone-only params
        if not 0 <= slot < self.bank_slots:
            return None
        if self.adapters is not None and not self.adapters.slot_loaded(slot):
            return None                             # unloaded/free slot
        return slot

    def _coerce_admit_items(self, items) -> Tuple[
            List[Tuple[Request, np.ndarray, int, SamplingParams, int]],
            List[Request]]:
        """Normalize ``try_admit`` input — ``ServeRequest`` objects or the
        deprecated ``(Request, prompt, adapter:int)`` tuples — into
        resolved ``(Request, prompt, bank_slot, sampling, seed)`` tuples,
        rejecting items whose adapter cannot be resolved.  The PRNG seed
        is resolved HERE, at the API boundary (explicit seed, else the
        request id) — the hot path only ever sees int32 seeds."""
        out: List[Tuple[Request, np.ndarray, int, SamplingParams, int]] = []
        rejected: List[Request] = []
        warned = False
        for it in items:
            if isinstance(it, ServeRequest):
                req, prompt, adapter = it.ensure_request(), it.prompt, \
                    it.adapter
                sp = it.sampling if it.sampling is not None else GREEDY
            else:
                if not warned:
                    warnings.warn(
                        "(Request, prompt_tokens, adapter) tuples to "
                        "try_admit are deprecated; pass ServeRequest "
                        "objects (adapter by registry name)",
                        DeprecationWarning, stacklevel=4)
                    warned = True
                req, prompt, adapter = it
                sp = GREEDY
            slot = self._resolve_adapter(adapter)
            if slot is None:
                self.reject_unknown_adapter(req)
                rejected.append(req)
                continue
            out.append((req, np.asarray(prompt), slot, sp,
                        sp.resolve_seed(req.req_id)))
        return out, rejected

    # ----------------------------------------------------------- admission
    def _plan_blocks(self, items: Sequence[Tuple]
                     ) -> Optional[Tuple[List[Tuple[List[int], List[int]]],
                                         List[List[int]]]]:
        """Per item, (shared prefix blocks, freshly allocated blocks) —
        logical order is shared + fresh — plus the per-item list of blocks
        newly registered in the prefix index (an item whose *shared* list
        intersects an earlier item's *registered* list depends on that
        item's prefill writes).  Sequential with rollback so items inside
        one group can share each other's just-registered blocks; returns
        None (pool state restored, bar evicted cached entries) if any
        item's fresh allocation cannot be covered."""
        plans: List[Tuple[List[int], List[int]]] = []
        registered: List[List[int]] = []
        for req, prompt, adapter, *_ in items:
            need = self.admit_cost_blocks(len(prompt), req.output_len)
            shared: List[int] = []
            node = None
            if self.prefix is not None:
                shared, node = self.prefix.match(adapter, prompt)
                # shared chains cover only full prompt blocks, so they can
                # never reach the block the first decode write lands in
                self.pool.share(shared)
            fresh = self.pool.alloc(need - len(shared))
            if fresh is None:
                if shared:
                    self.pool.free(shared)
                for plan, reg in zip(reversed(plans), reversed(registered)):
                    for b in reg:            # un-index BEFORE freeing: the
                        #   blocks were never written (no prefill ran)
                        self.prefix.forget_block(b)
                    if plan[1]:
                        self.pool.free(plan[1])
                    if plan[0]:
                        self.pool.free(plan[0])
                return None
            reg: List[int] = []
            if self.prefix is not None:
                reg = self.prefix.register(adapter, prompt, shared + fresh,
                                           len(shared), node)
            plans.append((shared, fresh))
            registered.append(reg)
        return plans, registered

    def _chunk_prefill(self, items: Sequence[Tuple[np.ndarray, int,
                                                   List[int], int, int,
                                                   SamplingParams, int]]
                       ) -> List[int]:
        """Advance up to ``prefill_rows`` prompts' chunk loops side by side
        against the pool cache, one fixed (prefill_rows, prefill_chunk)
        dispatch per round; rows whose loop finished early (and unused rows
        of a partial group) ride along as garbage rows.  Items must not
        read blocks their groupmates write (``try_admit`` partitions those
        out) — each row only reads its own earlier rounds, prior requests'
        blocks, or same-round writes of its own row.

        Each item is (prompt, adapter, blocks, covered_blk, sid, sampling,
        seed); the loop starts at the first prefix-uncovered token (a
        fully covered prompt still recomputes its last block: the
        first-token logit needs position L-1's hidden state, which only
        compute yields).  Stacks with REC/SSD layers always start at
        token 0 — the recurrent state must integrate every prefix token,
        so shared blocks skip the WRITE but never the compute — and each
        round maps dispatch row i to the item's slot-state row ``sid``
        (finished/padding rows map to the garbage row; the first chunk
        reads zero state because it starts at position 0).  Returns the
        per-item first output tokens, sampled in-step (RNG counter 0)
        from each item's final chunk logit."""
        scfg, tel = self.scfg, self._telemetry
        bs, C = scfg.block_size, scfg.prefill_chunk
        G, MB = scfg.prefill_rows, scfg.max_blocks_per_slot
        assert 0 < len(items) <= G
        starts: List[List[int]] = []
        # per-row sampling vectors: constant across rounds (non-final
        # rounds draw and discard; only the final round's draw is kept).
        # Padding rows keep the greedy defaults — no RNG, no NaN hazard.
        temp = np.zeros((G,), np.float32)
        top_k = np.zeros((G,), np.int32)
        top_p = np.ones((G,), np.float32)
        seed = np.zeros((G,), np.int32)
        for i, (prompt, _, _, cov, _, sp, sd) in enumerate(items):
            L = len(prompt)
            if self.has_state:
                start_tok = 0
            else:
                start_tok = min(cov * bs, ((L - 1) // bs) * bs)
            starts.append(list(range(start_tok, L, C)))
            self.stats["recomputed_tokens"] += L - start_tok
            temp[i] = sp.temperature
            top_k[i] = sp.top_k
            top_p[i] = sp.top_p
            seed[i] = sd
        nb_c = C // bs
        firsts = [0] * len(items)
        final_rounds = {len(s) - 1 for s in starts}
        toks_by_round: Dict[int, Any] = {}   # final rounds only: the (G,)
        #   sampled tokens (the retired path held (G, V) logits here)
        for r in range(max(len(s) for s in starts)):
            live = sum(1 for s in starts if r < len(s))
            with NO_SPAN if tel is None else tel.span(
                    tm.SPAN_RT_PREFILL_ROUND, round=r, live_rows=live):
                tok = np.zeros((G, C), np.int32)
                start = np.zeros((G,), np.int32)
                last_idx = np.zeros((G,), np.int32)
                ai = np.zeros((G,), np.int32)
                ids = np.full((G, nb_c), GARBAGE_BLOCK, np.int32)
                tbl = np.full((G, MB), -1, np.int32)
                srows = np.full((G,), self.garbage_state_row, np.int32)
                for i, (prompt, adapter, blocks, cov, sid, _, _) \
                        in enumerate(items):
                    if r >= len(starts[i]):
                        continue             # finished: garbage row
                    c0 = starts[i][r]
                    L = len(prompt)
                    n_real = min(C, L - c0)
                    tok[i, :n_real] = prompt[c0:c0 + n_real]
                    start[i] = c0
                    last_idx[i] = min(max(L - 1 - c0, 0), C - 1)
                    ai[i] = adapter
                    tbl[i, : len(blocks)] = blocks
                    srows[i] = sid
                    for jj in range(nb_c):
                        j = c0 // bs + jj
                        # skip shared blocks (they already hold exactly
                        # these K/V and may be mapped by other slots) and
                        # out-of-range blocks (chunk-tail junk past the
                        # last allocated position)
                        if cov <= j < len(blocks):
                            ids[i, jj] = blocks[j]
                first, self.cache = self._prefill(
                    self.params, jnp.asarray(tok), jnp.asarray(start),
                    jnp.asarray(last_idx), jnp.asarray(ai), self.cache,
                    jnp.asarray(ids), jnp.asarray(tbl), jnp.asarray(srows),
                    jnp.asarray(temp), jnp.asarray(top_k),
                    jnp.asarray(top_p), jnp.asarray(seed))
                if r in final_rounds:
                    # start the D2H transfer now so it overlaps the
                    # remaining prefill rounds instead of stalling at the
                    # sync below
                    first.copy_to_host_async()
                    toks_by_round[r] = first
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_live_rows"] += live
        # One whole-batch transfer per final round, then index on host.
        # The per-item ``np.asarray(logits[r])`` loop this replaces was
        # reprolint's first real RL002 hit: a device sync inside a
        # Python loop, serializing admission against the device.
        self.stats["admit_syncs"] += len(toks_by_round)
        with NO_SPAN if tel is None else tel.span(tm.SPAN_RT_ADMIT_SYNC):
            synced: Dict[int, np.ndarray] = {
                r: np.asarray(t)  # reprolint: sync-point (token emission)
                for r, t in toks_by_round.items()}
        for i in range(len(items)):
            firsts[i] = int(synced[len(starts[i]) - 1][i])
        return firsts

    def try_admit(self, items: Sequence[Any], *,
                  now: Optional[float] = None) -> Optional[AdmitResult]:
        """Join ``ServeRequest`` items into free slots.

        ``now`` (virtual-clock seconds) arms deadline shedding: items
        whose finite ``deadline_ttft``/``deadline_e2e`` provably cannot be
        met — queue wait so far plus the OPTIMISTIC lower bound on their
        remaining work (``deadline_floors``) already exceeds the budget —
        are dropped (``stats["rejected_deadline"]``, breakdown flag,
        reported via ``AdmitResult.rejected``).  Without ``now``, or for
        requests with the default infinite deadlines, behaviour is
        unchanged bit for bit.

        Each item names its adapter by registry name (or raw bank slot);
        resolution happens HERE, at the API boundary — the hot path below
        only ever sees validated bank slots.  Legacy ``(Request,
        prompt_tokens, adapter:int)`` tuples are still accepted for one
        release (DeprecationWarning).

        Unserveable items are never fatal: oversized prompts (``fits``
        fails -> ``stats["rejected_too_long"]``) and unknown/unloaded
        adapters (``stats["rejected_unknown_adapter"]``) are dropped from
        the group, flagged in ``request.breakdown``, and reported via
        ``AdmitResult.rejected`` — so one bad request cannot kill a whole
        trace replay.  The per-item result lists align with the surviving
        items.  Admitted items pin their adapter's registry slot until
        they finish (``AdapterRegistry.unload`` refuses pinned slots).

        All-or-nothing for the surviving items: returns None (no state
        change beyond the rejection count and prefix-cache eviction) if
        slots or blocks are short — callers retrying after None should
        pre-filter with ``fits`` (the replay does) so rejected items are
        not popped again.

        Prefix sharing: each item's longest chain of full prompt blocks
        already indexed for its adapter is mapped into the slot table with
        refcount bumps, and the chunk loop starts past the covered tokens —
        a shared block is written exactly once in its lifetime (by the
        request that first registered it) and its positions are never
        recomputed.  The partially-filled tail block is never shared: the
        new request gets a private copy filled by its own chunk loop."""
        assert len(items) > 0
        tel = self._telemetry
        if tel is None:
            return self._admit(items, now, None)
        with tel.span(tm.SPAN_RT_ADMIT) as sp:
            res = self._admit(items, now, tel)
            sp.args["req_ids"] = [
                (it.request if isinstance(it, ServeRequest) else it[0]).req_id
                for it in items]
            sp.args["admitted"] = 0 if res is None else len(res.first_tokens)
        return res

    def _admit(self, items: Sequence[Any], now: Optional[float],
               tel: Optional[Telemetry]) -> Optional[AdmitResult]:
        """``try_admit``'s body; ``tel`` is the attached recorder or None."""
        with NO_SPAN if tel is None else tel.span(tm.SPAN_RT_ADMIT_PLAN):
            resolved, rejected = self._coerce_admit_items(items)
            kept: List[Tuple[Request, np.ndarray, int, SamplingParams, int]] \
                = []
            for it in resolved:
                req, prompt = it[0], it[1]
                if self.fits(len(prompt), max(req.output_len, 1)):
                    kept.append(it)
                else:
                    self.reject_too_long(req)
                    rejected.append(req)
            if now is not None and kept:
                # deadline shedding — only requests that OPTED IN by setting a
                # finite deadline are ever considered, and only a provable
                # miss sheds (lower-bound estimates; no data -> no shedding)
                shed_checked: List[Tuple[Request, np.ndarray, int,
                                         SamplingParams, int]] = []
                for it in kept:
                    req, prompt, adapter = it[0], it[1], it[2]
                    d_ttft, d_e2e = req.deadline_ttft, req.deadline_e2e
                    if not (math.isfinite(d_ttft) or math.isfinite(d_e2e)):
                        shed_checked.append(it)
                        continue
                    cov = (self.prefix.covered_tokens(adapter, prompt)
                           if self.prefix is not None else 0)
                    floors = self.deadline_floors(
                        len(prompt), max(req.output_len, 1), cov)
                    if floors is None:
                        shed_checked.append(it)
                        continue
                    waited = now - req.arrival
                    if waited + floors[0] > d_ttft \
                            or waited + floors[1] > d_e2e:
                        self.reject_deadline(req)
                        rejected.append(req)
                    else:
                        shed_checked.append(it)
                kept = shed_checked
            if not kept:
                return AdmitResult([], [], [], 0.0, rejected=rejected)
            scfg = self.scfg
            free = self.slots.free_slots()
            if len(kept) > len(free):
                return None
            planned = self._plan_blocks(kept)
            if planned is None:
                return None
        plans, registered = planned

        # Grouped rows of one dispatch read the pool the SAME round they
        # write it, so an item that shares a block a groupmate registered
        # in this very call (its prefill must write it first) cannot ride
        # in the same rounds — it runs in its own dispatch afterwards.
        # Blocks registered by *earlier* requests are already written.
        group_reg: set = set()
        independent: List[int] = []
        dependent: List[int] = []
        for i in range(len(kept)):
            if group_reg & set(plans[i][0]):
                dependent.append(i)
            else:
                independent.append(i)
            group_reg.update(registered[i])

        # slots are bound AFTER prefill, but state rows must be known
        # DURING it (chunk r+1 continues from the state chunk r left in the
        # slot's row), so each surviving item pre-claims free[i] — the same
        # sid the binding loop below uses
        sids = [free[i] for i in range(len(kept))]

        bs = scfg.block_size
        firsts: Dict[int, int] = {}
        total_dt = 0.0
        for batch_idx in ([independent[j:j + scfg.prefill_rows]
                           for j in range(0, len(independent),
                                          scfg.prefill_rows)]
                          + [[i] for i in dependent]):
            if not batch_idx:
                continue
            # one dispatch time per prefill group: [w0, w1] brackets the
            # group's whole chunk loop incl. the final logit sync (the
            # per-round host array prep rides inside)
            w0 = self._timer()
            got = self._chunk_prefill(
                [(kept[i][1], kept[i][2], plans[i][0] + plans[i][1],
                  len(plans[i][0]), sids[i], kept[i][3], kept[i][4])
                 for i in batch_idx])
            w1 = self._timer()
            total_dt += w1 - w0
            self.metrics.histogram(
                "prefill_dispatch_s",
                "wall seconds per prefill group dispatch").observe(w1 - w0)
            firsts.update(zip(batch_idx, got))

        with NO_SPAN if tel is None else tel.span(tm.SPAN_RT_ADMIT_BIND):
            slot_ids, first_tokens, finished = [], [], []
            for i, (req, prompt, adapter, sp, sd) in enumerate(kept):
                shared, fresh = plans[i]
                L = len(prompt)
                first = firsts[i]
                self.stats["prompt_tokens"] += L
                # the prefill token is output position 0 — bucket it by mode
                self.stats[f"tokens_mode_{sp.mode()}"] += 1
                if not sp.greedy:
                    self.stats["sampled_tokens"] += 1
                cov = len(shared) * bs
                self.stats["shared_tokens"] += cov
                self.stats["prefill_tokens"] += L - cov
                self.stats["shared_block_maps"] += len(shared)
                if req.breakdown.get("preempted"):
                    # resume accounting: a preempted request re-admitting —
                    # shared coverage here IS the cheap-resume payoff (its
                    # demoted blocks survived in the cached-LRU tier)
                    if shared:
                        self.stats["resume_prefix_hits"] += 1
                    req.breakdown["resumed_covered_tokens"] = float(cov)
                    start_tok = 0 if self.has_state \
                        else min(cov, ((L - 1) // bs) * bs)
                    req.breakdown["resume_recomputed_tokens"] = \
                        float(L - start_tok)

                sid = sids[i]
                st = SlotState(sid=sid, req=req, adapter=adapter, prompt_len=L,
                               budget=max(req.output_len, 1), pos=L,
                               blocks=shared + fresh, last_token=first,
                               shared=len(shared), prompt_tokens=prompt,
                               history=[first], sampling=sp, seed=sd)
                first_tokens.append(first)
                done = st.budget == 1 or (scfg.eos_id is not None
                                          and first == scfg.eos_id)
                if done:
                    # finished at prefill: never bound, so free[i] would be
                    # a lie — report -1 (the slot stays free for other
                    # requests).  The free is a refcount drop: registered
                    # prompt blocks park in the pool's cached LRU for
                    # future admits to share.
                    st.sid = -1
                    slot_ids.append(-1)
                    self.pool.free(st.blocks)
                    finished.append(st)
                else:
                    slot_ids.append(sid)
                    self.slots.bind(st, first)
                    if self.adapters is not None:
                        # in-flight requests pin their adapter: unload/swap of
                        # a bank slot some live decode row still reads would
                        # change that request's results mid-stream
                        self.adapters.pin(adapter)
            self._sample_gauges()
        return AdmitResult(slot_ids, first_tokens, finished, total_dt,
                           shared_blocks=[len(p[0]) for p in plans],
                           rejected=rejected)

    # -------------------------------------------------------------- decode
    def _unpin(self, st: SlotState) -> None:
        """Release a finished/aborted slot's adapter pin (no-op without a
        registry — legacy int-adapter runtimes have nothing to pin)."""
        if self.adapters is not None:
            self.adapters.unpin(st.adapter)

    def _demote_blocks(self, st: SlotState) -> int:
        """Re-index a dying slot's COMPLETED full blocks in the prefix
        trie, so the ``pool.free`` that follows parks them in the
        cached-LRU tier instead of the free list: a re-admission of the
        same request (preempt-resume, resubmitted force-evict victim)
        recovers the computed prefix — prompt AND decoded tokens —
        through the normal ``prefix.match`` and pays only the tail.

        A block is completed when every position in its [j*bs, (j+1)*bs)
        range was written (j < pos // bs); the chain truncates at the
        first window-reclaimed entry (-1) because trie chains must be
        contiguous from block 0.  Token content comes from the slot's own
        record: prompt_tokens for positions [0, L), history for [L, pos).
        Returns the number of newly indexed blocks."""
        if self.prefix is None or st.prompt_tokens is None:
            return 0
        bs = self.scfg.block_size
        n_full = min(st.pos // bs, len(st.blocks))
        for j in range(n_full):
            if st.blocks[j] < 0:
                n_full = j
                break
        if n_full <= 0:
            return 0
        stream = [int(t) for t in st.prompt_tokens]
        stream += st.history[: max(st.pos - st.prompt_len, 0)]
        tokens = stream[: n_full * bs]
        covered, node = self.prefix.match(st.adapter, tokens)
        new = self.prefix.register(st.adapter, tokens, st.blocks,
                                   len(covered), node)
        self.stats["demoted_blocks"] += len(new)
        return len(new)

    def _release_slot(self, st: SlotState, *, demote: bool = False) -> None:
        """THE exit path for every way a bound slot dies — finish, abort,
        preempt, force-evict: optionally demote completed blocks to the
        cached-LRU tier, release the held blocks, release the adapter
        pin.  Pin/block symmetry is audited here once, not per call site
        (the force-evict path used to unpin a dispatch later than the
        finish path did)."""
        if demote:
            self._demote_blocks(st)
        self.pool.free(self.slots.release(st.sid))
        self._unpin(st)

    def _preempt_slot(self, st: SlotState) -> None:
        """Release a slot preserving its computed prefix (demote-to-
        cached) and count the preemption.  The request record stays
        re-admittable: the caller requeues (or abandons) it."""
        st.req.breakdown["preempted"] = \
            st.req.breakdown.get("preempted", 0.0) + 1.0
        self._release_slot(st, demote=True)
        self.stats["preemptions"] += 1

    def preempt(self, sid: int, *, now: Optional[float] = None
                ) -> SlotState:
        """Public preemption of bound slot ``sid`` (deadline-driven
        scheduling): completed KV demotes to the cached-LRU tier, blocks
        and adapter pin release, telemetry gets the preempt instant.
        Requeue policy belongs to the caller — ``replay_trace`` re-enters
        the request with exponential backoff and a bounded retry budget.
        Returns the released ``SlotState``."""
        st = self.slots.states[sid]
        if st is None:
            raise KeyError(f"slot {sid} is not bound")
        self._preempt_slot(st)
        t = now if now is not None else self._timer()
        if self.telemetry is not None:
            self.telemetry.instant(EVT_PREEMPT, f"slot{sid}", t,
                                   req_id=st.req.req_id,
                                   produced=st.produced)
        self._sample_gauges()
        return st

    def abort(self, request_id: int, *, now: Optional[float] = None
              ) -> bool:
        """Cancel an in-flight request by id with full accounting:
        completed blocks demote to the cached-LRU tier, remaining blocks
        and the adapter pin release, the abort lands in the ``aborted``
        counter, the breakdown flag, and a telemetry abort instant.
        Returns False when no bound slot serves ``request_id`` (a queued
        request belongs to the scheduler, not the runtime)."""
        for st in self.slots.active():
            if st.req.req_id == request_id:
                self._release_slot(st, demote=True)
                st.req.breakdown["aborted"] = 1.0
                self.stats["aborted"] += 1
                t = now if now is not None else self._timer()
                if st.req.done < 0:
                    st.req.done = t
                if self.telemetry is not None:
                    self.telemetry.instant(EVT_ABORT, f"slot{st.sid}", t,
                                           req_id=request_id,
                                           produced=st.produced)
                self._sample_gauges()
                return True
        return False

    def deadline_preemption_victim(self, req: Request,
                                   now: float) -> Optional[int]:
        """Slot id worth preempting so the queued ``req`` can still meet
        its TTFT deadline, or None.  Conservative on both sides: fires
        only when even the OPTIMISTIC bound on waiting for a natural slot
        release (fastest-finishing slot's remaining decode rounds at the
        decode floor) plus req's own prefill floor already misses the
        deadline — and only a victim of STRICTLY lower SLO class (equal
        classes never thrash each other)."""
        if not self.scfg.robust.preemption:
            return None
        if not math.isfinite(req.deadline_ttft):
            return None
        if self.slots.free_slots():
            return None              # a slot is free; plain admission wins
        tp = self._dispatch_floor("prefill")
        td = self._dispatch_floor("decode")
        if tp is None or td is None:
            return None              # nothing provable without timing data
        k = max(self.scfg.decode_chunk, 1)
        waits = [(-(-(s.budget - s.produced) // k)) * td
                 for s in self.slots.active()]
        wait_floor = min(waits) if waits else 0.0
        ttft_floor = self._prefill_rounds(req.prompt_len, 0) * tp
        if (now - req.arrival) + wait_floor + ttft_floor \
                <= req.deadline_ttft:
            return None              # could still make it by waiting
        cands = [s for s in self.slots.active()
                 if s.req.slo_class < req.slo_class]
        if not cands:
            return None
        victim = min(cands, key=lambda s: (s.req.slo_class,
                                           s.budget - s.produced))
        return victim.sid

    def _ensure_blocks(self) -> Tuple[List[int], List[SlotState],
                                      List[SlotState]]:
        """On-demand allocation for this chunk's writes; stall on
        shortage.  If *everyone* stalls, one slot must die for the system
        to make progress: with ``RobustConfig.preemption`` the lowest-
        priority victim is PREEMPTED — completed blocks demote to the
        cached-LRU tier and the caller requeues the request for cheap
        resume — otherwise it is force-evicted terminally (legacy
        ``aborted_oom``; since the demote fix its completed blocks also
        park cached, so even a resubmitted force-evict victim hits the
        prefix cache).  Victim choice: lowest SLO class first, then
        closest to completion (fewest remaining tokens = least wasted
        work).  Attention-free stacks never allocate and never stall."""
        scfg, aborted, preempted = self.scfg, [], []
        if not self.needs_kv:
            for s in self.slots.active():
                s.stalled = False
            return [], aborted, preempted
        while True:
            stalled = []
            for s in self.slots.active():
                s.stalled = False
                last_pos = min(s.pos + scfg.decode_chunk - 1,
                               s.prompt_len + s.budget - 2)
                while len(s.blocks) * scfg.block_size <= last_pos:
                    ids = self.pool.alloc(1)
                    if ids is None:
                        s.stalled = True
                        break
                    self.slots.grow(s.sid, ids[0])
                if s.stalled:
                    stalled.append(s)
            if stalled and len(stalled) == self.slots.num_active:
                victim = min(stalled,
                             key=lambda s: (s.req.slo_class,
                                            s.budget - s.produced))
                if scfg.robust.preemption:
                    self._preempt_slot(victim)
                    preempted.append(victim)
                else:
                    victim.req.breakdown["aborted_oom"] = 1.0
                    self.stats["aborted"] += 1
                    self._release_slot(victim, demote=True)
                    aborted.append(victim)
                continue
            return [s.sid for s in stalled], aborted, preempted

    def decode(self) -> Optional[DecodeResult]:
        """One fixed-shape decode chunk across every slot (inactive rows
        write to the garbage block and are ignored)."""
        if self.slots.num_active == 0:
            return None
        scfg, tel = self.scfg, self._telemetry
        with NO_SPAN if tel is None else tel.span(
                tm.SPAN_RT_DECODE, rows=self.slots.num_active):
            with NO_SPAN if tel is None else tel.span(
                    tm.SPAN_RT_DECODE_PLAN):
                stalled, aborted, preempted = self._ensure_blocks()
            # a stall step = one slot riding one chunk with discarded
            # outputs
            self.stats["stall_steps"] += len(stalled)
            if self.slots.num_active == 0:  # everything aborted/preempted
                return DecodeResult({}, [], aborted, stalled, 0.0,
                                    preempted=preempted)

            # Stalled slots run the chunk unmodified from (pending token,
            # pos): every KV position the stalled chunk writes is
            # re-written by the resumed chunk before it can be attended
            # (decode writes position pos+t at scan step t, then attends
            # <= pos+t), writes past the allocated suffix clip to the
            # garbage block, and the slot's REC/SSD state row is
            # redirected to the garbage state row (slots.state_rows) so
            # the recurrence cannot advance twice — so discarding the
            # outputs and not advancing pos is a true no-op.
            walked = self._decode_kv_blocks()
            with NO_SPAN if tel is None else tel.span(
                    tm.SPAN_RT_DECODE_STAGE):
                t0 = self._timer()
                toks, self.cache = self._decode(
                    self.params, jnp.asarray(self.slots.tokens), self.cache,
                    jnp.asarray(self.slots.pos),
                    jnp.asarray(self.slots.block_tbl),
                    jnp.asarray(self.slots.adapter),
                    jnp.asarray(self.slots.state_rows(
                        self.garbage_state_row)),
                    jnp.asarray(self.slots.temp),
                    jnp.asarray(self.slots.top_k),
                    jnp.asarray(self.slots.top_p),
                    jnp.asarray(self.slots.seed),
                    jnp.asarray(self.slots.rng_counter))
            with NO_SPAN if tel is None else tel.span(
                    tm.SPAN_RT_DECODE_SYNC):
                toks = np.asarray(toks)  # reprolint: sync-point — (B, K)
                #   token emission, the serving loop's one deliberate
                #   decode sync
                t1 = self._timer()
            with NO_SPAN if tel is None else tel.span(
                    tm.SPAN_RT_DECODE_EMIT):
                dt = t1 - t0
                self.stats["decode_chunks"] += 1
                self.stats["decode_kv_blocks"] += walked
                self.metrics.histogram(
                    "decode_dispatch_s",
                    "wall seconds per jitted decode-chunk dispatch"
                ).observe(dt)
                emitted, finished = self._emit(toks)
                self._sample_gauges()
        return DecodeResult(emitted, finished, aborted, stalled, dt,
                            preempted=preempted)

    def _decode_kv_blocks(self) -> int:
        """K/V blocks the paged-decode kernel fetches (per layer and kv
        head) over the next dispatch's scan steps, from the slot mirrors
        and the kernel's bounds (``kernels/paged_attention/paged_attn.py``):
        a row walks its allocated table entries from its window's first
        block to its position's block, and nothing where that block is
        unallocated (an inactive slot, or a scan step past the row's
        allocation)."""
        tbl, bs, w = self.slots.block_tbl, self.scfg.block_size, \
            self.cfg.sliding_window
        B, MB = tbl.shape
        held = np.zeros((B, MB + 1), np.int64)    # allocated entries < j
        np.cumsum(tbl >= 0, axis=1, out=held[:, 1:])
        rows = np.arange(B)
        walked = 0
        for t in range(self.scfg.decode_chunk):
            pos = self.slots.pos.astype(np.int64) + t
            last = np.minimum(pos // bs, MB - 1)
            first = (np.zeros_like(last) if w is None else
                     np.minimum(np.maximum(pos - w + 1, 0) // bs, last))
            n = held[rows, last + 1] - held[rows, first]
            walked += int(np.where(tbl[rows, last] >= 0, n, 0).sum())
        return walked

    def _emit(self, toks: np.ndarray
              ) -> Tuple[Dict[int, List[int]], List[SlotState]]:
        """Accept each running, unstalled slot's tokens of this chunk
        (budget- and EOS-clipped), release the slots that finished and
        advance the rest."""
        scfg = self.scfg
        emitted: Dict[int, List[int]] = {}
        finished: List[SlotState] = []
        for s in list(self.slots.active()):
            if s.stalled:
                continue
            remaining = s.budget - s.produced
            accept = toks[s.sid, :remaining]
            eos_hit = False
            if scfg.eos_id is not None:
                hits = np.flatnonzero(accept == scfg.eos_id)
                if hits.size:
                    accept = accept[: hits[0] + 1]
                    eos_hit = True
            emitted[s.sid] = [int(t) for t in accept]
            s.history.extend(emitted[s.sid])
            s.produced += len(accept)
            mode = s.sampling.mode()
            self.stats[f"tokens_mode_{mode}"] += len(accept)
            if mode != "greedy":
                self.stats["sampled_tokens"] += len(accept)
            if eos_hit or s.produced >= s.budget:
                self._release_slot(s)
                finished.append(s)
            else:
                s.pos += scfg.decode_chunk
                s.last_token = int(accept[-1])
                self.slots.pos[s.sid] = s.pos
                self.slots.tokens[s.sid] = s.last_token
                # RNG counter == tokens generated so far: the next chunk
                # samples counters [produced, produced + chunk).  Stalled
                # slots never reach here, so their counters re-dispatch
                # unchanged — the stall replay draws the same keys.
                self.slots.rng_counter[s.sid] = s.produced
                self._reclaim_window(s)
        return emitted, finished

    def _reclaim_window(self, s: SlotState) -> None:
        """Release blocks that slid fully out of the sliding window.

        Every future query of this slot sits at position >= s.pos, and all
        decode paths mask keys at t <= pos - window (and -1 table entries),
        so a block whose whole [j*bs, (j+1)*bs) range is <= s.pos - window
        can never be read (or written: writes land at pos // bs >= the
        first live block) again.  The release is a refcount drop — a
        prefix-shared prompt block outlives this slot's window if other
        requests still map it, and a registered one parks in the cached
        LRU, still matchable by future admits."""
        w = self.cfg.sliding_window
        if w is None or not self.scfg.window_reclamation:
            return
        dead = (s.pos - w + 1) // self.scfg.block_size
        if dead > s.reclaimed:
            freed = self.slots.reclaim(s.sid, dead)
            if freed:
                self.pool.free(freed)
                self.stats["reclaimed_blocks"] += len(freed)

    # -------------------------------------------------------------- meta
    def check_invariants(self, requests: Optional[Sequence[Request]] = None,
                         *, raise_on_error: bool = True) -> Dict[str, Any]:
        """Audit the runtime's books; the ONE implementation replay,
        benches, and tests share (``replay_trace`` runs it after every
        replay).

        Structural checks (always): every block a bound slot maps is live
        with a refcount covering all its holders and is not simultaneously
        parked in the cached LRU; adapter pin counts equal the live
        holders per bank slot (a mismatch is a pin leak on some exit
        path).

        Terminal-state conservation (with ``requests``): every trace
        request ends in EXACTLY one of finished / rejected / aborted /
        abandoned — the per-class totals reconcile with the trace length.
        Classification is per-request (breakdown flags + timestamps), so
        the check is valid even when several replays shared one runtime's
        counters.

        Returns a report dict (``problems``, ``terminal`` class counts,
        pool/slot occupancy); raises AssertionError listing every
        violation unless ``raise_on_error=False``."""
        problems: List[str] = []
        held: Dict[int, int] = {}
        for s in self.slots.active():
            for b in s.blocks:
                if b >= 0:
                    held[b] = held.get(b, 0) + 1
        for b, n in sorted(held.items()):
            r = self.pool.refcount(b)
            if r < n:
                problems.append(
                    f"block {b}: {n} slot holder(s) but refcount {r}")
            if self.pool.is_cached(b):
                problems.append(
                    f"block {b} parked in the cached LRU while a bound "
                    f"slot still maps it")
        if self.adapters is not None:
            want: Dict[int, int] = {}
            for s in self.slots.active():
                want[s.adapter] = want.get(s.adapter, 0) + 1
            got = self.adapters.pin_counts()
            if got != want:
                problems.append(
                    f"adapter pins {got} != active-slot holders {want} "
                    f"(pin leak/double-unpin on some exit path)")
        terminal = {"finished": 0, "rejected": 0, "aborted": 0,
                    "abandoned": 0, "unresolved": 0}
        if requests is not None:
            requests = list(requests)
            for r in requests:
                try:
                    cls = terminal_state(r)
                except ValueError as e:
                    problems.append(str(e))
                    continue
                terminal[cls if cls is not None else "unresolved"] += 1
            if terminal["unresolved"]:
                problems.append(
                    f"{terminal['unresolved']} request(s) ended the "
                    f"replay in NO terminal state")
            resolved = sum(v for k, v in terminal.items()
                           if k != "unresolved")
            if resolved + terminal["unresolved"] != len(requests):
                problems.append(
                    f"terminal classes sum to {resolved} != trace "
                    f"length {len(requests)}")
        report = {
            "problems": problems,
            "terminal": terminal,
            "pool": {"live": self.pool.in_use,
                     "cached": self.pool.num_cached,
                     "free": self.pool.num_free},
            "slots_active": self.slots.num_active,
        }
        if problems and raise_on_error:
            raise AssertionError(
                "runtime invariant violation(s): " + "; ".join(problems))
        return report

    def warmup(self) -> Dict[str, Any]:
        """Compile the two fixed shapes — ONE chunked-prefill step (for
        every prompt length) and the decode chunk — and measure
        steady-state latencies.  Leaves pool and slots untouched (warmup
        traffic only ever writes the garbage block).

        The timings also land in the metrics registry as
        ``warmup_prefill_chunk_s`` / ``warmup_decode_chunk_s`` gauges, so
        every metrics snapshot carries the Eq. 2 profile the admission
        scheduler was seeded with, instead of the dict being dropped
        after ``replay_trace`` wires the scheduler."""
        timings = {}
        for kind, fn in (("prefill", self._prefill),
                         ("decode", self._decode)):
            for rep in range(2):
                t0 = self._timer()
                out, self.cache = fn(*self._warmup_args(kind))
                np.asarray(out)
                timings[f"{kind}_chunk_s"] = self._timer() - t0
        for key, val in timings.items():
            self.metrics.gauge(
                f"warmup_{key}", "steady-state step latency measured at "
                "warmup (the admission scheduler's Eq. 2 seed)").set(val)
        return timings

    def _warmup_args(self, kind: str) -> Tuple[Any, ...]:
        """Arguments of one ``kind`` ("prefill" / "decode") dispatch at
        the serving shape.  Warmup rows write only the garbage block and
        the garbage state row, and sample in greedy mode (temp 0 / k off /
        p off): the sampling vectors are data, so this is the ONE shape
        every later mode mix reuses."""
        scfg = self.scfg
        if kind == "prefill":
            G, C = scfg.prefill_rows, scfg.prefill_chunk
            zeros = jnp.zeros((G,), jnp.int32)
            return (self.params, jnp.zeros((G, C), jnp.int32), zeros, zeros,
                    zeros, self.cache,
                    jnp.full((G, C // scfg.block_size), GARBAGE_BLOCK,
                             jnp.int32),
                    jnp.full((G, scfg.max_blocks_per_slot), -1, jnp.int32),
                    jnp.full((G,), self.garbage_state_row, jnp.int32),
                    jnp.zeros((G,), jnp.float32), zeros,
                    jnp.ones((G,), jnp.float32), zeros)
        B = scfg.num_slots
        zeros = jnp.zeros((B,), jnp.int32)
        return (self.params, jnp.asarray(self.slots.tokens), self.cache,
                jnp.asarray(self.slots.pos),
                jnp.asarray(self.slots.block_tbl),
                jnp.asarray(self.slots.adapter),
                jnp.full((B,), self.garbage_state_row, jnp.int32),
                jnp.zeros((B,), jnp.float32), zeros,
                jnp.ones((B,), jnp.float32), zeros, zeros)

    def lower_dispatch(self, kind: str) -> jax.stages.Lowered:
        """The ``kind`` ("prefill" / "decode") program lowered at its one
        serving shape, without running it: ``.as_text()`` names the
        kernels it calls (``tpu_custom_call`` for a Pallas kernel)."""
        fn = {"prefill": self._prefill, "decode": self._decode}[kind]
        return fn.lower(*self._warmup_args(kind))

    def _sample_gauges(self) -> None:
        """Sample the occupancy gauges at a scheduling boundary (end of
        every admit / decode chunk).  Pure host-side reads — no device
        sync, no timer calls, so sampling can never perturb timings."""
        g = self.metrics.gauge
        g("pool_free_blocks", "free-list blocks").set(self.pool.num_free)
        g("pool_live_blocks",
          "live blocks (refcount >= 1)").set(self.pool.in_use)
        g("pool_cached_blocks",
          "refcount-0 blocks parked for prefix reuse").set(
            self.pool.num_cached)
        g("pool_high_water_blocks",
          "peak live-block count").set(self.pool.high_water)
        g("slots_active", "bound decode slots").set(self.slots.num_active)
        g("slot_utilization_frac", "active / num_slots").set(
            self.slots.num_active / max(self.scfg.num_slots, 1))
        if self.prefix is not None:
            g("prefix_trie_blocks",
              "physical blocks indexed by the prefix trie").set(
                len(self.prefix))

    def metrics_snapshot(self) -> Dict[str, Any]:
        """One flat JSON-able dict of everything the runtime knows:
        counters (the legacy ``stats`` keys plus the new mirrors), gauge
        summaries, latency histograms and compile-count mirrors.
        Exporters (``--metrics-out``, ``BENCH_serving.json``) call this
        once after a replay."""
        self._sample_gauges()
        self.metrics.gauge(
            "decode_compiles", "decode-step compile count (must be 1)"
        ).set(self.decode_compiles())
        self.metrics.gauge(
            "prefill_compiles", "chunked-prefill compile count (must be 1)"
        ).set(self.prefill_compiles())
        snap = self.metrics.snapshot()
        if self.telemetry is not None:
            snap["spans"] = len(self.telemetry.spans)
            snap["instant_events"] = len(self.telemetry.instants)
        return snap

    def decode_compiles(self) -> int:
        """Compile-count probe for the decode step (must be 1 after warmup;
        re-jit mid-serving would blow every TPOT SLO)."""
        return int(self._decode._cache_size())

    def prefill_compiles(self) -> int:
        """Compile-count probe for the chunked prefill step (must be 1
        after warmup across EVERY prompt length — the bucketed path paid
        one compile per bucket, all of them at cold-start warmup)."""
        return int(self._prefill._cache_size())
