"""Paged-KV continuous-batching serve engine over a tiered KVStore.

KV memory is owned by ``repro.serve.kv_store``: refcounted block handles in
named storage tiers — the device block pool (``repro.serve.paged_cache``) and
a host swap tier.  Each request holds an ordered table of handles, blocks are
allocated as its sequence grows and released the step it retires, so live KV
scales with tokens actually resident instead of the dense slot cache's
``max_batch x max_len`` preallocation (MNN-LLM block-wise layout, arXiv
2506.10443).  On top of the handles the engine gets two storage-architecture
capabilities the flat pool couldn't express:

  * **Prefix sharing (copy-on-write)** — completed prompts register their
    blocks in the store's budgeted prefix registry; a later request whose
    prompt shares a prefix ``fork()``s the same physical blocks instead of
    re-prefilling them (``ServeMetrics.re_prefill_avoided``), and any write
    into a still-shared block is privatized by a device-side copy first.
  * **Preemption-by-swap** — optimistic admission's evictions park the
    victim's KV on the host tier (``REPRO_KV_SWAP=1``, the default) and
    restore it on re-admission, resuming mid-generation; with the knob off,
    preemption falls back to the legacy drop-and-restart-from-prompt.

Scheduling is continuous batching with **chunked prefill**: every engine step
runs (a) at most one prompt chunk for one admitting request and (b) one
batched decode step for every live request — a long prompt therefore never
stalls tokens streaming out of the decode batch.  Admission is worst-case by
default: the exact bound is ``prompt + max_new - 1`` written KV positions
(the last sampled token's KV never lands), plus one spare block when the
prefix registry may force a copy-on-write of the prompt's partial tail block.
``admission="optimistic"`` reserves only the prompt footprint and preempts
the youngest request when the pool runs dry.

Per-request sampling: greedy, temperature, top-k — Gumbel-max draws keyed on
(request seed, token index), stateless and host-side, so runs are exactly
reproducible (including across preemptions, swapped or restarted) with no
per-token device dispatch in the decode loop.

Kernel planning goes through the unified ``repro.pipeline`` entry point: the
engine compiles its *paged* attention shapes — a 1-token decode query and a
prefill chunk query against the pooled KV span — so the compiler plans for
the layout serving actually uses.  The plan's kv tile also fixes the paged
flash-attention kernel's pages-per-fetch (``repro.kernels.paged_attention``;
dispatch via the REPRO_PAGED_ATTN knob — kernel on TPU, dense-gather
fallback on CPU).  The pipeline's compile cache makes repeated engine
construction skip saturation and search entirely.

**Multi-device serving** (``mesh=`` or the REPRO_SERVE_MESH knob): the
device tier's block slab is sharded over the mesh's "model" axis on the
kv-heads dim (``repro.distributed.sharding.paged_cache_specs``) and the
paged attention paths run under shard_map grouped by KV head — outputs are
token-identical to a single-device run because no floating-point reduction
ever crosses a shard (per-shard head outputs are all-gathered, never
partial-summed).  Scheduling, admission, CoW, prefix sharing, and
preemption-by-swap are untouched: block ids stay global, and
``swap_out``/``swap_in`` gather/scatter each block's per-shard slices so the
host tier keeps holding whole blocks (replicated-on-host).

**Weight tensor parallelism** (``tp=True`` or REPRO_SERVE_TP=1, on top of a
mesh): params are ``device_put`` with the partition rules Auto
Distribution's SBP cost model emits (``repro.distributed.param_sharding``
— canonically column-parallel qkv/up/gate, row-parallel wo/down, so
per-device param bytes drop to ~1/n).  By default weights are gathered at
their use site, keeping decode bitwise identical; REPRO_TP_REDUCE_SCATTER=1
makes compute follow the stored layout with one all-reduce per layer
(fp32-tolerance closeness instead).  ``param_bytes_per_device`` /
``param_bytes_replicated`` report the storage win; see docs/sharding.md.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.codegen import lora_tiles, paged_pages_per_fetch
from repro.core.tensor_ir import inp, matmul, unary
from repro.distributed import param_sharding
from repro.models import build_model
from repro.models import attention as attn_lib
from repro import tracing
from repro.perf import perf
from repro.pipeline import CompileOptions, Compiler, default_compiler
from repro.kernels import lora as lora_kernels
from repro.serve.adapters import AdapterStore, AdapterStoreFull
from repro.serve.faults import FaultInjector, InjectedFault, check_kv_invariants
from repro.serve.kv_store import (DEVICE, HOST, Block, BlockTable, DeviceTier,
                                  HostTier, KVStore, SlabDeviceView, StateSlab)
from repro.serve.paged_cache import (BlockPool, PoolExhausted, ServeMetrics,
                                     blocks_for_tokens, dense_equiv_blocks,
                                     worst_case_blocks)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding strategy.  temperature <= 0 means greedy;
    top_k == 0 means the full vocabulary."""
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0


GREEDY = SamplingParams()


def _mesh_from_knob():
    """Resolve REPRO_SERVE_MESH: "0"/"" = single-device (None), "auto" =
    shard over every visible device, an int = shard over the first N."""
    knob = perf().serve_mesh
    if knob in ("", "0", "off"):
        return None
    from repro.launch.mesh import make_serve_mesh
    return make_serve_mesh(None if knob == "auto" else int(knob))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    sampling: SamplingParams = GREEDY
    # multi-LoRA (PR 9): which tenant adapter decorates this request's
    # projections; None = base-only (the traced graph stays structurally
    # adapter-free, so base requests are bitwise identical to a LoRA-less
    # engine).  The engine acquires a refcounted AdapterStore slot at submit
    # and releases it exactly once on whichever terminal path runs.
    adapter_id: Optional[str] = None
    _adapter_slot: int = -1
    _adapter_held: bool = False
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    rejected: bool = False
    cancelled: bool = False
    reject_reason: str = ""
    # fault-tolerance terminal states (PR 8)
    expired: bool = False       # deadline reaper killed it
    shed: bool = False          # bounded queue refused it at submit
    errored: bool = False       # quarantined by a step-loop crash
    error: str = ""             # why (crash message)
    # per-request deadline in ms from submit; None consults the
    # REPRO_SERVE_DEADLINE_MS default, 0 disables.  The engine stamps the
    # absolute monotonic cutoff into _deadline_at at submit time.
    deadline_ms: Optional[float] = None
    _deadline_at: float = 0.0
    # timing (monotonic seconds).  t_received: the front-end took the
    # request (the async inbox; equal to t_submit when submitted directly);
    # t_submit: the engine took it; t_prefill0: its first prefill dispatch,
    # kept across preemption; t_first: its first token; t_done: it ended
    t_received: float = 0.0
    t_submit: float = 0.0
    t_prefill0: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    # streaming hooks (the async front-end wires these; both run inside the
    # engine's step loop, so they must be cheap and must not raise).
    # on_token(token_id, index): fired the moment a token is sampled.  Under
    # legacy drop-and-restart preemption (REPRO_KV_SWAP=0) a request replays
    # its deterministic sample stream, so indices can repeat — consumers
    # dedupe on ``index``, not on call count.
    on_token: Optional[Callable[[int, int], None]] = None
    # on_finish(request): fired exactly once, after done/rejected/cancelled
    # is set and the request's KV blocks are back in the pool.
    on_finish: Optional[Callable[["Request"], None]] = None

    @property
    def finish_reason(self) -> str:
        """OpenAI-style terminal state ("" while still running)."""
        if self.cancelled:
            return "cancelled"
        if self.expired:
            return "expired"
        if self.shed:
            return "shed"
        if self.errored:
            return "error"
        if self.rejected:
            return "rejected"
        if self.done:
            return "length"
        return ""


_REQUEST_SPANS = ("request.inbox", "request.queue", "request.prefill",
                  "request.decode")


def _count_donation(pool) -> None:
    """With tracing on, count whether a dispatch consumed the pool it was
    given (``kv.pool_donated``) or left it live, which means the program
    wrote a whole new pool (``kv.pool_copied``).  Host metadata only."""
    if tracing.enabled():
        donated = all(x.is_deleted() for x in jax.tree.leaves(pool))
        tracing.count("kv.pool_donated" if donated else "kv.pool_copied")


def _trace_request(req: Request) -> None:
    """A request's spans, a view of its stamps (received, submit,
    prefill0, first, done): each span between two stamps that were
    taken."""
    if not tracing.enabled():
        return
    ns = [round(t * 1e9) for t in (req.t_received, req.t_submit,
                                   req.t_prefill0, req.t_first, req.t_done)]
    for name, t0, t1 in zip(_REQUEST_SPANS, ns, ns[1:]):
        if t0 and t1:
            tracing.record(name, t0, t1, rid=req.rid)


@dataclasses.dataclass
class _Active:
    """A request occupying a batch slot."""
    req: Request
    table: BlockTable
    reserved_left: int          # blocks still earmarked in the pool for us
    admit_seq: int              # admission order (preemption picks the max)
    next_prefill: int = 0       # prompt tokens already prefilled
    pos: int = 0                # KV entries written (valid only post-prefill)
    # stateful families (ssm/hybrid): the request's recurrent-state slab
    # slot, a refcounted handle in the engine's StateSlab (None otherwise)
    state: Optional[Block] = None

    @property
    def prefill_done(self) -> bool:
        return self.next_prefill >= len(self.req.prompt)


@dataclasses.dataclass
class _Parked:
    """A preempted request's KV, waiting on the host tier for re-admission.
    ``blocks`` mixes tiers: exclusive blocks were swapped to host; blocks
    shared with the prefix registry stay device-resident (other holders pin
    them anyway), and we just keep our reference."""
    blocks: List[Block]
    next_prefill: int
    pos: int
    # stateful families: the recurrent state, swapped whole to the slab's
    # host tier (state is never shared, so it always moves on park)
    state: Optional[Block] = None


# ---------------------------------------------------------------------------
# Pipeline terms: the attention shapes serving actually executes
# ---------------------------------------------------------------------------

def _attn_term(q_rows: int, kv_span: int, head_dim: int):
    """O = MatMul(Exp(MatMul(Q, K)), V) with ``q_rows`` queries against a
    ``kv_span``-position KV — the one attention inner block every serving
    shape instantiates."""
    q = inp("Q", (q_rows, head_dim))
    k = inp("K", (head_dim, kv_span))
    v = inp("V", (kv_span, head_dim))
    return matmul(unary(matmul(q, k), kind="exp"), v)


def attention_block_term(seq_len: int, head_dim: int):
    """Square attention inner block (kept for inspection tooling)."""
    return _attn_term(seq_len, seq_len, head_dim)


def paged_decode_attention_term(span: int, head_dim: int):
    """One decode token's attention against a request's pooled KV span
    (``span`` = max_blocks_per_seq * block_size gathered positions)."""
    return _attn_term(1, span, head_dim)


def chunked_prefill_attention_term(chunk: int, span: int, head_dim: int):
    """A prefill chunk's attention: ``chunk`` queries against the span."""
    return _attn_term(chunk, span, head_dim)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, max_batch: int = 4,
                 max_len: int = 256, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 admission: str = "conservative",
                 host_blocks: Optional[int] = None,
                 prefix_cache_blocks: Optional[int] = None,
                 compiler: Optional[Compiler] = None,
                 plan_kernels: bool = True,
                 mesh=None,
                 tp: Optional[bool] = None,
                 max_queue: Optional[int] = None,
                 fault_injector=None):
        # mesh: a jax Mesh with a "model" axis to shard the KV pool over,
        # None to consult REPRO_SERVE_MESH, or False to force single-device
        # tp: also shard the WEIGHTS over the model axis with the partition
        # rules Auto Distribution emits (param_sharding); None consults
        # REPRO_SERVE_TP.  Requires a mesh; no-op without one.
        # max_queue: bound on the admission queue (submits past it are shed
        # with finish_reason="shed"); None consults REPRO_SERVE_MAX_QUEUE,
        # 0 = unbounded.
        # fault_injector: a repro.serve.faults.FaultInjector wired into the
        # allocator, the swap paths, and the step dispatch; None consults
        # REPRO_FAULT, False forces off (oracle/reference engines must not
        # inherit chaos from ambient env).
        # vlm is excluded deliberately: the paged prefill/decode path embeds
        # raw token ids with 2-D positions, which would silently degrade
        # M-RoPE + vision-embeds frontends; wiring the embeds interface
        # through chunked prefill is a roadmap item.
        assert cfg.family in ("dense", "moe", "ssm", "hybrid"), \
            "paged engine targets token-frontend decoder-LM families"
        assert admission in ("conservative", "optimistic")
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.block_size = block_size
        self.max_blocks_per_seq = blocks_for_tokens(max_len, block_size)
        if num_blocks is None:
            # capacity parity with the dense slot cache, plus the null block;
            # smaller pools trade throughput for memory via admission control
            num_blocks = max_batch * self.max_blocks_per_seq + 1
        self.pool = BlockPool(num_blocks, block_size)
        self.admission = admission
        self.prefill_chunk_tokens = prefill_chunk_tokens or block_size

        self.fns = build_model(cfg)
        assert self.fns.decode_paged is not None, \
            f"family {cfg.family!r} has no paged decode path"
        assert self.fns.paged_block_copy is not None, \
            f"family {cfg.family!r} has no paged block data plane"
        # stateful families (ssm, hybrid) carry O(1) recurrent state per
        # request in a StateSlab tier beside the block pool; attention-free
        # families never touch the block table at all
        self.has_attention = cfg.family in ("dense", "moe", "hybrid")
        self.has_state = self.fns.state_slot_copy is not None
        if self.has_state:
            # scan-chunk alignment: engine chunk boundaries must land on
            # multiples of the SSD chunk so the associative-scan tree inside
            # an engine chunk matches the dense oracle's bitwise (the masked
            # tail is exact: dt=0 gives a=exp(0)=1, b=0, the scan identity)
            g = cfg.ssm.chunk
            self.prefill_chunk_tokens = max(
                g * ((self.prefill_chunk_tokens + g - 1) // g), g)

        # tiered KV store: device slab + host swap tier + prefix registry
        self.swap_enabled = perf().kv_swap and (host_blocks is None
                                                or host_blocks > 0)
        n_host = (host_blocks if host_blocks is not None else num_blocks) \
            if self.swap_enabled else 0
        prefix_budget = prefix_cache_blocks if prefix_cache_blocks \
            is not None else self.pool.usable_blocks // 4
        if self.has_state:
            # adopted KV blocks cannot reproduce a request's scan state, so
            # prefix sharing is structurally off for stateful families:
            # budget 0 makes match_prefix miss and register_prefix a no-op
            prefix_budget = 0

        # multi-device serving: shard the block slab over the mesh's "model"
        # axis on the kv-heads dim, replicate params, and leave every piece
        # of bookkeeping (global block ids, refcounts, tables) untouched.
        # mesh=None (default) consults REPRO_SERVE_MESH; mesh=False forces
        # single-device regardless of the knob (oracle/reference engines
        # must not be silently sharded by ambient env)
        if mesh is False:
            self.mesh = None
        else:
            self.mesh = mesh if mesh is not None else _mesh_from_knob()
        if self.mesh is not None and self.has_state:
            raise NotImplementedError(
                "sharded serving of ssm/hybrid families is not supported "
                "yet — the state slab has no mesh partition rules; run "
                "stateful families on a single-device engine")
        self.tp = bool(tp) if tp is not None else perf().serve_tp
        if self.mesh is None:
            self.tp = False
        self.tp_rules = None
        self.tp_report = None
        # slot 0 of the state slab is the null slot (padded decode rows)
        self.state_slots = max_batch + 1 if self.has_state else 0
        cache0 = (self.fns.make_paged_cache(num_blocks, block_size,
                                            state_slots=self.state_slots)
                  if self.has_state
                  else self.fns.make_paged_cache(num_blocks, block_size))
        shardings = None
        if self.mesh is not None:
            n_tp = int(self.mesh.shape.get("model", 1))
            if cfg.n_kv_heads % n_tp or cfg.n_heads % n_tp:
                raise ValueError(
                    f"serve mesh model axis {n_tp} must divide n_kv_heads "
                    f"{cfg.n_kv_heads} and n_heads {cfg.n_heads} — the pool "
                    "is sharded per KV head (GQA groups stay intact)")
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.distributed.sharding import paged_cache_specs, to_named
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache0)
            shardings = to_named(paged_cache_specs(cfg, abstract, self.mesh),
                                 self.mesh)
            if self.tp:
                # weight tensor parallelism: rules chosen by Auto
                # Distribution's SBP cost model, matched against the param
                # paths, device_put per-leaf — see param_sharding.py
                param_sharding.validate_tp_divisibility(cfg, n_tp)
                abstract_p = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    self.params)
                self.tp_rules = param_sharding.choose_tp_rules(cfg, n_tp)
                pspecs, self.tp_report = param_sharding.tp_param_specs(
                    cfg, abstract_p, n_tp, rules=self.tp_rules)
                self.params = jax.device_put(
                    self.params, to_named(pspecs, self.mesh))
            else:
                self.params = jax.device_put(
                    self.params, NamedSharding(self.mesh, PartitionSpec()))
        self._tp_reduce_scatter = self.tp and perf().tp_reduce_scatter
        self.param_bytes_replicated = param_sharding.param_bytes_total(
            self.params)
        self.param_bytes_per_device = param_sharding.param_bytes_per_device(
            self.params)
        device = DeviceTier(cache0, self.pool,
                            copy_block=self.fns.paged_block_copy,
                            read_block=self.fns.paged_block_read,
                            write_block=self.fns.paged_block_write,
                            shardings=shardings)
        self.store = KVStore(device, HostTier(n_host),
                             prefix_cache_blocks=prefix_budget)

        # state slab: per-request O(1) recurrent state as the degenerate
        # one-block case of the block pool — same refcounted handles, same
        # host swap tier, same ledger invariants.  The slab view shares the
        # DeviceTier (one cache pytree holds KV pages and state slots; the
        # slot data plane touches only the state leaves).
        self.state_store: Optional[StateSlab] = None
        if self.has_state:
            state_pool = BlockPool(self.state_slots, 1)
            slab_view = SlabDeviceView(device, state_pool,
                                       self.fns.state_slot_copy,
                                       self.fns.state_slot_read,
                                       self.fns.state_slot_write)
            # parked states can outnumber the live slots; host-full simply
            # downgrades the park to the legacy drop (perf, not correctness)
            n_state_host = 4 * max_batch if self.swap_enabled else 0
            self.state_store = StateSlab(slab_view, HostTier(n_state_host))

        # fault tolerance: chaos injector (opt-in), bounded queue, default
        # deadline, crash quarantine bookkeeping
        if fault_injector is False:
            self.faults = None
        else:
            self.faults = fault_injector if fault_injector is not None \
                else FaultInjector.from_env()
        self.pool.fault_injector = self.faults
        self.store.fault_injector = self.faults
        if self.state_store is not None:
            self.state_store.fault_injector = self.faults
            self.state_store.device.pool.fault_injector = self.faults
        self.max_queue = perf().serve_max_queue if max_queue is None \
            else max_queue
        self.default_deadline_ms = perf().serve_deadline_ms
        self.shed_pressure = perf().serve_shed_pressure
        self.max_consecutive_crashes = max(perf().serve_max_crashes, 1)
        self.degraded = False
        self.invariant_violations: List[str] = []
        self._blame_rid: Optional[int] = None    # request under the knife now
        self._crash_rid: Optional[int] = None    # captured at raise time
        self._consecutive_crashes = 0
        self._step_crashes = 0
        self._swap_failures = 0
        self._gateway_shed = 0   # 429s the gateway refused pre-submit

        self.slots: List[Optional[_Active]] = [None] * max_batch
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.rejected: List[Request] = []
        self.cancelled: List[Request] = []
        self.expired: List[Request] = []
        self.errored: List[Request] = []
        self.shed: List[Request] = []
        self._parked: Dict[int, _Parked] = {}
        self.steps = 0
        self._admit_seq = 0
        self._t0: Optional[float] = None
        self._t_last = 0.0
        self._submitted = 0
        self._prefill_tokens = 0
        self._decode_tokens = 0
        self._preemptions = 0
        self._re_prefill_avoided = 0
        # per-tenant delivery tallies (key: adapter_id, "base" for None)
        self._tenant_tokens: Dict[str, int] = {}
        self._tenant_finished: Dict[str, int] = {}

        # multi-LoRA adapter store: per-tenant low-rank deltas in a
        # refcounted two-tier slab (device + host write-through).  Zero
        # device bytes until the first load, so LoRA-less engines pay
        # nothing.  In-flight requests hold a ref, so a live tenant can
        # never be evicted out from under its own decode.
        self.adapters = AdapterStore(cfg)

        # unified pipeline: compile the paged attention shapes once (cached,
        # so a second engine on the same shapes skips the search passes)
        self.compile_reports: Dict[str, object] = {}
        self.compile_report = None
        self.kernel_plan = None
        if plan_kernels and self.has_attention:
            with tracing.span("engine.kernel_plan"):
                compiler = compiler or default_compiler()
                hd = cfg.resolved_head_dim
                span = self.max_blocks_per_seq * block_size
                opts = CompileOptions(extraction="greedy",
                                      schedule_iterations=10)
                dec = compiler.compile(paged_decode_attention_term(span, hd),
                                       options=opts)
                pre = compiler.compile(
                    chunked_prefill_attention_term(self.prefill_chunk_tokens,
                                                   span, hd), options=opts)
                self.compile_reports = {"decode": dec.report,
                                        "prefill": pre.report}
                self.compile_report = dec.report
                self.kernel_plan = dec.report.kernel_plan

        # the compiler's kv tile for the *decode* shape sets how many pages
        # the paged-attention kernel streams per grid step; the jit wrappers
        # publish it at trace time so the traced graph bakes this plan in
        # even if another engine has since planned different shapes
        self.pages_per_fetch = 1
        self.lora_block_out = 256
        if self.kernel_plan is not None:
            self.pages_per_fetch = paged_pages_per_fetch(
                self.kernel_plan, block_size, self.max_blocks_per_seq)
            # the same plan routes the segmented LoRA expand's output tile
            self.lora_block_out, _ = lora_tiles(
                self.kernel_plan, cfg.d_model, self.adapters.rank_cap)

        # set_serve_mesh is restored after tracing (the finally runs at
        # trace time, right after the model graph is built) so the module
        # state never leaks into unrelated traces in the same process
        def _decode(p, c, b):
            attn_lib.set_paged_plan(self.pages_per_fetch)
            lora_kernels.set_lora_plan(self.lora_block_out)
            attn_lib.set_serve_mesh(self.mesh)
            param_sharding.set_serve_tp(self.mesh if self.tp else None,
                                        self._tp_reduce_scatter)
            try:
                return self.fns.decode_paged(p, c, b)
            finally:
                attn_lib.set_serve_mesh(None)
                param_sharding.set_serve_tp(None)

        def _prefill(p, c, b, m_used):
            attn_lib.set_paged_plan(self.pages_per_fetch)
            lora_kernels.set_lora_plan(self.lora_block_out)
            attn_lib.set_serve_mesh(self.mesh)
            param_sharding.set_serve_tp(self.mesh if self.tp else None,
                                        self._tp_reduce_scatter)
            try:
                return self.fns.prefill_chunk(p, c, b, m_used=m_used)
            finally:
                attn_lib.set_serve_mesh(None)
                param_sharding.set_serve_tp(None)

        # decode consumes the pool it is given (donated) and hands back the
        # updated one: its layer scan writes the pool in place, so a decode
        # step moves no pool bytes (models/transformer.lm_decode_step_paged).
        # Prefill still slices each layer's slab out of the scan's xs, where
        # donation would make the program copy more, so it stays undonated
        self._decode_fn = jax.jit(_decode, donate_argnums=(1,))
        # one retrace per distinct m_used (bounded by max_blocks_per_seq),
        # each strictly cheaper than the old full-table trace
        self._prefill_fn = jax.jit(_prefill, static_argnames=("m_used",))

    # the jitted fns thread the device slab functionally; the store's device
    # tier holds the current reference between dispatches.  A decode
    # dispatch deletes the pool it was given, so every holder reads
    # ``cache`` anew after one and keeps no reference across it.
    @property
    def cache(self):
        return self.store.device.cache

    @cache.setter
    def cache(self, value):
        # _pin re-asserts the slab's mesh sharding (no-op when unsharded or
        # when GSPMD preserved it, which the shard_map out_specs guarantee)
        self.store.device.cache = self.store.device._pin(value)

    # -- multi-LoRA adapters -----------------------------------------------
    def load_adapter(self, name: str, weights=None,
                     rank: Optional[int] = None,
                     alpha: Optional[float] = None) -> int:
        """Make tenant ``name``'s adapter device-resident (synthesizing
        deterministic factors from the name when ``weights`` is None) and
        return its slot.  Multi-LoRA is single-device for now: the segmented
        gather kernels run outside the shard_map the sharded attention paths
        trace, so a mesh engine refuses adapters rather than silently
        computing wrong deltas."""
        if self.mesh is not None:
            raise NotImplementedError(
                "multi-LoRA serving is not supported on a sharded serve "
                "mesh yet — run adapters on a single-device engine")
        return self.adapters.load(name, weights=weights, rank=rank,
                                  alpha=alpha)

    def _release_adapter(self, req: Request) -> None:
        """Drop ``req``'s adapter ref exactly once, whichever terminal path
        runs first (retire / reject / cancel / expire / quarantine)."""
        if req._adapter_held:
            req._adapter_held = False
            self.adapters.release(req.adapter_id)

    def _tenant_count(self, req: Request, n: int = 1) -> None:
        t = req.adapter_id or "base"
        self._tenant_tokens[t] = self._tenant_tokens.get(t, 0) + n

    def _lora_descriptor(self, ids: np.ndarray) -> Optional[dict]:
        """``batch["lora"]`` for one dispatch (``ids``: adapter slot per
        row, -1 = base), or None when no row uses an adapter.  The None
        keeps every LoRA op out of the traced graph — that structural
        absence is the ``adapter_id=None`` bitwise-identity contract."""
        if not (ids >= 0).any():
            return None
        slabs = self.adapters.slabs()
        assert slabs is not None, "row holds an adapter slot but no slab"
        return {"ids": jnp.asarray(ids, jnp.int32), "slabs": slabs}

    # -- request lifecycle -----------------------------------------------
    def submit(self, req: Request) -> None:
        """Enqueue ``req`` (FIFO).  Admission control runs inside ``step``:
        the request may later be rejected (impossible footprint — see
        ``Request.reject_reason``) or queued until blocks free up.  The
        engine mutates ``req`` in place: ``out`` grows as tokens are
        sampled, ``done``/``rejected`` flip on completion, and the
        ``t_submit``/``t_first``/``t_done`` stamps feed ``ServeMetrics``.

        A bounded queue (``max_queue`` / REPRO_SERVE_MAX_QUEUE) sheds the
        request instead of enqueueing it — ``finish_reason="shed"``, hooks
        fired — so a flooded engine answers immediately rather than growing
        an unbounded backlog.  The deadline cutoff (per-request
        ``deadline_ms`` or the REPRO_SERVE_DEADLINE_MS default) is stamped
        here; the step loop's reaper enforces it."""
        req.t_submit = time.monotonic()
        if not req.t_received:
            req.t_received = req.t_submit
        self._submitted += 1
        if self.max_queue and len(self.queue) >= self.max_queue:
            req.shed = True
            req.done = True
            req.t_done = req.t_submit
            self.shed.append(req)
            if req.on_finish is not None:
                req.on_finish(req)
            return
        if req.adapter_id is not None:
            if self.mesh is not None:
                raise NotImplementedError(
                    "multi-LoRA serving is not supported on a sharded "
                    "serve mesh yet")
            if not self.adapters.known(req.adapter_id):
                self._reject(req, f"unknown adapter {req.adapter_id!r}")
                return
            try:
                if not self.adapters.is_loaded(req.adapter_id):
                    # evicted to the host tier; slab write brings it back
                    self.adapters.load(req.adapter_id)
                req._adapter_slot = self.adapters.acquire(req.adapter_id)
            except AdapterStoreFull as e:
                self._reject(req, f"adapter store full: {e}")
                return
            req._adapter_held = True
        dl = req.deadline_ms if req.deadline_ms is not None \
            else self.default_deadline_ms
        if dl and dl > 0:
            req._deadline_at = req.t_submit + dl / 1e3
        self.queue.append(req)

    def _reject(self, req: Request, reason: str) -> None:
        self._release_adapter(req)
        req.rejected = True
        req.done = True
        req.reject_reason = reason
        self.rejected.append(req)
        if req.on_finish is not None:
            req.on_finish(req)

    def _admission_need(self, req: Request, parked: Optional[_Parked]) -> int:
        """Blocks to reserve at admission.

        Conservative: the exact lifetime bound (prompt + max_new - 1 written
        positions), plus one spare when the prefix registry may retain the
        prompt's partial tail block and force a copy-on-write allocation at
        the first decode write — the spare is what keeps the admitted-never-
        dies guarantee with sharing enabled.  Optimistic: just the prompt.
        A restored request already holds its written blocks; it reserves the
        remaining growth plus one slot per host block to swap back in.
        """
        if not self.has_attention:
            # attention-free: the footprint is one fixed-size state slot,
            # bounded by construction (slots == max_batch) — no KV blocks
            # to reserve, admission is gated by batch slots alone
            return 0
        plen, bs = len(req.prompt), self.block_size
        worst = worst_case_blocks(plen, req.max_new, bs)
        if parked is not None:
            swap_ins = sum(1 for b in parked.blocks if b.tier == HOST)
            if self.admission == "optimistic":
                return swap_ins
            cow_spare = 1 if (self.store.prefix_cache_blocks > 0 and plen % bs
                              and req.max_new >= 2 and parked.pos == 0) else 0
            return worst - len(parked.blocks) + swap_ins + cow_spare
        if self.admission == "optimistic":
            return blocks_for_tokens(plen, bs)
        cow_spare = 1 if (self.store.prefix_cache_blocks > 0 and plen % bs
                          and req.max_new >= 2) else 0
        # clamp: the spare must not make a barely-fitting request unadmittable
        # (the CoW fallback path evicts/preempts if the spare was clamped off)
        return min(worst + cow_spare, self.pool.usable_blocks)

    def _admit(self) -> int:
        """Move queued requests into free slots, FIFO, under admission
        control.  Head-of-line order is preserved: if the head doesn't fit
        *right now*, nothing behind it jumps the queue."""
        admitted = 0
        while self.queue:
            req = self.queue[0]
            worst = worst_case_blocks(len(req.prompt), req.max_new,
                                      self.block_size)
            if not req.prompt:
                self.queue.pop(0)
                self._reject(req, "empty prompt")
                continue
            if req.max_new < 1:
                self.queue.pop(0)
                self._reject(req, f"max_new must be >= 1, got {req.max_new}")
                continue
            if len(req.prompt) + req.max_new > self.max_len:
                self.queue.pop(0)
                self._reject(req, f"prompt+max_new {len(req.prompt) + req.max_new}"
                                  f" exceeds max_len {self.max_len}")
                continue
            if self.has_attention and worst > self.pool.usable_blocks:
                self.queue.pop(0)
                self._reject(req, f"worst-case footprint {worst} blocks exceeds "
                                  f"pool capacity {self.pool.usable_blocks}")
                continue
            slot = next((i for i, s in enumerate(self.slots) if s is None), None)
            if slot is None:
                break
            parked = self._parked.get(req.rid)
            need = self._admission_need(req, parked)
            if not self.pool.reserve(need):
                # pressure-relief ladder, mirroring _alloc_device: the prefix
                # registry is droppable cache, and OTHER parked requests'
                # stranded device blocks (shared at preemption, exclusive
                # since) can move to the host tier — without this, a drained
                # engine with a strand-blocked queue head would halt with
                # requests permanently queued
                self.store.evict_prefixes(need - self.pool.available())
                if not self.pool.reserve(need):
                    self._swap_parked_out(need - self.pool.available(),
                                          exclude_rid=req.rid)
                    if not self.pool.reserve(need):
                        break
            a = _Active(req=req, table=BlockTable(self.block_size),
                        reserved_left=need, admit_seq=self._admit_seq)
            if parked is not None:
                try:
                    with self._blame(req.rid):
                        self._restore(a, parked)
                except BaseException:
                    # failed restore: ``a`` was never slotted, so quarantine
                    # can't reach what it holds — release it here (req stays
                    # at queue[0] with its remaining parked blocks; the
                    # quarantine path drops those and fails the request)
                    a.table.release_to(self.store)
                    self.pool.release(a.reserved_left)
                    a.reserved_left = 0
                    if a.state is not None:
                        self.state_store.decref(a.state)
                        a.state = None
                    raise
            elif self.state_store is not None:
                # fresh stateful request: claim its slab slot now.  Exhaustion
                # is impossible by construction (slots == max_batch, and a
                # free batch slot implies a free slab slot) — a raise here is
                # an injected slab_alloc fault, and quarantine finds the
                # request still at queue[0] holding nothing
                try:
                    with self._blame(req.rid):
                        a.state = self.state_store.alloc()
                except BaseException:
                    self.pool.release(a.reserved_left)
                    a.reserved_left = 0
                    raise
            self.slots[slot] = a
            self._admit_seq += 1
            self.queue.pop(0)
            admitted += 1
        return admitted

    def _restore(self, a: _Active, parked: _Parked) -> None:
        """Re-admission of a preempted request: swap its parked blocks back
        onto the device and resume exactly where it stopped — this replaces
        the legacy restart-from-prompt.

        Crash-safe: blocks move out of ``parked.blocks`` only once fully
        restored, and a swap_in/alloc failure mid-restore undoes its own
        partial allocation before propagating — so a quarantine can release
        ``a.table`` plus the *remaining* parked blocks without double-frees.
        """
        if parked.state is not None:
            # the recurrent state comes back first: one slab slot, swapped in
            # whole.  A failure undoes its own allocation; quarantine then
            # drops ``parked`` (including the still-parked state block).
            dst = self.state_store.alloc()
            try:
                a.state = self.state_store.swap_in(parked.state, dst)
            except BaseException:
                self.state_store.decref(dst)
                raise
            parked.state = None
        while parked.blocks:
            b = parked.blocks[0]
            if b.tier == DEVICE:
                a.table.blocks.append(b)       # stayed resident (shared)
            else:
                dst = self.store.alloc(reserved=True)
                a.reserved_left -= 1
                try:
                    restored = self.store.swap_in(b, dst)
                except BaseException:
                    self.store.decref(dst)     # undo: dst never held data
                    self.pool.reserve(1)       # re-earmark the freed block
                    a.reserved_left += 1
                    raise
                a.table.blocks.append(restored)
            parked.blocks.pop(0)
        a.next_prefill = parked.next_prefill
        a.pos = parked.pos
        # the legacy path would have re-prefilled everything written so far
        self._re_prefill_avoided += parked.next_prefill
        del self._parked[a.req.rid]

    # -- block accounting --------------------------------------------------
    def _alloc_device(self, a: _Active) -> Optional[Block]:
        """One device block for ``a``: reservation first, then the open pool;
        under pressure evict prefix-cache entries, swap parked stragglers
        out, and finally preempt the youngest active request.  None means
        ``a`` itself was the youngest and got preempted."""
        while True:
            if a.reserved_left > 0:
                # alloc BEFORE decrementing: a crash inside alloc (injected
                # or real) must leave the slot ledger matching the pool's
                blk = self.store.alloc(reserved=True)
                a.reserved_left -= 1
                return blk
            try:
                return self.store.alloc()
            except PoolExhausted:
                if self.store.evict_prefixes(1) > 0:
                    continue
                if self._swap_parked_out(1) > 0:
                    continue
                # Evict the youngest active request — possibly ourselves.
                # Age-ordered eviction means the oldest request always makes
                # progress, so overcommit can't livelock into mutual
                # preemption ping-pong.
                victim = max((s for s in self.slots if s is not None),
                             key=lambda s: s.admit_seq)
                self._requeue(victim)
                if victim is a:
                    return None

    def _swap_parked_out(self, min_blocks: int,
                         exclude_rid: Optional[int] = None) -> int:
        """Parked requests can strand device blocks (blocks that were shared
        at preemption time and have since gone exclusive); push them to the
        host tier to relieve pool pressure.  ``exclude_rid`` protects the
        request currently being admitted — swapping its own resident blocks
        out would invalidate the admission need just computed for it."""
        freed = 0
        for rid, parked in self._parked.items():
            if rid == exclude_rid:
                continue
            for j, b in enumerate(parked.blocks):
                if (b.tier == DEVICE and not b.shared
                        and self.store.host.num_free > 0):
                    try:
                        parked.blocks[j] = self.store.swap_out(b)
                    except InjectedFault:
                        # swap faults at entry: the block is still intact on
                        # device — skip it, pressure relief just frees less
                        self._swap_failures += 1
                        continue
                    freed += 1
                    if freed >= min_blocks:
                        return freed
        return freed

    def _grow(self, a: _Active, n_tokens: int) -> bool:
        """Grow ``a``'s table to hold ``n_tokens`` positions; False if the
        pool ran dry and preemption evicted ``a`` itself (optimistic mode —
        conservative reservations make this infallible)."""
        if not self.has_attention:
            return True  # attention-free: no KV table to grow
        while a.table.capacity < n_tokens:
            blk = self._alloc_device(a)
            if blk is None:
                return False
            a.table.blocks.append(blk)
        return True

    def _make_writable(self, a: _Active, start: int, end: int) -> bool:
        """Privatize every shared block overlapping write positions
        [start, end) — copy-on-write: sharers (prefix registry, forked
        siblings) keep the original, ``a`` gets a device-side copy.  False if
        allocating a copy preempted ``a`` itself."""
        if not self.has_attention:
            return True
        bs = self.block_size
        for i in range(start // bs, min((end - 1) // bs + 1,
                                        len(a.table.blocks))):
            while a.table.blocks[i].shared:
                dst = self._alloc_device(a)
                if dst is None:
                    return False
                if not a.table.blocks[i].shared:
                    # eviction inside _alloc_device dropped the other holder;
                    # the block went exclusive under us — write in place
                    self.store.decref(dst)
                    break
                a.table.blocks[i] = self.store.cow_into(a.table.blocks[i], dst)
        return True

    def _requeue(self, victim: _Active) -> None:
        """Preempt ``victim`` back to the queue head.  With the host tier
        enabled (REPRO_KV_SWAP=1) its KV is parked there and restored on
        re-admission — generated tokens survive.  Otherwise (or when the host
        tier is full) fall back to the legacy drop: KV and generated tokens
        are discarded and the request restarts from its prompt."""
        self.pool.release(victim.reserved_left)
        victim.reserved_left = 0
        req = victim.req
        # attention families only park victims that actually hold KV: parking
        # an empty table would re-admit with a zero reservation (no
        # backpressure) and ping-pong straight back into preemption under
        # pool pressure.  Stateful families park whenever their slab state
        # can move — the state block IS the resumable footprint, even with an
        # empty (or absent) KV table.
        parked: Optional[List[Block]] = None
        state_parked: Optional[Block] = None
        holds = bool(victim.table.blocks) or (
            self.state_store is not None and victim.state is not None)
        can = self.swap_enabled and holds \
            and self.store.can_swap_out(victim.table.blocks)
        if can and self.state_store is not None:
            can = victim.state is not None \
                and self.state_store.can_swap_out([victim.state])
        if can:
            park_ok = True
            if self.state_store is not None:
                try:
                    state_parked = self.state_store.swap_out(victim.state)
                    victim.state = None
                except Exception as e:  # noqa: BLE001 — downgrade
                    self._swap_failures += 1
                    print(f"serve-engine: state swap_out failed parking "
                          f"request {req.rid} ({type(e).__name__}: {e}); "
                          "dropping its state (legacy restart)",
                          file=sys.stderr)
                    park_ok = False
            if park_ok:
                parked = []
                try:
                    for b in victim.table.blocks:
                        parked.append(self.store.swap_out(b))
                except Exception as e:  # noqa: BLE001 — downgrade, don't crash
                    # swap failed mid-park: degrade to the legacy drop.  Faults
                    # fire at swap_out entry, so the failing block is still a
                    # live device ref; release everything parked so far plus
                    # the untouched remainder and let the request restart from
                    # its prompt — token-identical by stateless-sampling
                    # replay.
                    self._swap_failures += 1
                    print(f"serve-engine: swap_out failed parking request "
                          f"{req.rid} ({type(e).__name__}: {e}); dropping its "
                          "KV (legacy restart)", file=sys.stderr)
                    for b in parked:
                        self.store.decref(b)
                    for b in victim.table.blocks[len(parked):]:
                        self.store.decref(b)
                    victim.table.blocks = []
                    parked = None
                    if state_parked is not None:
                        # already on the slab's host tier; the restart
                        # re-creates state from scratch, so just drop it
                        self.state_store.decref(state_parked)
                        state_parked = None
        if parked is not None:
            victim.table.blocks = []
            self._parked[req.rid] = _Parked(
                blocks=parked, next_prefill=victim.next_prefill,
                pos=victim.pos, state=state_parked)
        else:
            victim.table.release_to(self.store)
            if victim.state is not None:
                self.state_store.decref(victim.state)
                victim.state = None
            # counters report *delivered* work: back out the discarded tokens
            # so preemption churn can't inflate the CI-gated tokens/sec
            self._prefill_tokens -= victim.next_prefill
            self._decode_tokens -= max(len(req.out) - 1, 0)
            self._tenant_count(req, -len(req.out))  # replay re-emits them
            req.out.clear()
        self.queue.insert(0, req)
        self.slots[self.slots.index(victim)] = None
        self._preemptions += 1

    def _retire(self, a: _Active, now: Optional[float] = None) -> None:
        self._release_adapter(a.req)
        t = a.req.adapter_id or "base"
        self._tenant_finished[t] = self._tenant_finished.get(t, 0) + 1
        a.req.done = True
        a.req.t_done = time.monotonic() if now is None else now
        _trace_request(a.req)
        a.table.release_to(self.store)
        self.pool.release(a.reserved_left)
        a.reserved_left = 0
        if a.state is not None:
            self.state_store.decref(a.state)
            a.state = None
        self.finished.append(a.req)
        self.slots[self.slots.index(a)] = None
        if a.req.on_finish is not None:
            a.req.on_finish(a.req)

    # -- cancellation ------------------------------------------------------
    def _drop_parked(self, rid: int) -> None:
        parked = self._parked.pop(rid, None)
        if parked is not None:
            for b in parked.blocks:
                self.store.decref(b)
            if parked.state is not None:
                self.state_store.decref(parked.state)

    def _finish_cancel(self, req: Request) -> None:
        self._release_adapter(req)
        req.cancelled = True
        req.done = True
        req.t_done = time.monotonic()
        _trace_request(req)
        self.cancelled.append(req)
        if req.on_finish is not None:
            req.on_finish(req)

    def cancel(self, rid: int) -> bool:
        """Abort request ``rid`` wherever it currently lives — queued,
        occupying a batch slot, or parked on the host tier after preemption —
        and return every KV block it held to the pool the same call (a
        mid-stream client disconnect must free memory immediately, not when
        the generation would have finished).  Tokens already sampled stay in
        ``req.out``.  Returns False if the id is unknown or already done."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                self.queue.pop(i)
                # a preempted request sits in the queue AND holds parked KV
                self._drop_parked(rid)
                self._finish_cancel(req)
                return True
        for a in self.slots:
            if a is not None and a.req.rid == rid:
                self._release_active(a)
                self._finish_cancel(a.req)
                return True
        return False

    # -- fault tolerance ---------------------------------------------------
    @contextlib.contextmanager
    def _blame(self, rid: int):
        """Attribute any exception raised in the body to request ``rid``:
        the innermost attribution at raise time wins (captured in
        ``_crash_rid``, read by ``_on_step_crash`` after the stack unwinds).
        """
        prev = self._blame_rid
        self._blame_rid = rid
        try:
            yield
        except BaseException:
            if self._crash_rid is None:
                self._crash_rid = rid
            raise
        finally:
            self._blame_rid = prev

    def _release_active(self, a: _Active) -> None:
        """Free everything an active slot holds: table blocks back to the
        store, reservation back to the pool, slot emptied."""
        a.table.release_to(self.store)
        self.pool.release(a.reserved_left)
        a.reserved_left = 0
        if a.state is not None:
            self.state_store.decref(a.state)
            a.state = None
        self.slots[self.slots.index(a)] = None

    def _finish_expired(self, req: Request) -> None:
        self._release_adapter(req)
        req.expired = True
        req.done = True
        req.t_done = time.monotonic()
        _trace_request(req)
        self.expired.append(req)
        if req.on_finish is not None:
            req.on_finish(req)

    def _fail_request(self, req: Request, msg: str) -> None:
        """Terminal error state (quarantine outcome).  The on_finish hook is
        guarded: a raising hook is exactly the kind of poison quarantine
        exists to absorb, so it must not re-crash the recovery path."""
        self._release_adapter(req)
        req.errored = True
        req.error = msg
        req.done = True
        req.t_done = time.monotonic()
        _trace_request(req)
        self.errored.append(req)
        if req.on_finish is not None:
            try:
                req.on_finish(req)
            except Exception as e:  # noqa: BLE001
                print(f"serve-engine: on_finish hook raised for errored "
                      f"request {req.rid}: {type(e).__name__}: {e}",
                      file=sys.stderr)

    def _reap_deadlines(self) -> int:
        """Expire queued, parked, and active requests past their deadline
        cutoff, freeing every block and reservation they hold.  Runs at the
        top of ``step`` — before the crash-prone model dispatch — so
        deadlines keep draining a persistently-crashing engine."""
        now = time.monotonic()
        n = 0
        for req in [r for r in self.queue
                    if r._deadline_at and now > r._deadline_at]:
            self.queue.remove(req)
            self._drop_parked(req.rid)   # a preempted request queues parked
            self._finish_expired(req)
            n += 1
        for a in [s for s in self.slots
                  if s is not None and s.req._deadline_at
                  and now > s.req._deadline_at]:
            self._release_active(a)
            self._finish_expired(a.req)
            n += 1
        return n

    def _quarantine(self, rid: int, msg: str) -> bool:
        """Remove request ``rid`` from wherever it lives (queue, slot,
        parked) and fail it with ``finish_reason="error"``, releasing its
        device/host blocks and reservations."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                self.queue.pop(i)
                self._drop_parked(rid)
                self._fail_request(req, msg)
                return True
        for a in self.slots:
            if a is not None and a.req.rid == rid:
                self._release_active(a)
                self._fail_request(a.req, msg)
                return True
        parked = self._parked.pop(rid, None)
        if parked is not None:       # parked without a queue entry: cleanup
            for b in parked.blocks:
                self.store.decref(b)
            if parked.state is not None:
                self.state_store.decref(parked.state)
            return True
        return False

    def _on_step_crash(self, exc: BaseException) -> None:
        """Recovery after ``step()`` raised: quarantine the blamed request
        (or the youngest live one when the crash had no single owner — a
        batched decode dispatch), count consecutive crashes toward the
        degraded state, and assert the KV-leak invariants."""
        self._step_crashes += 1
        self._consecutive_crashes += 1
        if self._consecutive_crashes >= self.max_consecutive_crashes:
            self.degraded = True
        rid = self._crash_rid
        if rid is None:
            live = [s for s in self.slots if s is not None]
            if live:
                rid = max(live, key=lambda s: s.admit_seq).req.rid
        msg = f"engine step crashed: {type(exc).__name__}: {exc}"
        print(f"serve-engine: {msg} (crash {self._step_crashes}, "
              f"{self._consecutive_crashes} consecutive"
              + (f"; quarantining request {rid}" if rid is not None else
                 "; no request to blame")
              + (", engine DEGRADED" if self.degraded else "") + ")",
              file=sys.stderr)
        if rid is not None:
            self._quarantine(rid, msg)
        violations = self.check_invariants()
        if violations:
            self.invariant_violations.extend(violations)
            for v in violations:
                print(f"serve-engine: KV-LEAK INVARIANT VIOLATED: {v}",
                      file=sys.stderr)

    def step_guarded(self) -> bool:
        """``step()`` wrapped in crash isolation: an exception quarantines
        the request that poisoned the batch and the loop keeps going —
        this is what the async stepper thread drives.  Returns True after a
        crash (recovery IS work); a clean productive step resets the
        consecutive-crash counter and clears the degraded flag."""
        self._crash_rid = None
        try:
            worked = self.step()
        except Exception as e:  # noqa: BLE001 — isolate, quarantine, go on
            self._on_step_crash(e)
            return True
        if worked:
            self._consecutive_crashes = 0
            self.degraded = False
        return worked

    def overload_reason(self) -> str:
        """Why a new submit should be shed right now ("" = accept): the
        admission queue hit its bound, or — with REPRO_SERVE_SHED_PRESSURE
        set — the pool is pressure-saturated with a backlog already queued.
        The gateway turns a non-empty reason into HTTP 429 + Retry-After."""
        if self.max_queue and len(self.queue) >= self.max_queue:
            return (f"admission queue full "
                    f"({len(self.queue)} >= {self.max_queue})")
        if self.shed_pressure > 0 and self.queue:
            frac = (self.pool.usable_blocks - self.pool.available()) \
                / self.pool.usable_blocks
            if frac >= self.shed_pressure:
                return (f"block pool pressure {frac:.2f} >= "
                        f"{self.shed_pressure:g} with "
                        f"{len(self.queue)} queued")
        return ""

    def note_gateway_shed(self) -> None:
        """Count a request the gateway refused before submit (429)."""
        self._gateway_shed += 1

    def check_invariants(self) -> List[str]:
        """KV-leak invariants (see ``repro.serve.faults``): every allocated
        device/host block reachable from active+parked+prefix-registry with
        a consistent refcount, reservation ledgers in agreement.  Empty list
        = healthy."""
        return check_kv_invariants(self)

    # -- sampling ----------------------------------------------------------
    @staticmethod
    def _sample(logits_row: np.ndarray, sp: SamplingParams, n_emitted: int) -> int:
        """Gumbel-max sampling keyed on (seed, token index): stateless, so a
        preempted request replays the same draws on restart, and host-side,
        so the decode hot loop pays no per-token device dispatches."""
        if sp.temperature <= 0.0:
            return int(np.argmax(logits_row))
        x = logits_row.astype(np.float64) / sp.temperature
        if 0 < sp.top_k < x.size:
            kth = np.partition(x, -sp.top_k)[-sp.top_k]
            x = np.where(x < kth, -np.inf, x)
        rng = np.random.default_rng(
            np.random.SeedSequence([sp.seed & (2**63 - 1), n_emitted]))
        return int(np.argmax(x + rng.gumbel(size=x.size)))

    # -- prefill -----------------------------------------------------------
    def _adopt_prefix(self, a: _Active) -> None:
        """First prefill chunk of a fresh request: fork the longest
        registered prompt prefix instead of recomputing it.  Capped at
        ``plen - 1`` — the last prompt position must run through the model to
        produce the first sampled token's logits."""
        req = a.req
        plen, bs = len(req.prompt), self.block_size
        # prefixes are namespaced by tenant: identical prompts under
        # different adapters have different KV, so a cross-tenant hit would
        # serve one tenant's activations to another (isolation contract)
        n, blocks = self.store.match_prefix(req.prompt,
                                            namespace=req.adapter_id)
        n = min(n, plen - 1)
        if n <= 0:
            return
        a.table.blocks = self.store.fork(blocks[:blocks_for_tokens(n, bs)])
        # fully-shared blocks are mappings, not allocations: hand their
        # reservation slots back (the shared partial tail, if any, keeps its
        # slot — the copy-on-write before our first write consumes it)
        release = min(n // bs, a.reserved_left)
        if release:
            self.pool.release(release)
            a.reserved_left -= release
        a.next_prefill = n
        self._re_prefill_avoided += n

    def _prefill_step(self) -> bool:
        """Run ONE prompt chunk for the oldest admitting request.  Bounding
        prefill work per engine step is what keeps decode latency flat while
        long prompts trickle in."""
        pending = [s for s in self.slots if s is not None and not s.prefill_done]
        if not pending:
            return False
        a = min(pending, key=lambda s: s.admit_seq)
        with self._blame(a.req.rid):
            return self._prefill_chunk_for(a)

    def _prefill_chunk_for(self, a: _Active) -> bool:
        req, c = a.req, self.prefill_chunk_tokens
        plen = len(req.prompt)
        with tracing.span("prefill.prepare"):
            if a.next_prefill == 0 and not a.table.blocks:
                self._adopt_prefix(a)
            start = a.next_prefill
            # realign to the canonical chunk grid: an adopted (or restored)
            # prefix can leave ``start`` mid-chunk, and letting every offset
            # produce its own attended-span value would retrace the jitted
            # prefill per offset — the first chunk is shortened to the next
            # grid point instead, so m_used stays in the same small set every
            # request visits (the write limit masks the chunk's unused tail
            # positions)
            end = min(plen, start + c, (start // c + 1) * c)
            if not self._grow(a, end):
                return True  # preempted ourselves; the step still did work
            if not self._make_writable(a, start, end):
                return True
            chunk = req.prompt[start:end] + [0] * (c - (end - start))
            batch = {
                "tokens": jnp.asarray([chunk], jnp.int32),
                "block_table": jnp.asarray(
                    [a.table.padded(self.max_blocks_per_seq)], jnp.int32),
                "start": jnp.int32(start),
                "prompt_len": jnp.int32(end),
            }
            if a.state is not None:
                # traced slot index: one jit per cache shape, not per slot
                batch["state_slot"] = jnp.int32(a.state.idx)
            lora = self._lora_descriptor(
                np.asarray([a.req._adapter_slot], np.int32))
            if lora is not None:
                batch["lora"] = lora
            # attend only over blocks written so far, not the full table
            # capacity (attention-free prefill ignores the span — pin the
            # static arg to 0 so distinct chunk counts don't retrace the jit)
            m_used = min(blocks_for_tokens(end, self.block_size),
                         self.max_blocks_per_seq) if self.has_attention else 0
            if self.faults is not None:
                self.faults.check("step")
        if not req.t_prefill0:
            req.t_prefill0 = time.monotonic()
        with tracing.span("prefill.dispatch", rid=req.rid, start=start,
                          end=end, last=end >= plen):
            pool = self.cache
            self.cache, logits = self._prefill_fn(self.params, pool, batch,
                                                  m_used=m_used)
        _count_donation(pool)
        a.next_prefill = end
        self._prefill_tokens += end - start
        if a.prefill_done:
            a.pos = plen
            # retain the finished prompt for future sharers (the registry
            # holds its own refs; budget-bounded, LRU-evicted under pressure)
            self.store.register_prefix(
                req.prompt,
                a.table.blocks[:blocks_for_tokens(plen, self.block_size)],
                namespace=req.adapter_id)
            row = logits[0, plen - 1 - start]
            with tracing.span("prefill.device_wait"):
                row.block_until_ready()
            with tracing.span("prefill.logits_copy"):
                row = np.asarray(row)
            with tracing.span("prefill.sample"):
                first = self._sample(row, req.sampling, 0)
            req.out.append(first)
            self._tenant_count(req)
            req.t_first = time.monotonic()
            if req.on_token is not None:
                req.on_token(first, 0)
            if req.max_new <= 1:
                self._retire(a)
        return True

    # -- decode ------------------------------------------------------------
    def _decode_step(self) -> bool:
        """One batched decode step for every live (prefill-complete) slot."""
        with tracing.span("decode.prepare"):
            live = [s for s in self.slots
                    if s is not None and s.prefill_done]
            # make sure every live row can write its next KV entry — growing
            # the table AND privatizing a shared write target; under
            # optimistic admission either can preempt (an earlier row's
            # growth may evict a later row — or the row itself, when it is
            # the youngest)
            for a in live:
                if a in self.slots:
                    with self._blame(a.req.rid):
                        if self._grow(a, a.pos + 1):
                            self._make_writable(a, a.pos, a.pos + 1)
            live = [a for a in live if a in self.slots]
            if not live:
                return False

            m = self.max_blocks_per_seq
            tok = np.zeros((self.max_batch, 1), np.int32)
            tables = np.zeros((self.max_batch, m), np.int32)
            lens = np.zeros((self.max_batch,), np.int32)
            state_slots = np.zeros((self.max_batch,), np.int32)  # 0 = null
            adapter_ids = np.full((self.max_batch,), -1, np.int32)
            rows = []
            for a in live:
                i = self.slots.index(a)
                rows.append((i, a))
                tok[i, 0] = a.req.out[-1]
                tables[i] = a.table.padded(m)
                lens[i] = a.pos
                if a.state is not None:
                    state_slots[i] = a.state.idx
                adapter_ids[i] = a.req._adapter_slot
            batch = {"token": jnp.asarray(tok),
                     "block_tables": jnp.asarray(tables),
                     "seq_lens": jnp.asarray(lens)}
            if self.has_state:
                batch["state_slots"] = jnp.asarray(state_slots)
            lora = self._lora_descriptor(adapter_ids)
            if lora is not None:
                batch["lora"] = lora
            # the batched dispatch has no single owner: a crash here blames
            # no rid and _on_step_crash falls back to the youngest live
            # request
            if self.faults is not None:
                self.faults.check("step")
        with tracing.span("decode.dispatch") as sp:
            if sp:
                # each row attends over its written KV plus the new token
                sp.facts["spans"] = [a.pos + 1 for _, a in rows]
            pool = self.cache
            self.cache, logits = self._decode_fn(self.params, pool, batch)
        _count_donation(pool)
        with tracing.span("decode.device_wait"):
            logits.block_until_ready()
        with tracing.span("decode.logits_copy"):
            logits_np = np.asarray(logits)
        now = time.monotonic()
        with tracing.span("decode.sample"):
            picked = []
            for i, a in rows:
                with self._blame(a.req.rid):
                    picked.append(self._sample(logits_np[i], a.req.sampling,
                                               len(a.req.out)))
        with tracing.span("decode.emit"):
            for (_, a), nxt in zip(rows, picked):
                req = a.req
                with self._blame(req.rid):
                    req.out.append(nxt)
                    a.pos += 1
                    self._decode_tokens += 1
                    self._tenant_count(req)
                    if req.on_token is not None:
                        req.on_token(nxt, len(req.out) - 1)
                    if len(req.out) >= req.max_new or a.pos >= self.max_len:
                        self._retire(a, now=now)
        return True

    # -- engine loop -------------------------------------------------------
    def step(self) -> bool:
        """One engine iteration: reap deadlines, admit, one prefill chunk,
        one batched decode step.  Returns False when there is nothing left
        to do."""
        if self._t0 is None:
            self._t0 = time.monotonic()
        with tracing.span("engine.step"):
            with tracing.span("engine.reap"):
                worked = self._reap_deadlines() > 0
            with tracing.span("engine.admit"):
                worked = self._admit() > 0 or worked
            with tracing.span("engine.prefill"):
                worked = self._prefill_step() or worked
            with tracing.span("engine.decode"):
                worked = self._decode_step() or worked
        if worked:
            self.steps += 1
            self._t_last = time.monotonic()
        return worked

    def run_until_done(self, max_steps: int = 100_000) -> List[Request]:
        """Drive ``step`` until queue and slots drain (or ``max_steps``
        engine iterations pass); returns the finished requests in completion
        order.  Rejected requests are in ``self.rejected``, not here; a
        request preempted mid-run is restored (or restarted, see
        REPRO_KV_SWAP) and still finishes before this returns."""
        for _ in range(max_steps):
            if not self.step():
                break
        return list(self.finished)

    def release_prefix_cache(self) -> int:
        """Drop every retained prompt prefix, returning blocks freed —
        benchmarks and tests call this to drain the pool to zero."""
        return self.store.drop_prefixes()

    def reset_metrics(self) -> None:
        """Zero the run counters (benchmarks warm the jit caches with a
        throwaway workload first, then measure a clean window).  Requests
        already finished are dropped from the ledger — callers keep their own
        references."""
        assert all(s is None for s in self.slots) and not self.queue, \
            "reset_metrics with requests in flight"
        self.steps = 0
        self._t0 = None
        self._t_last = 0.0
        self._submitted = 0
        self._prefill_tokens = 0
        self._decode_tokens = 0
        self._preemptions = 0
        self._re_prefill_avoided = 0
        self._tenant_tokens = {}
        self._tenant_finished = {}
        self.store.reset_counters()
        if self.state_store is not None:
            self.state_store.reset_counters()
        self.finished = []
        self.rejected = []
        self.cancelled = []
        self.expired = []
        self.errored = []
        self.shed = []
        self._step_crashes = 0
        self._consecutive_crashes = 0
        self._swap_failures = 0
        self._gateway_shed = 0
        self.degraded = False
        self.invariant_violations = []
        self.pool.peak_used = self.pool.num_used

    # -- metrics -----------------------------------------------------------
    def metrics(self) -> ServeMetrics:
        wall = max(self._t_last - self._t0, 1e-9) if self._t0 else 0.0
        fin = self.finished
        ttfts = [r.t_first - r.t_submit for r in fin if r.t_first > 0]
        itl_num = sum(r.t_done - r.t_first for r in fin if len(r.out) > 1)
        itl_den = sum(len(r.out) - 1 for r in fin if len(r.out) > 1)
        am = self.adapters.metrics()
        tenants = sorted(set(self._tenant_tokens) | set(self._tenant_finished))
        return ServeMetrics(
            wall_s=wall,
            requests_submitted=self._submitted,
            requests_finished=len(fin),
            requests_rejected=len(self.rejected),
            prefill_tokens=self._prefill_tokens,
            decode_tokens=self._decode_tokens,
            engine_steps=self.steps,
            tokens_per_sec=self._decode_tokens / wall if wall else 0.0,
            ttft_mean_s=float(np.mean(ttfts)) if ttfts else 0.0,
            ttft_max_s=float(np.max(ttfts)) if ttfts else 0.0,
            itl_mean_s=itl_num / itl_den if itl_den else 0.0,
            peak_blocks_used=self.pool.peak_used,
            pool_blocks=self.pool.usable_blocks,
            block_size=self.block_size,
            peak_pool_utilization=self.pool.peak_used / self.pool.usable_blocks,
            dense_equiv_blocks=dense_equiv_blocks(self.max_batch, self.max_len,
                                                  self.block_size),
            preemptions=self._preemptions,
            shared_blocks=self.store.shared_blocks,
            cow_copies=self.store.cow_copies,
            # the state slab is the degenerate one-block pool: its swaps are
            # the same tier movement, folded into the same counters
            swap_out_blocks=self.store.swapped_out
            + (self.state_store.swapped_out if self.state_store else 0),
            swap_in_blocks=self.store.swapped_in
            + (self.state_store.swapped_in if self.state_store else 0),
            re_prefill_avoided=self._re_prefill_avoided,
            requests_expired=len(self.expired),
            requests_shed=len(self.shed) + self._gateway_shed,
            requests_errored=len(self.errored),
            step_crashes=self._step_crashes,
            swap_failures=self._swap_failures,
            degraded=self.degraded,
            mesh_devices=int(self.mesh.shape.get("model", 1))
            if self.mesh is not None else 1,
            tp_devices=int(self.mesh.shape.get("model", 1))
            if self.tp and self.mesh is not None else 1,
            param_bytes_per_device=self.param_bytes_per_device,
            param_bytes_replicated=self.param_bytes_replicated,
            adapters_loaded=am["adapters_loaded"],
            adapter_loads=am["adapter_loads"],
            adapter_evictions=am["adapter_evictions"],
            adapter_host_reloads=am["adapter_host_reloads"],
            adapter_device_bytes=am["adapter_device_bytes"],
            adapter_host_bytes=am["adapter_host_bytes"],
            per_tenant={
                t: {"tokens": self._tenant_tokens.get(t, 0),
                    "requests_finished": self._tenant_finished.get(t, 0)}
                for t in tenants},
        )
