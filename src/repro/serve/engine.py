"""Slot-native serving engine: paged block-pool KV cache, batched
prefill admission, and mixed-length continuous-batching decode for one
model (the substrate under every PaaS replica when the payload is an LM).

The engine slots requests into a fixed-capacity batch (one slot per
sequence). KV memory comes in two layouts:

* **Paged (default for pure-attention caches, leaves ``{k, v}``)** — a
  shared :class:`~repro.serve.blocks.BlockPool` of ``num_blocks x
  block_size`` tokens per layer. A slot holds only the blocks its
  sequence actually needs (``ceil(len / block_size)``), mapped through a
  per-slot block table; admission is gated on *blocks*, not on a free
  ``max_seq`` stripe, so many short requests fit where few stripes did.
  Decode grows a slot's table lazily as it crosses block boundaries;
  on exhaustion the slot **parks** (skips token emission, state intact)
  until another request frees blocks — and if every active slot is
  parked, the newest admission is **preempted** (blocks freed, request
  re-queued for recompute re-admission) so the oldest can advance.
* **Fixed-stripe (recurrent rwkv / hybrid-SSM / cross-attn caches)** —
  one ``max_seq`` stripe per slot at ``model.init_cache(B, max_seq)``.
  Recurrent state is O(1) in sequence length, so paging buys nothing
  there; the stripe path is also the reference the paged path must
  match token-for-token.

Paged engines add two behaviours on top of the block tables:

* **Prefix sharing + copy-on-write** (``prefix_sharing=True``, non-MoE):
  admission walks the prompt through the pool's prefix index and
  *acquires* blocks already holding that content instead of recomputing
  and re-storing them — the request prefills only its un-shared suffix
  (fed through ordinary decode steps), and the scheduler's block gate
  charges only that post-sharing cost. A shared block is read-only;
  the first append into a shared tail duplicates it on device first
  (copy-on-write), so no holder ever sees another's tokens.
* **In-place kernel decode** (``use_kernel=True``): the paged attention
  read runs the Pallas kernel in ``kernels/paged_attention`` (K/V read
  through the block table via scalar-prefetched index maps, no
  transient gather; interpret mode off-TPU) instead of the jnp gather
  reference.

With ``speculation=k`` (and a draft model) the engine decodes
**speculatively**: each step, a :class:`~repro.serve.spec.DraftRunner`
proposes k tokens per slot and the target verifies them in ONE
multi-token step (``model.verify_step``), committing the accepted
prefix plus a bonus/correction token — up to k+1 tokens per slot per
target step. Paged slots are granted their window blocks up front (the
**watermark**; copy-on-write where shared, degraded under pressure)
and rolled back to the committed length afterwards; greedy acceptance
is deterministic and the streams are bit-identical to non-speculative
decode (docs/serving.md, "Speculative decode"). Every emitted token is
drawn by the per-request sampler (``serve/sampling.py``: greedy /
temperature / top-k, counter-based keys) and streams with its logprob.

Three properties carry over from the stripe engine and hold in both
layouts:

* **Device-side admission** — prefill writes the new sequence's KV into
  its slot (stripe) or its blocks (pool) with jitted
  ``jax.lax.dynamic_update_slice`` (cache buffers donated); the full
  cache never round-trips through host numpy. Several waiting requests
  prefill as one batch.
* **Mixed-length decode** — every slot keeps its own length; one decode
  step ropes, writes, and masks each row at its own position, so slots
  at different depths decode together bit-exactly for dense/recurrent
  families. MoE is the one caveat (capacity routing shares per-expert
  budget across co-batched rows — see docs/serving.md, "The MoE
  caveat"), and the reason MoE admission prefills one row at a time.
* **Slot recycling mid-flight** — EOS/stop-token early exit frees a slot
  (and its blocks) the moment its request finishes; the next waiting
  request is admitted into it while the other slots keep decoding.

Prompts for paddable caches are right-padded to power-of-two buckets so
admission compiles O(B x log max_seq) variants, not one per prompt
length; pad positions are never attended (per-slot length masks them)
and pad tail blocks are never allocated — a paged slot pays blocks for
its *real* tokens only.

**Chunked prefill** (``prefill_chunk``, default on for paddable
families): a prompt longer than the chunk admits with its FIRST chunk
only; the remainder becomes the slot's pending queue and feeds through
**chunk windows** — multi-token steps (the verify machinery) that write
each row's next ``<= chunk`` prompt tokens at its own positions while
every decode slot rides the same batch with its single next token. A
long prompt therefore admits as a sequence of budgeted chunk steps
interleaved with decode instead of one monolithic stall — the
head-of-line blocking fix the paper's sub-700ms responsiveness claim
needs under sequential long-document arrival. The same queue drains a
shared admission's un-shared suffix chunk-at-a-time, which removes the
old bounded-suffix trade on prefix sharing (the suffix used to feed one
token per step, so only short suffixes could share); chunk-written
prompt blocks register in the prefix index exactly as prefilled ones
do, so half-prefilled prompts share forward too. Chunked streams are
bit-identical to monolithic prefill (``tests/test_chunked.py`` holds
the whole engine grid to it). ``prefill_chunk=0`` restores monolithic
admission; recurrent and MoE families always prefill monolithically
(multi-token windows need the ``{k, v}`` scatter and bit-exact
co-batching).
"""
from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import sampling
from repro.serve.blocks import BlockPool
from repro.serve.sampling import GREEDY, SamplingParams
from repro.serve.spec import DraftRunner
from repro.serve.telemetry import NOOP, PID_LOOP, PID_POOL, PID_REQUESTS

_MIN_BUCKET = 8
# default chunk for chunked prefill (tokens per slot per chunk step):
# small enough that a max_seq-sized prompt never stalls decode for more
# than one chunk's compute, large enough that short prompts (the common
# case) still admit in one piece exactly as before
DEFAULT_PREFILL_CHUNK = 64


@dataclass
class Request:
    rid: int
    prompt: list                    # token ids
    max_new_tokens: int = 8
    stop_tokens: tuple = ()         # EOS ids -> early exit
    priority: int = 0               # scheduler tier (higher = more urgent)
    deadline_s: float | None = None  # absolute perf_counter SLO deadline
    sampling: SamplingParams = GREEDY   # greedy | temperature | top-k
    speculation: int | None = None  # draft tokens/step; None = engine
    #                                 default, 0 = opt out of speculation
    prefill_chunk: int | None = None  # per-request chunk width override
    #                                 (None = engine default)
    out_tokens: list = field(default_factory=list)
    out_logprobs: list = field(default_factory=list)  # raw log-softmax of
    #                                 each emitted token, 1:1 with out_tokens
    submitted_s: float = field(default_factory=time.perf_counter)
    done_s: float | None = None
    preemptions: int = 0            # times evicted for recompute readmission
    admitted_s: float | None = None     # first engine-slot admission
    first_token_s: float | None = None  # first *generated* token commit
    #                                 (TTFT = first_token_s - submitted_s)

    @property
    def latency_s(self) -> float:
        return (self.done_s or time.perf_counter()) - self.submitted_s

    @property
    def finished_by_stop(self) -> bool:
        return bool(self.out_tokens) and self.out_tokens[-1] in self.stop_tokens


def _bucket(n: int, cap: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    return min(b, cap)


class _Tick:
    """One **dispatched** engine step: the device work is already in
    flight (JAX async dispatch returns before the computation finishes),
    the host-side bookkeeping is deferred to :meth:`commit`. Between
    ``dispatch_step()`` and ``commit()`` the engine's host state must be
    treated as read-only — that window is exactly where the async serve
    loop overlaps next-tick planning (admission cost walks, intake
    validation) with the device step. Commit is one-shot."""

    __slots__ = ("_commit",)

    def __init__(self, commit_fn):
        self._commit = commit_fn

    def commit(self) -> list:
        """Synchronize on the device results, run the per-slot
        bookkeeping, and return the finished requests."""
        fn, self._commit = self._commit, None
        if fn is None:
            raise RuntimeError("tick already committed")
        return fn()


# ------------------------------------------------------ paged programs
def paged_admit_step(model, plan, block_size, p, tokens, last_idx, temps,
                     top_ks, seeds, ctrs):
    """Batched prefill for the pool path: returns the first token per
    row (+ logprob) and the prefill KV padded (with zeros, never
    attended) to a block_size multiple so every logical block slices
    full."""
    logits, pref = model.prefill(p, {"tokens": tokens}, plan,
                                 last_idx=last_idx)
    pad = (-tokens.shape[1]) % block_size
    if pad:
        pref = {key: jnp.pad(pref[key],
                             ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                for key in pref}
    nxt, logp = sampling.sample(logits[:, -1, :], temps, top_ks, seeds,
                                ctrs)
    return nxt, logp, pref


def paged_decode_step(model, plan, kernel, p, tok, caches, lengths, table,
                      temps, top_ks, seeds, ctrs):
    logits, caches = model.decode_step(p, tok, caches, lengths, plan,
                                       block_table=table,
                                       paged_kernel=kernel)
    nxt, logp = sampling.sample(logits[:, -1, :], temps, top_ks, seeds,
                                ctrs)
    return nxt, logp, caches


def paged_chunk_step(model, plan, kernel, p, toks, caches, lengths, table,
                     n_write, last_idx, temps, top_ks, seeds, ctrs):
    """Paged chunk window: scatter through the block table, diverted to
    scratch past each row's fed count (pads, parked riders)."""
    logits, caches = model.prefill(p, {"tokens": toks}, plan,
                                   cache=caches, cache_len=lengths,
                                   block_table=table, paged_kernel=kernel,
                                   n_write=n_write, last_idx=last_idx)
    nxt, logp = sampling.sample(logits[:, 0, :], temps, top_ks, seeds,
                                ctrs)
    return nxt, logp, caches


def paged_programs(model, *, block_size: int, use_kernel: bool,
                   plan=None) -> dict:
    """The paged engine's three serving programs, jitted: ``admit``
    (batched prefill of first chunks), ``decode`` (one token per slot)
    and ``chunk`` (a multi-token chunk window). Decode and chunk donate
    the pool. One definition serves the engine, its ahead-of-time
    compile (:meth:`ServingEngine.compile_programs`) and the compile-only
    tests."""
    def bind(fn, *static):
        # keep the step's own name on the jitted program (traces, HLO)
        return functools.update_wrapper(functools.partial(fn, model, plan,
                                                          *static), fn)
    kernel = bool(use_kernel)
    return {
        "admit": jax.jit(bind(paged_admit_step, block_size)),
        "decode": jax.jit(bind(paged_decode_step, kernel),
                          donate_argnums=(2,)),
        "chunk": jax.jit(bind(paged_chunk_step, kernel),
                         donate_argnums=(2,)),
    }


def paged_program_args(params, caches, *, batch: int, blocks_per_slot: int,
                       width: int, sharding=None) -> dict:
    """Arguments for :func:`paged_programs` at one serving shape:
    ``batch`` slots, ``width``-token chunk windows, and an admission of
    ``batch`` first chunks ``width`` wide. ``params`` and ``caches`` pass
    through (arrays or shape stand-ins); the rest are
    ``ShapeDtypeStruct`` stand-ins on ``sharding``."""
    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    rows = sds((batch,))
    samp = (sds((batch,), jnp.float32), rows, rows, rows)
    table = sds((batch, blocks_per_slot))
    return {
        "admit": (params, sds((batch, width)), rows, *samp),
        "decode": (params, sds((batch, 1)), caches, rows, table, *samp),
        "chunk": (params, sds((batch, width)), caches, rows, table, rows,
                  rows, *samp),
    }


class ServingEngine:
    def __init__(self, model, params, *, batch_size: int = 4,
                 max_seq: int = 256, plan=None, paged: bool | None = None,
                 block_size: int = 16, num_blocks: int | None = None,
                 reserve_blocks: int = 1, prefix_sharing: bool = True,
                 use_kernel: bool = False, draft_model=None,
                 draft_params=None, speculation: int = 0,
                 prefill_chunk: int | None = None,
                 prefill_budget: int | None = None,
                 clock=time.perf_counter, tracer=None):
        self.model = model
        self.params = params
        self.B = batch_size
        self.max_seq = max_seq
        self.plan = plan
        # injectable time source (completion stamps); a VirtualClock
        # here makes every latency/deadline observable deterministic
        self.clock = clock
        # span/event recorder (serve/telemetry.py). The NOOP default
        # keeps the hot path flat, and every emission site additionally
        # guards on ``.enabled`` so an untraced engine never builds
        # event payloads. Pass a Tracer sharing this clock for traces
        # on the same timeline as the latency stamps.
        self.tracer = NOOP if tracer is None else tracer
        cache_spec = jax.eval_shape(lambda: model.init_cache(1, _MIN_BUCKET))
        pure_attn = set(cache_spec) <= {"k", "v"}
        # MoE routing flattens the whole (rows x tokens) block into one
        # shared per-expert capacity, so pad tokens / co-batched rows can
        # displace real tokens from dispatch — prefill those one row at a
        # time, exact length, to keep admission bit-exact with solo serving.
        is_moe = bool(getattr(model.cfg, "n_experts", 0))
        # pure-attention caches tolerate right-padded prompts (pad KV is
        # masked, then overwritten); recurrent state does not.
        self._paddable = pure_attn and not is_moe
        self._solo_prefill = is_moe
        # recurrent / cross-attn state is O(1) in sequence length: paging
        # buys nothing, keep the stripe layout there.
        self.paged = pure_attn if paged is None else paged
        if self.paged and not pure_attn:
            raise ValueError("paged KV requires a pure-attention {k, v} "
                             f"cache; got leaves {sorted(cache_spec)}")
        # prefix sharing rides on the block tables; the catch-up tokens of
        # a shared admission decode co-batched, which is bit-exact for
        # dense/GQA but not for MoE (the shared expert-capacity caveat
        # again) — so MoE engines never share.
        self.prefix_sharing = bool(prefix_sharing) and self.paged \
            and not is_moe
        self.use_kernel = bool(use_kernel)
        # chunked prefill: prompts longer than the chunk admit with their
        # first chunk and feed the rest through decode-interleaved chunk
        # windows. Needs the multi-token {k, v} window (recurrent state
        # steps token-at-a-time) and bit-exact co-batching (the MoE
        # shared-capacity caveat), so only paddable families chunk;
        # 0 = monolithic admission (the legacy comparison mode).
        if prefill_chunk is not None and prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got "
                             f"{prefill_chunk}")
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError(f"prefill_budget must be >= 1, got "
                             f"{prefill_budget}")
        if self._paddable:
            self.prefill_chunk = DEFAULT_PREFILL_CHUNK \
                if prefill_chunk is None else int(prefill_chunk)
        else:
            if prefill_chunk:
                raise ValueError("chunked prefill requires a paddable "
                                 "pure-attention non-MoE cache")
            self.prefill_chunk = 0
        # per-step cap on pending prompt tokens fed across slots (the
        # scheduler charges the same budget before admitting new work)
        self.prefill_budget = prefill_budget
        # speculative draft-and-verify: a small draft model proposes k
        # tokens per slot, the target verifies them in one multi-token
        # step. Pure-attention targets only (the verify window needs the
        # {k, v} scatter; recurrent state steps token-at-a-time) and
        # never MoE (the window co-batches k+1 tokens through shared
        # expert capacity — the standard bit-exactness caveat).
        self.spec_k = int(speculation)
        if self.spec_k:
            if draft_model is None or draft_params is None:
                raise ValueError("speculation requires a draft model")
            if not pure_attn:
                raise ValueError("speculation requires a pure-attention "
                                 f"{{k, v}} cache; got {sorted(cache_spec)}")
            if is_moe:
                raise ValueError("speculation unsupported for MoE targets "
                                 "(expert-capacity caveat, docs/serving.md)")
            self.draft = DraftRunner(draft_model, draft_params,
                                     batch_size=batch_size, max_seq=max_seq,
                                     plan=plan, tracer=self.tracer)
        else:
            self.draft = None
        self.slot_len = np.zeros(batch_size, np.int32)   # tokens in cache
        self.slot_req: list = [None] * batch_size
        # prompt tokens a shared admission still owes the model: fed one
        # per decode step (writing K/V at the slot's own position) until
        # the last prompt token's logits produce the first output token
        self.slot_pending: list = [[] for _ in range(batch_size)]
        # prefix-index registration frontier per slot, for chunk-written
        # prompt blocks: slot_reg is the canonical parent block the next
        # registration chains after (pool.ROOT for a fresh chain, False
        # when the chain is broken and registration stops), slot_reg_pos
        # the prompt position indexed so far
        self.slot_reg: list = [False] * batch_size
        self.slot_reg_pos = np.zeros(batch_size, np.int64)
        self._finished_at_admit: list = []
        self._used_slots: set = set()
        self._waiting: deque = deque()       # preempted, awaiting re-admission
        self._admit_order = np.zeros(batch_size, np.int64)
        self._admit_seq = 0

        if self.paged:
            self.block_size = block_size
            self.blocks_per_slot = -(-max_seq // block_size)
            if num_blocks is None:
                # parity default: same token capacity as B fixed stripes
                num_blocks = batch_size * self.blocks_per_slot + 1  # + scratch
            self.pool = BlockPool(num_blocks, block_size,
                                  tracer=self.tracer)
            self.reserve_blocks = min(reserve_blocks, max(self.pool.total - 1,
                                                          0))
            self.caches = model.init_paged_cache(num_blocks, block_size)
            self.block_table = np.zeros((batch_size, self.blocks_per_slot),
                                        np.int32)
            self.slot_blocks: list = [[] for _ in range(batch_size)]
        else:
            self.pool = None
            self.caches = model.init_cache(batch_size, max_seq)

        def admit(p, caches, tokens, last_idx, slots, temps, top_ks,
                  seeds, ctrs):
            """Batched prefill + device-side stripe insertion.

            tokens (k, S) right-padded prompts, last_idx (k,) index of each
            row's final real token, slots (k,) destination slot per row;
            temps/top_ks/seeds/ctrs (k,) per-row sampling params. Returns
            (first generated token per row, its logprob, updated caches).
            """
            logits, pref = model.prefill(p, {"tokens": tokens}, plan,
                                         last_idx=last_idx)
            for j in range(tokens.shape[0]):
                for key in caches:
                    row = jax.lax.dynamic_slice_in_dim(pref[key], j, 1, axis=1)
                    start = (jnp.int32(0), slots[j]) + \
                        (jnp.int32(0),) * (row.ndim - 2)
                    caches[key] = jax.lax.dynamic_update_slice(
                        caches[key], row.astype(caches[key].dtype), start)
            nxt, logp = sampling.sample(logits[:, -1, :], temps, top_ks,
                                        seeds, ctrs)
            return nxt, logp, caches

        def write_block(caches, pref, row, start, phys):
            """Copy one logical block of row ``row`` of the prefill KV
            (token window [start, start+block_size)) into physical pool
            block ``phys`` — a device-side dynamic_update_slice on the
            donated pool, same no-host-copy property as the stripe path.
            The prefill KV is token-major (L, rows, S, Hkv, hd); the
            pool is head-major (L, num_blocks, Hkv, bs, hd)."""
            for key in caches:
                L = pref[key].shape[0]
                chunk = jax.lax.dynamic_slice(
                    pref[key], (jnp.int32(0), row, start, jnp.int32(0),
                                jnp.int32(0)),
                    (L, 1, block_size) + pref[key].shape[3:])
                chunk = jnp.swapaxes(chunk, 2, 3)
                caches[key] = jax.lax.dynamic_update_slice(
                    caches[key], chunk.astype(caches[key].dtype),
                    (jnp.int32(0), phys) + (jnp.int32(0),) * 3)
            return caches

        def decode(p, tok, caches, lengths, temps, top_ks, seeds, ctrs):
            logits, caches = model.decode_step(p, tok, caches, lengths, plan)
            nxt, logp = sampling.sample(logits[:, -1, :], temps, top_ks,
                                        seeds, ctrs)
            return nxt, logp, caches

        kernel_flag = self.use_kernel

        def verify(p, toks, caches, lengths, dprobs, proposed, n_spec,
                   temps, top_ks, seeds, ctrs):
            """Stripe verify: one multi-token step + acceptance."""
            logits, caches = model.verify_step(p, toks, caches, lengths,
                                               plan)
            acc = sampling.speculative_accept(logits, dprobs, proposed,
                                              n_spec, temps, top_ks, seeds,
                                              ctrs)
            return (*acc, caches)

        def verify_paged(p, toks, caches, lengths, table, n_write, dprobs,
                         proposed, n_spec, temps, top_ks, seeds, ctrs):
            """Paged verify: the window scatters through the block table
            (diverted to scratch past each row's granted watermark)."""
            logits, caches = model.verify_step(p, toks, caches, lengths,
                                               plan, block_table=table,
                                               paged_kernel=kernel_flag,
                                               n_write=n_write)
            acc = sampling.speculative_accept(logits, dprobs, proposed,
                                              n_spec, temps, top_ks, seeds,
                                              ctrs)
            return (*acc, caches)

        def chunk(p, toks, caches, lengths, last_idx, temps, top_ks,
                  seeds, ctrs):
            """Stripe chunk window: each row feeds its next pending
            prompt tokens (decode riders their single next token, pads
            past each row's count) through one multi-token window, and
            samples at its own last real position (``last_idx`` — the
            model projects only that position against the vocabulary);
            the draw only counts for rows that finished their prompt
            this window."""
            logits, caches = model.prefill(p, {"tokens": toks}, plan,
                                           cache=caches, cache_len=lengths,
                                           last_idx=last_idx)
            nxt, logp = sampling.sample(logits[:, 0, :], temps, top_ks,
                                        seeds, ctrs)
            return nxt, logp, caches

        def copy_block(caches, src, dst):
            """Copy-on-write: duplicate physical block ``src`` into the
            freshly-allocated ``dst`` on device (all layers, one jitted
            dynamic_update_slice per leaf, pool donated)."""
            for key in caches:
                nd = caches[key].ndim
                sizes = (caches[key].shape[0], 1) + caches[key].shape[2:]
                blk = jax.lax.dynamic_slice(
                    caches[key], (jnp.int32(0), src) + (jnp.int32(0),)
                    * (nd - 2), sizes)
                caches[key] = jax.lax.dynamic_update_slice(
                    caches[key], blk,
                    (jnp.int32(0), dst) + (jnp.int32(0),) * (nd - 2))
            return caches

        self._admit = jax.jit(admit, donate_argnums=(1,))
        self._write_block = jax.jit(write_block, donate_argnums=(0,))
        self._copy_block = jax.jit(copy_block, donate_argnums=(0,))
        self._verify = jax.jit(verify_paged if self.paged else verify,
                               donate_argnums=(2,))
        if self.paged:
            progs = paged_programs(model, plan=plan, block_size=block_size,
                                   use_kernel=self.use_kernel)
            self._prefill_paged = progs["admit"]
            self._decode = progs["decode"]
            self._chunk_fn = progs["chunk"]
        else:
            self._decode = jax.jit(decode, donate_argnums=(2,))
            self._chunk_fn = jax.jit(chunk, donate_argnums=(2,))
        self.metrics = {"prefills": 0, "prefill_batches": 0,
                        "decode_steps": 0, "completed": 0,
                        "stop_token_exits": 0, "slot_reuses": 0,
                        "blocks_grown": 0, "parked_slot_steps": 0,
                        "preemptions": 0, "shared_admissions": 0,
                        "cow_copies": 0, "cow_parks": 0,
                        "prefill_tokens_computed": 0,
                        "prefill_tokens_shared": 0,
                        "verify_steps": 0, "draft_steps": 0,
                        "spec_proposed": 0, "spec_accepted": 0,
                        "spec_blocks_rolled_back": 0,
                        "chunked_admissions": 0, "chunk_steps": 0,
                        "chunk_prefill_tokens": 0, "cancelled": 0,
                        # Pallas paged-attention dispatch accounting
                        # (use_kernel=True only): fused multi-token
                        # window launches (verify + chunk) vs the total
                        # real query positions fed through the kernel
                        # (1 per active row on a plain decode tick) —
                        # Prometheus tells fused-window from
                        # single-token launches by these two series
                        "kernel_windows": 0, "kernel_positions": 0}

    def compile_programs(self) -> dict:
        """Compile the paged serving programs ahead of time at this
        engine's shapes: decode, a full-width chunk window, and an
        admission of ``batch_size`` first chunks. Later calls at these
        shapes reuse the executables. Returns ``{name: compiled}``, whose
        ``memory_analysis()`` and ``as_text()`` the launcher and the chip
        smoke read."""
        if not self.paged:
            raise ValueError("compile_programs covers the paged programs")
        width = _bucket(self.prefill_chunk or self.max_seq, self.max_seq)
        args = paged_program_args(self.params, self.caches, batch=self.B,
                                  blocks_per_slot=self.blocks_per_slot,
                                  width=width)
        progs = {"admit": self._prefill_paged, "decode": self._decode,
                 "chunk": self._chunk_fn}
        return {name: fn.lower(*args[name]).compile()
                for name, fn in progs.items()}

    # ---------------------------------------------------------- telemetry
    def _trace_admit(self, req: Request, slot: int, *,
                     shared: bool = False, chunked: bool = False) -> None:
        """Stamp the admission (first one only: a preempted request's
        re-admission keeps the original, so its prefill span covers the
        recompute) and mark it on the request's trace track."""
        if req.admitted_s is None:
            req.admitted_s = self.clock()
        if self.tracer.enabled:
            self.tracer.instant(
                "admitted", pid=PID_REQUESTS, tid=req.rid,
                args={"slot": slot, "shared": shared, "chunked": chunked,
                      "readmission": req.preemptions > 0})

    def _note_first_token(self, req: Request) -> None:
        """Stamp the request's first *generated* token the moment it
        commits — TTFT is ``first_token_s - submitted_s``, the value the
        trace's first-token instant must reconstruct exactly."""
        if req.first_token_s is not None:
            return
        req.first_token_s = self.clock()
        if self.tracer.enabled:
            self.tracer.instant("first_token", pid=PID_REQUESTS,
                                tid=req.rid, ts=req.first_token_s)

    def _trace_retire(self, req: Request, status: str) -> None:
        """Render the finished request's lifecycle as spans on its trace
        track: the whole-request span plus prefill (admitted -> first
        token) and decode (first token -> done) phases where they
        happened. Emitted at retire time from the request's own stamps,
        so the spans agree with the engine's reported latencies by
        construction."""
        tr = self.tracer
        tr.complete("request", req.submitted_s,
                    req.done_s - req.submitted_s, pid=PID_REQUESTS,
                    tid=req.rid,
                    args={"status": status, "tokens": len(req.out_tokens),
                          "preemptions": req.preemptions})
        if req.first_token_s is None:
            return
        if req.admitted_s is not None:
            tr.complete("prefill", req.admitted_s,
                        req.first_token_s - req.admitted_s,
                        pid=PID_REQUESTS, tid=req.rid)
        tr.complete("decode", req.first_token_s,
                    req.done_s - req.first_token_s,
                    pid=PID_REQUESTS, tid=req.rid)

    # ------------------------------------------------------------- slots
    def free_slots(self) -> list:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _free_slot(self) -> int | None:
        free = self.free_slots()
        return free[0] if free else None

    @property
    def active(self) -> int:
        return self.B - len(self.free_slots())

    @property
    def waiting(self) -> int:
        """Preempted requests parked off-device, pending re-admission."""
        return len(self._waiting)

    def load(self) -> int:
        """Occupied slots + preempted backlog — least-loaded balancing."""
        return self.active + len(self._waiting)

    # --------------------------------------------------------- pool probes
    @staticmethod
    def _eff_prompt(req: Request) -> list:
        """The tokens a (re-)admission must prefill: the prompt plus any
        tokens already generated before a preemption evicted the slot."""
        return req.prompt + req.out_tokens

    def _match_cost(self, eff: list, chunk: int):
        """Resident-or-cached prefix match for ``eff`` and the admission
        cost with it: ``(blocks, matched, need)``. ``need`` counts the
        un-shared blocks, plus one per **cached** matched block (a freed
        block whose index entry survived — reviving it consumes a free
        block, so memory-wise it costs like an allocation even though
        its prefill compute is free), plus ONE extra when the match ends
        inside a *resident* partial tail block — the first append must
        copy-on-write that block, so the gate has to charge the copy up
        front or a batch of tail-sharing admissions would all park on
        their first decode step. (A cached tail revives sole-owned:
        writable in place, no copy.)

        ``chunk`` is the request's chunk width. With chunked prefill
        (the default) the un-shared suffix drains chunk-at-a-time, so
        ANY match is worth using. Only in legacy monolithic mode
        (``chunk == 0``), where the suffix feeds one token per decode
        step, is a match restricted to bounded suffixes —
        ``P - m <= max(block_size, m)`` — so a 16-token preamble in
        front of a 240-token document doesn't trade one batched prefill
        for 240 serial catch-up steps."""
        P = len(eff)
        full = self.pool.blocks_for(P)
        blocks, m = self.pool.match(eff, P - 1)
        if m < self.block_size or \
                (not chunk and P - m > max(self.block_size, m)):
            return [], 0, full
        need = full - len(blocks)
        need += sum(1 for b in blocks if self.pool.refcount(b) == 0)
        if m % self.block_size and self.pool.refcount(blocks[-1]) >= 1:
            need += 1                    # imminent CoW of the shared tail
        return blocks, m, need

    def _chunk_for(self, req: Request) -> int:
        """Chunk width for ``req`` (0 = monolithic admission + serial
        catch-up): the request's override when set — an explicit 0 opts
        the request out of chunking, matching the engine knob's meaning
        — else the engine default; always 0 for families that cannot
        run multi-token windows (recurrent / MoE). Negative overrides
        are clamped here (add_requests rejects them loudly; this keeps
        pre-admission probes like blocks_needed safe on them too)."""
        if not self._paddable:
            return 0
        if req.prefill_chunk is None:
            return self.prefill_chunk
        return max(int(req.prefill_chunk), 0)

    def pending_chunk_tokens(self) -> int:
        """Pending prompt tokens the active slots will feed through
        chunk windows on the next step — the continuation demand the
        scheduler charges against its per-tick prefill budget before
        admitting new prefills."""
        tot = 0
        for i, r in enumerate(self.slot_req):
            if r is not None and self.slot_pending[i]:
                tot += min(len(self.slot_pending[i]),
                           max(self._chunk_for(r), 1))
        if self.prefill_budget is not None:
            tot = min(tot, self.prefill_budget)
        return tot

    def admission_costs(self, req: Request) -> tuple:
        """``(blocks, prefill_tokens)`` admitting ``req`` right now
        would cost — ONE prefix-match walk answers both (the scheduler
        asks per queued candidate per tick, so the walk must not run
        once per number). ``blocks`` is :meth:`blocks_needed`'s
        post-sharing + speculative-watermark figure; ``prefill_tokens``
        is what the admission call itself prefills — the first chunk
        (or whole prompt when monolithic), and 0 for a shared
        admission, whose un-shared suffix is chunk-step work charged as
        continuation on later ticks."""
        eff = self._eff_prompt(req)
        P = len(eff)
        C = self._chunk_for(req)
        first = min(P, C) if C else P
        if not self.paged:
            return 0, first
        spec = self.pool.blocks_for(min(P + self._spec_window(req),
                                        self.max_seq)) \
            - self.pool.blocks_for(P)
        if self.prefix_sharing:
            _, m, need = self._match_cost(eff, C)
            return need + spec, (0 if m >= self.block_size else first)
        return self.pool.blocks_for(P) + spec, first

    def admit_prefill_tokens(self, req: Request) -> int:
        """Prompt tokens admitting ``req`` right now would run through
        prefill in the admission call itself (see
        :meth:`admission_costs`)."""
        return self.admission_costs(req)[1]

    def _spec_window(self, req: Request) -> int:
        """Write positions one speculative step may need past the
        committed length: k proposals + the bonus token's scatter site.
        0 when the engine or the request opts out."""
        if not self.spec_k:
            return 0
        k = self.spec_k if req.speculation is None \
            else min(req.speculation, self.spec_k)
        return k + 1 if k > 0 else 0

    def blocks_needed(self, req: Request) -> int:
        """Pool blocks this request's admission requires right now — the
        **post-sharing** cost: blocks covered by a resident prefix match
        are already paid for (reusing them is free; revived cached
        blocks and a shared partial tail's imminent copy-on-write are
        charged). A speculating engine additionally charges the
        request's **speculative watermark** — the blocks its first
        draft-and-verify window will grow into — so a batch of
        admissions doesn't pass the gate and then mass-park on its
        first speculative step. A CHUNKED admission still charges its
        whole prompt here even though it only allocates its first
        chunk's blocks up front: gating on the first chunk would admit
        prompts the pool cannot finish and mass-park them mid-prompt.
        (0 when not paged — stripe admission is gated on free slots
        alone.)"""
        return self.admission_costs(req)[0]

    def blocks_worst_case(self, req: Request) -> int:
        """Upper bound on the request's block demand, independent of what
        happens to be resident — the "can this EVER be served" gate (a
        prefix match can vanish before a preempted re-admission)."""
        if not self.paged:
            return 0
        return self.pool.blocks_for(len(self._eff_prompt(req)))

    def blocks_available(self) -> int | None:
        return self.pool.available if self.paged else None

    def _admit_ok(self, need: int, planned: int) -> bool:
        avail = self.pool.available - planned
        if need + self.reserve_blocks <= avail:
            return True
        return self.active == 0 and planned == 0 and need <= avail

    def can_admit(self, req: Request, planned_blocks: int = 0, *,
                  need: int | None = None) -> bool:
        """Would admission succeed right now, with ``planned_blocks``
        already promised to earlier picks? Stripe engines admit whenever
        a slot is free; paged engines additionally demand blocks for the
        prompt (at the post-sharing cost) plus ``reserve_blocks`` of
        decode-growth headroom (waived when the engine is idle — an
        empty pool has nothing to protect). Pass ``need`` when the
        caller already holds :meth:`blocks_needed`'s answer, to skip a
        second prefix-match walk."""
        if not self.paged:
            return True
        if need is None:
            need = self.blocks_needed(req)
        return self._admit_ok(need, planned_blocks)

    def memory_pressure(self) -> float:
        """Fraction of KV memory in use: pool occupancy when paged, slot
        occupancy otherwise. The Scheduler sheds on this."""
        if self.paged:
            return self.pool.occupancy
        return self.active / self.B if self.B else 1.0

    def pool_stats(self) -> dict:
        if not self.paged:
            return {"paged": False, "slots": self.B, "active": self.active,
                    "occupancy": self.memory_pressure()}
        return {"paged": True, "waiting": len(self._waiting),
                # logical view: table entries across slots. With prefix
                # sharing this exceeds ``used`` — the physical count —
                # because a shared block is counted once by the pool
                # however many tables map it.
                "logical_blocks": sum(len(b) for b in self.slot_blocks),
                **self.pool.stats()}

    # --------------------------------------------------------- sampling
    @staticmethod
    def _sampling_rows(reqs: list):
        """Per-row sampling params for a prefill group. The counter is
        the request's emission index (``len(out_tokens)``) — a pure
        function of the request, so a sampled stream reproduces across
        engine configurations and preempted re-admissions."""
        n = len(reqs)
        temps = np.zeros(n, np.float32)
        top_ks = np.zeros(n, np.int32)
        seeds = np.zeros(n, np.int32)
        ctrs = np.zeros(n, np.int32)
        for j, r in enumerate(reqs):
            sp = r.sampling or GREEDY
            temps[j] = sp.temperature
            top_ks[j] = sp.top_k
            seeds[j] = sp.seed
            ctrs[j] = len(r.out_tokens)
        return (jnp.asarray(temps), jnp.asarray(top_ks),
                jnp.asarray(seeds), jnp.asarray(ctrs))

    def _sampling_slots(self):
        """Per-slot sampling params for a decode/verify step (greedy
        defaults for empty slots — their draws are discarded)."""
        B = self.B
        temps = np.zeros(B, np.float32)
        top_ks = np.zeros(B, np.int32)
        seeds = np.zeros(B, np.int32)
        ctrs = np.zeros(B, np.int32)
        for i, r in enumerate(self.slot_req):
            if r is None:
                continue
            sp = r.sampling or GREEDY
            temps[i] = sp.temperature
            top_ks[i] = sp.top_k
            seeds[i] = sp.seed
            ctrs[i] = len(r.out_tokens)
        return (jnp.asarray(temps), jnp.asarray(top_ks),
                jnp.asarray(seeds), jnp.asarray(ctrs))

    # --------------------------------------------------------- admission
    def add_request(self, req: Request) -> bool:
        """Prefill into a free slot; False if engine is full."""
        return self.add_requests([req]) == 1

    def _sim_chains(self, eff: list, sim: set) -> None:
        """Record the prefix chains a plain (prefilled) admission will
        register, for in-batch match simulation."""
        bs = self.block_size
        for i in range(self.pool.blocks_for(len(eff))):
            sim.add(tuple(eff[:min((i + 1) * bs, len(eff))]))

    def _sim_match(self, eff: list, max_len: int, sim: set) -> int:
        """Matched length against the union of the real prefix index and
        the chains earlier same-batch plain admissions will register.
        Once the walk leaves the real chain for a sim-promised chunk it
        stays sim-only (the source's later blocks will chain off the
        same canonical prefix, resolved at insertion time)."""
        bs = self.block_size
        pos = 0
        parent = self.pool.ROOT
        while pos + bs <= max_len:
            if tuple(eff[:pos + bs]) in sim:
                parent = False               # sim-only from here on
            else:
                if parent is False:
                    break
                b = self.pool.lookup(parent, tuple(eff[pos:pos + bs]))
                if b is None:
                    break
                parent = b
            pos += bs
        if pos < max_len:
            # partial tail: a sim chain extending past max_len also covers
            # it (the registered block holds at least these tokens)
            tail = tuple(eff[pos:max_len])
            if (parent is not False
                    and self.pool.lookup(parent, tail, partial=True)
                    is not None) \
                    or any(c[:max_len] == tuple(eff[:max_len])
                           and len(c) >= max_len for c in sim):
                return max_len
        return pos

    def add_requests(self, reqs: list) -> int:
        """Admit as many of ``reqs`` (in order, behind any preempted
        requests awaiting re-admission) as free slots AND pool blocks
        allow. Plain admissions prefill each shape-compatible group as
        ONE batched call whose slot insertion happens on device; with
        prefix sharing, a request whose prompt prefix is resident (or is
        being prefilled by an earlier member of this very batch) skips
        prefill for the shared blocks — it acquires them and owes only
        its un-shared suffix, fed through the normal decode steps.
        Returns how many of the *caller's* requests were admitted (a
        prefix of ``reqs``)."""
        for r in reqs:
            if len(r.prompt) > self.max_seq:
                raise ValueError(f"request {r.rid}: prompt length "
                                 f"{len(r.prompt)} > max_seq {self.max_seq}")
            if r.prefill_chunk is not None and r.prefill_chunk < 0:
                raise ValueError(f"request {r.rid}: prefill_chunk "
                                 f"{r.prefill_chunk} < 0")
            if self.paged and \
                    self.pool.blocks_for(len(r.prompt)) > self.pool.total:
                raise ValueError(f"request {r.rid}: prompt needs "
                                 f"{self.pool.blocks_for(len(r.prompt))} "
                                 f"blocks > pool total {self.pool.total}")
        slots_avail = self.free_slots()
        cand = list(self._waiting) + list(reqs)
        take: list = []          # (req, slot, acquired-blocks | None)
        planned = 0
        sim: set = set()         # chains this batch's plain members add
        for r in cand:
            if len(take) >= len(slots_avail):
                break
            eff = self._eff_prompt(r)
            P = len(eff)
            if P > self.max_seq:
                # a preempted request regrew past capacity: it cannot be
                # re-prefilled — finish it as capacity-truncated
                r.done_s = self.clock()
                self.metrics["completed"] += 1
                if self.tracer.enabled:
                    self._trace_retire(r, "truncated")
                self._finished_at_admit.append(r)
                self._waiting.remove(r)
                continue
            slot = slots_avail[len(take)]
            acquired = None
            matched = 0
            if self.paged:
                need = self.pool.blocks_for(P)
                if self.prefix_sharing:
                    blocks, m, cost = self._match_cost(eff,
                                                       self._chunk_for(r))
                    if m >= self.block_size:
                        acquired, matched, need = list(blocks), m, cost
                    else:
                        m_sim = self._sim_match(eff, P - 1, sim)
                        if m_sim >= self.block_size \
                                and (self._chunk_for(r)
                                     or P - m_sim <= max(self.block_size,
                                                         m_sim)):
                            # an earlier member of this batch prefills the
                            # prefix: plan at the post-sharing cost and
                            # resolve the real blocks at insertion time
                            acquired = []
                            need -= self.pool.blocks_for(m_sim)
                            if m_sim % self.block_size:
                                need += 1          # its CoW, like above
                if not self._admit_ok(need, planned):
                    break            # in-order admission: head waits
                planned += need
                if acquired:
                    for b in acquired:
                        # commit the match now: holding a reference keeps
                        # the blocks resident (and indexed) however the
                        # rest of this batch retires or frees. A revived
                        # cached block leaves ``planned`` the moment it
                        # leaves the free list — ``need`` charged it, and
                        # pool.available now reflects it, so keeping both
                        # would double-count it against later picks.
                        if self.pool.refcount(b) == 0:
                            planned -= 1
                        self.pool.acquire(b, owner=slot)
                if acquired is None and self.prefix_sharing:
                    # promise only what this admission actually REGISTERS
                    # in this call: a chunked admission indexes its first
                    # chunk's full blocks now and the rest over later
                    # chunk steps — promising the whole prompt would let
                    # a same-batch peer plan a cheap shared admission,
                    # find the promise broken at insertion time, and
                    # fall back to a plain prefill the block planner
                    # never budgeted
                    C = self._chunk_for(r)
                    n0 = min(P, C) if C else P
                    reg = eff if n0 >= P \
                        else eff[:n0 - n0 % self.block_size]
                    if reg:
                        self._sim_chains(reg, sim)
            take.append((r, slot, acquired, matched))
        n_from_waiting = 0
        for r, _, _, _ in take:
            if self._waiting and self._waiting[0] is r:
                self._waiting.popleft()
                n_from_waiting += 1
        if not take:
            return 0
        # ---- plain admissions first: batched prefill per shape group.
        # A chunked admission contributes only its FIRST chunk here (n0
        # tokens); the remainder becomes the slot's pending queue, fed
        # through decode-interleaved chunk windows by step().
        plain = [(r, s) for r, s, acq, _ in take if acq is None]
        groups: dict = {}
        for n, (req, slot) in enumerate(plain):
            P = len(self._eff_prompt(req))
            C = self._chunk_for(req)
            n0 = min(P, C) if C else P           # first-chunk token count
            if self._solo_prefill:
                key = (n,)                       # one row per prefill call
            elif self._paddable:
                key = _bucket(n0, self.max_seq)
            else:
                key = n0                         # exact-length co-batching
            groups.setdefault(key, []).append((req, slot, n0))
        for key, members in groups.items():
            width = key if isinstance(key, int) else members[0][2]
            toks = np.zeros((len(members), width), np.int32)
            last = np.zeros(len(members), np.int32)
            slots = np.zeros(len(members), np.int32)
            for j, (req, slot, n0) in enumerate(members):
                toks[j, :n0] = self._eff_prompt(req)[:n0]
                last[j] = n0 - 1
                slots[j] = slot
            samp = self._sampling_rows([req for req, _, _ in members])
            if self.paged:
                nxt, logp, pref = self._prefill_paged(
                    self.params, jnp.asarray(toks), jnp.asarray(last),
                    *samp)
                for j, (req, slot, n0) in enumerate(members):
                    eff = self._eff_prompt(req)
                    self._insert_paged(pref, j, slot, eff[:n0],
                                       more=n0 < len(eff))
            else:
                nxt, logp, self.caches = self._admit(
                    self.params, self.caches, jnp.asarray(toks),
                    jnp.asarray(last), jnp.asarray(slots), *samp)
            nxt, logp = np.asarray(nxt), np.asarray(logp)
            for j, (req, slot, n0) in enumerate(members):
                eff = self._eff_prompt(req)
                P = len(eff)
                if slot in self._used_slots:
                    self.metrics["slot_reuses"] += 1
                self._used_slots.add(slot)
                self.slot_req[slot] = req
                self.slot_len[slot] = n0
                self.slot_pending[slot] = list(eff[n0:])
                self._admit_seq += 1
                self._admit_order[slot] = self._admit_seq
                self._trace_admit(req, slot, chunked=n0 < P)
                self.metrics["prefills"] += 1
                self.metrics["prefill_tokens_computed"] += P
                if n0 < P:
                    # mid-prompt: the sampled draw is mid-prompt logits,
                    # discarded — the first real token comes from the
                    # chunk window that drains the pending queue
                    self.metrics["chunked_admissions"] += 1
                    continue
                req.out_tokens.append(int(nxt[j]))
                req.out_logprobs.append(float(logp[j]))
                self._note_first_token(req)
                if self._is_done(req):
                    self._retire(slot)
                    self._finished_at_admit.append(req)
            self.metrics["prefill_batches"] += 1
        # ---- shared admissions after: the whole batch's registrations
        # are visible, so in-batch prefixes resolve to real blocks
        for req, slot, acquired, matched in take:
            if acquired is None:
                continue
            self._admit_shared(req, slot, acquired, matched)
        if self.draft is not None:
            # the draft model caches every admitted prompt too (shared
            # admissions included: the draft has no shared blocks, its
            # stripes are per-slot) — skipping slots that retired at
            # admission (stop token / max_new in the first token). The
            # draft caches everything but the newest committed token
            # (plain admissions just emitted one), which the proposal
            # loop feeds to draw the first proposal.
            members = []
            for req, slot, _, _ in take:
                if self.slot_req[slot] is not req:
                    continue
                eff = self._eff_prompt(req)
                members.append((slot, eff[:-1] if req.out_tokens else eff))
            if members:
                self.draft.admit(members)
        return len(take) - n_from_waiting

    def _extend_match(self, eff: list, slot: int, blocks: list,
                      m: int) -> int:
        """Extend a committed match chain past ``m`` with whatever this
        batch's prefills registered since planning, acquiring each new
        block for ``slot``. Never re-walks from the root — the committed
        chain stays authoritative (a re-walk could diverge onto blocks
        we hold no references to; see the partial-tail-vs-full-block
        race). Only a boundary-ended chain can extend."""
        bs = self.block_size
        if m % bs or not blocks:
            return m
        cap = len(eff) - 1
        parent = blocks[-1]
        while m + bs <= cap:
            b = self.pool.lookup(parent, tuple(eff[m:m + bs]))
            if b is None or b in blocks:
                break
            self.pool.acquire(b, owner=slot)
            blocks.append(b)
            parent = b
            m += bs
        tail = tuple(eff[m:cap])
        if tail and m % bs == 0:
            b = self.pool.lookup(parent, tail, partial=True)
            if b is not None and b not in blocks:
                self.pool.acquire(b, owner=slot)
                blocks.append(b)
                m += len(tail)
        return m

    def _admit_shared(self, req: Request, slot: int, acquired: list,
                      matched: int) -> None:
        """Admit ``req`` into ``slot`` reusing resident prefix blocks.
        ``acquired``/``matched`` are the chain committed at planning time
        (held since, so still resident and indexed); it is extended —
        never re-walked — with blocks this batch's prefills registered.
        An empty ``acquired`` is an in-batch promise resolved against
        the real index here. The un-shared suffix (always >= 1 token:
        the match is capped at P-1 so the last prompt token's logits are
        still computed) becomes the slot's pending queue, fed through
        the ordinary decode steps."""
        eff = self._eff_prompt(req)
        P = len(eff)
        C = self._chunk_for(req)
        if acquired:
            blocks = list(acquired)
            m = self._extend_match(eff, slot, blocks, matched)
        else:
            blocks, m, _ = self._match_cost(eff, C)  # m = 0 if unusable now
            for b in blocks:
                self.pool.acquire(b, owner=slot)
        if m < self.block_size:
            # in-batch promise broken: the source retired inside this
            # very batch and took its index entries with it (nothing was
            # acquired, and the source's freed blocks more than cover a
            # solo plain prefill) — chunked like any plain admission
            n0 = min(P, C) if C else P
            toks = np.asarray([eff[:n0]], np.int32)
            last = np.asarray([n0 - 1], np.int32)
            nxt, logp, pref = self._prefill_paged(
                self.params, jnp.asarray(toks), jnp.asarray(last),
                *self._sampling_rows([req]))
            self._insert_paged(pref, 0, slot, eff[:n0], more=n0 < P)
            self.slot_req[slot] = req
            self.slot_len[slot] = n0
            self.slot_pending[slot] = list(eff[n0:])
            self.metrics["prefill_batches"] += 1
            self.metrics["prefill_tokens_computed"] += P
            if n0 < P:
                self.metrics["chunked_admissions"] += 1
            else:
                req.out_tokens.append(int(np.asarray(nxt)[0]))
                req.out_logprobs.append(float(np.asarray(logp)[0]))
                self._note_first_token(req)
        else:
            self.slot_blocks[slot] = list(blocks)
            self.block_table[slot, :] = 0
            self.block_table[slot, :len(blocks)] = blocks
            self.slot_req[slot] = req
            self.slot_len[slot] = m
            self.slot_pending[slot] = list(eff[m:])
            # chunk-step registration continues the matched chain only
            # from a block boundary: a partial-tail match ends inside a
            # block another sequence registered, and children of a
            # partial parent are unreachable by the match walk
            if m % self.block_size == 0:
                self.slot_reg[slot] = blocks[-1]
                self.slot_reg_pos[slot] = m
            else:
                self.slot_reg[slot] = False
            self.metrics["shared_admissions"] += 1
            self.metrics["prefill_tokens_shared"] += m
            self.metrics["prefill_tokens_computed"] += P - m
        if slot in self._used_slots:
            self.metrics["slot_reuses"] += 1
        self._used_slots.add(slot)
        self._admit_seq += 1
        self._admit_order[slot] = self._admit_seq
        self._trace_admit(req, slot, shared=m >= self.block_size,
                          chunked=bool(self.slot_pending[slot]))
        self.metrics["prefills"] += 1
        if self._is_done(req):
            self._retire(slot)
            self._finished_at_admit.append(req)

    def _insert_paged(self, pref, row: int, slot: int, eff: list, *,
                      more: bool = False) -> None:
        """Allocate the slot's blocks and scatter its prefill KV into the
        pool block-by-block (jitted dynamic_update_slice, pool donated);
        with sharing on, advertise each block's prompt content in the
        prefix index so later admissions can reuse it. ``more``: the
        prompt continues past ``eff`` (a chunked admission's first
        chunk) — the trailing partial block keeps filling with prompt
        content over the coming chunk steps, so its registration is
        deferred to ``_register_chunk_progress`` (registering a
        half-chunk extent now would freeze the index at it)."""
        n_tokens = len(eff)
        n_blk = self.pool.blocks_for(n_tokens)
        blocks = self.pool.alloc(n_blk, owner=slot)
        assert blocks is not None, "admission accounting let an alloc fail"
        self.slot_blocks[slot] = blocks
        self.block_table[slot, :] = 0
        self.block_table[slot, :n_blk] = blocks
        bs = self.block_size
        parent = self.pool.ROOT if self.prefix_sharing else False
        reg_pos = 0
        for i, phys in enumerate(blocks):
            self.caches = self._write_block(
                self.caches, pref, np.int32(row),
                np.int32(i * bs), np.int32(phys))
            end = min((i + 1) * bs, n_tokens)
            if parent is not False and (end - i * bs == bs or not more):
                # thread the canonical block as the next link's parent so
                # duplicate chains converge on one indexed copy; an
                # unregistrable link ends the chain (False sentinel)
                parent = self.pool.register(phys, parent,
                                            tuple(eff[i * bs:end]))
                if parent is None:
                    parent = False
                else:
                    reg_pos = end
        if parent is not False and n_tokens % bs and not more:
            # the final registration was a partial tail: children of a
            # partial parent are unreachable by the match walk, so the
            # chain ends here. A chunked admission (``more``) instead
            # SKIPPED the partial registration above — its chain stays
            # open at the last full block (or ROOT for a sub-block first
            # chunk) and _register_chunk_progress registers the rest as
            # the chunk steps fill it.
            parent = False
        self.slot_reg[slot] = parent
        self.slot_reg_pos[slot] = reg_pos

    def _register_chunk_progress(self, i: int, final: bool) -> None:
        """Advertise prompt content a chunk / catch-up step just wrote
        into slot ``i``'s blocks: every newly FULL block registers in
        the prefix index chained after the slot's canonical frontier,
        and — once the prompt drains (``final``) — the trailing partial
        block registers at the prompt's true tail. These are exactly the
        entries a monolithic prefill would have left, so half-prefilled
        prompts share forward like whole ones. No-op when the chain is
        broken (partial-tail match, CoW below the frontier, duplicate
        registration) — sharing still covers everything before the
        break."""
        parent = self.slot_reg[i]
        if parent is False or not self.prefix_sharing:
            return
        bs = self.block_size
        end = int(self.slot_len[i])    # prompt content resident through
        pos = int(self.slot_reg_pos[i])
        eff = self._eff_prompt(self.slot_req[i])
        while parent is not False and pos + bs <= end:
            parent = self.pool.register(self.slot_blocks[i][pos // bs],
                                        parent, tuple(eff[pos:pos + bs]))
            if parent is None:
                parent = False
            else:
                pos += bs
        if parent is not False and final and pos < end:
            self.pool.register(self.slot_blocks[i][pos // bs], parent,
                               tuple(eff[pos:end]))
            parent = False     # a partial tail ends the walkable chain
            pos = end
        self.slot_reg[i] = parent
        self.slot_reg_pos[i] = pos

    # ------------------------------------------------------------- decode
    def _is_done(self, req: Request) -> bool:
        return (len(req.out_tokens) >= req.max_new_tokens
                or req.finished_by_stop)

    def _release_blocks(self, slot: int) -> None:
        if self.paged and self.slot_blocks[slot]:
            self.pool.free(self.slot_blocks[slot], owner=slot)
            self.slot_blocks[slot] = []
            self.block_table[slot, :] = 0

    def _retire(self, slot: int, *, cancelled: bool = False) -> None:
        req = self.slot_req[slot]
        req.done_s = self.clock()
        if self.tracer.enabled:
            self._trace_retire(req,
                               "cancelled" if cancelled else "completed")
        self.slot_req[slot] = None
        self.slot_len[slot] = 0
        self.slot_pending[slot] = []
        self.slot_reg[slot] = False
        self.slot_reg_pos[slot] = 0
        self._release_blocks(slot)
        if self.draft is not None:
            self.draft.reset(slot)
        if cancelled:
            self.metrics["cancelled"] += 1
            return
        self.metrics["completed"] += 1
        if req.finished_by_stop and len(req.out_tokens) < req.max_new_tokens:
            self.metrics["stop_token_exits"] += 1

    def cancel(self, rid: int) -> bool:
        """Cancel request ``rid`` mid-flight: retire its slot (blocks
        freed, draft state reset, slot recyclable this very tick) or
        drop it from the preempted backlog. Returns False when the
        engine doesn't hold it (already finished, or still queued in
        front of the engine — the scheduler owns that case). Must NOT
        be called between ``dispatch_step()`` and ``commit()``: the
        in-flight tick's bookkeeping indexes the slots it dispatched
        with — the async loop applies cancels at the loop boundary."""
        for i, r in enumerate(self.slot_req):
            if r is not None and r.rid == rid:
                self._retire(i, cancelled=True)
                return True
        for r in list(self._waiting):
            if r.rid == rid:
                self._waiting.remove(r)
                r.done_s = self.clock()
                if self.tracer.enabled:
                    self._trace_retire(r, "cancelled")
                self.metrics["cancelled"] += 1
                return True
        return False

    def _preempt(self, slot: int) -> None:
        """Evict a slot under pool exhaustion: free its blocks and queue
        the request for recompute re-admission (its prompt + generated
        tokens prefill again when memory frees — the standard paged-KV
        preemption, trading recompute for not deadlocking the batch).
        Freeing only drops this slot's references: blocks shared with a
        live slot stay resident for it."""
        req = self.slot_req[slot]
        req.preemptions += 1
        self.slot_req[slot] = None
        self.slot_len[slot] = 0
        self.slot_pending[slot] = []
        self.slot_reg[slot] = False
        self.slot_reg_pos[slot] = 0
        self._release_blocks(slot)
        if self.draft is not None:
            self.draft.reset(slot)
        self._waiting.append(req)
        self.metrics["preemptions"] += 1
        if self.tracer.enabled:
            self.tracer.instant("preempt", pid=PID_REQUESTS, tid=req.rid,
                                args={"slot": slot,
                                      "generated": len(req.out_tokens)})

    def _ensure_writable(self, i: int, width: int) -> int:
        """Make positions ``[len, len + width)`` of slot ``i`` safe to
        scatter into: **copy-on-write** a shared tail before any write
        would land in it, drop stale prefix-index entries for in-place
        writes, and allocate blocks through the window's last position
        (the speculative **watermark** — ``width = n_spec + 1`` for a
        speculating slot, 1 otherwise). Returns how many positions were
        actually secured: the full width, a degraded count when the pool
        ran out mid-window (the engine speculates less), or 0 — the slot
        cannot even take its next single token and must park."""
        L = int(self.slot_len[i])
        bs = self.block_size
        first_bi = L // bs
        if first_bi < len(self.slot_blocks[i]):
            b = self.slot_blocks[i][first_bi]
            if not self.pool.writable(b):
                # shared tail: writing in place would corrupt the other
                # holders' KV — duplicate the block on device, swap our
                # table entry to the copy, drop our hold on the original
                got = self.pool.alloc(1, owner=i)
                if got is None:
                    # park — and divert this slot's ride-along scatter to
                    # the scratch block: with the table still naming the
                    # SHARED block, the parked write would land in it and
                    # corrupt the other holders' KV (restored below once
                    # the copy, or sole ownership, arrives)
                    self.block_table[i, first_bi] = 0
                    self.metrics["cow_parks"] += 1
                    if self.tracer.enabled:
                        self.tracer.instant("cow_park", pid=PID_POOL,
                                            args={"slot": i,
                                                  "block": int(b)})
                    return 0
                self.caches = self._copy_block(self.caches, np.int32(b),
                                               np.int32(got[0]))
                self.pool.free([b], owner=i)
                self.slot_blocks[i][first_bi] = got[0]
                self.metrics["cow_copies"] += 1
                if self.tracer.enabled:
                    self.tracer.instant("cow_copy", pid=PID_POOL,
                                        args={"slot": i, "src": int(b),
                                              "dst": int(got[0])})
                b = got[0]
            self.block_table[i, first_bi] = b    # also restores a CoW park
            self.pool.prepare_write(b, L % bs)
        last_bi = (L + width - 1) // bs
        while last_bi >= len(self.slot_blocks[i]):
            bi = len(self.slot_blocks[i])
            got = self.pool.alloc(1, owner=i)
            if got is None:
                # secured everything below the unallocated block: the
                # window shrinks (0 when even position L has no block)
                return max(bi * bs - L, 0)
            self.slot_blocks[i].extend(got)
            self.block_table[i, bi] = got[0]
            self.metrics["blocks_grown"] += 1
        return width

    def _grow_or_park(self, active: list, want: dict | None = None) -> dict:
        """Make every active slot's write site(s) safe — ``want[i]``
        positions for a speculating slot (its watermark), one otherwise.
        Slots the pool cannot serve at all park (skip this step, state
        intact); slots it can only partially serve speculate less. If
        nobody can advance, preempt newest admissions until the oldest
        can. Returns {slot: positions secured} (parked slots are removed
        from ``active`` and absent)."""
        secured: dict = {}
        parked = []
        for i in list(active):
            got = self._ensure_writable(i, (want or {}).get(i, 1))
            if got == 0:
                parked.append(i)
                active.remove(i)
            else:
                secured[i] = got
        if parked and not active:
            # total stall: every active slot needs a block and none is
            # free (all blocks are held by the stalled slots themselves).
            order = sorted(parked, key=lambda i: self._admit_order[i])
            while len(order) > 1:
                victim = order.pop()            # newest admission recomputes
                parked.remove(victim)
                self._preempt(victim)
                got = self._ensure_writable(order[0], 1)
                if got:                         # oldest advances first
                    oldest = order.pop(0)
                    parked.remove(oldest)
                    active.append(oldest)
                    secured[oldest] = got
                    break
            if len(order) == 1 and not active:
                # one slot owns the whole pool and still needs more:
                # nothing left to preempt — finish it capacity-truncated
                i = order[0]
                parked.remove(i)
                self._finished_at_admit.append(self.slot_req[i])
                self._retire(i)
        self.metrics["parked_slot_steps"] += len(parked)
        if parked and self.tracer.enabled:
            for i in parked:
                self.tracer.instant("park", pid=PID_REQUESTS,
                                    tid=self.slot_req[i].rid,
                                    args={"slot": i})
        return secured

    def _rollback(self, i: int) -> None:
        """Speculative rollback: return pool blocks past the committed
        length to the pool. Every freed block was allocated for this
        slot's watermark *this or an earlier speculative step* and is
        sole-owned (the window was made writable — copied-on-write out
        of any sharing — before the verify scatter), so no co-holder's
        chain is ever rolled back."""
        keep = self.pool.blocks_for(max(int(self.slot_len[i]), 1))
        extra = self.slot_blocks[i][keep:]
        if extra:
            self.pool.free(extra, owner=i)
            del self.slot_blocks[i][keep:]
            self.block_table[i, keep:] = 0
            self.metrics["spec_blocks_rolled_back"] += len(extra)

    def _spec_step(self, active: list, n_spec, finished: list) -> _Tick:
        """Dispatch one draft-and-verify step. ``n_spec[i]`` proposals
        for each speculating slot (0 for riders: pending catch-up,
        opted-out, or watermark-degraded slots — they feed one real
        token through the same verify batch and advance by one, exactly
        the plain step). The returned tick's commit synchronizes on the
        verify outputs, commits each row's accepted prefix + bonus
        token, rolls the pool back to the committed watermark, and
        advances the draft."""
        k = self.spec_k
        temps, top_ks, seeds, ctrs = self._sampling_slots()
        rows = [i for i in active if n_spec[i] > 0]
        # the draft only needs each row's UNCACHED committed suffix (at
        # most ~2 tokens between rounds) — not an O(prompt + generated)
        # rebuild of the whole context per step
        tails = [None] * self.B
        totals = np.zeros(self.B, np.int64)
        for i in rows:
            r = self.slot_req[i]
            dl, P = int(self.draft.len[i]), len(r.prompt)
            tails[i] = (r.prompt[dl:] + r.out_tokens) if dl < P \
                else r.out_tokens[dl - P:]
            totals[i] = P + len(r.out_tokens)
        proposed, dprobs = self.draft.propose(tails, rows, k, temps,
                                              top_ks, seeds, ctrs)
        self.metrics["draft_steps"] = self.draft.steps_run
        toks = np.zeros((self.B, k + 1), np.int32)
        n_write = np.zeros(self.B, np.int32)
        for i in active:
            r = self.slot_req[i]
            toks[i, 0] = self.slot_pending[i][0] if self.slot_pending[i] \
                else r.out_tokens[-1]
            toks[i, 1:] = proposed[i]
            n_write[i] = n_spec[i] + 1
        ns = jnp.asarray(np.asarray(n_spec, np.int32))
        if self.paged and self.use_kernel:
            self.metrics["kernel_windows"] += 1
            self.metrics["kernel_positions"] += int(
                sum(n_write[i] for i in active))
        if self.paged:
            a, out_toks, lps, self.caches = self._verify(
                self.params, jnp.asarray(toks), self.caches,
                jnp.asarray(self.slot_len), jnp.asarray(self.block_table),
                jnp.asarray(n_write), dprobs, jnp.asarray(proposed), ns,
                temps, top_ks, seeds, ctrs)
        else:
            a, out_toks, lps, self.caches = self._verify(
                self.params, jnp.asarray(toks), self.caches,
                jnp.asarray(self.slot_len), dprobs, jnp.asarray(proposed),
                ns, temps, top_ks, seeds, ctrs)
        self.metrics["decode_steps"] += 1
        self.metrics["verify_steps"] += 1
        return _Tick(lambda: self._commit_spec(active, n_spec, finished,
                                               totals, a, out_toks, lps))

    def _commit_spec(self, active, n_spec, finished, totals, a, out_toks,
                     lps) -> list:
        a, out_toks, lps = np.asarray(a), np.asarray(out_toks), \
            np.asarray(lps)
        k = self.spec_k
        win_proposed = win_accepted = 0     # this verify window's totals
        for i in active:
            r = self.slot_req[i]
            if self.slot_pending[i]:
                # catch-up rider: the fed token was a *prompt* token —
                # its sampled successor only counts once the un-shared
                # suffix is exhausted
                self.slot_len[i] += 1
                self.slot_pending[i].pop(0)
                self._register_chunk_progress(
                    i, final=not self.slot_pending[i])
                if self.paged:
                    self._rollback(i)
                if self.slot_pending[i]:
                    continue
                commit = [int(out_toks[i, 0])]
                lpc = [float(lps[i, 0])]
            else:
                ai = int(min(a[i], n_spec[i]))
                self.slot_len[i] += ai + 1
                commit = [int(t) for t in out_toks[i, :ai + 1]]
                lpc = [float(x) for x in lps[i, :ai + 1]]
                if n_spec[i] > 0:
                    self.metrics["spec_proposed"] += int(n_spec[i])
                    self.metrics["spec_accepted"] += ai
                    win_proposed += int(n_spec[i])
                    win_accepted += ai
                    # draft cache valid through the accepted prefix; it
                    # only ever cached through proposal k-1
                    self.draft.commit(i, int(totals[i]) + min(ai, k - 1))
                if self.paged:
                    self._rollback(i)
            room = r.max_new_tokens - len(r.out_tokens)
            commit = commit[:room]
            for t_idx, t in enumerate(commit):
                if t in r.stop_tokens:       # stop inside the window
                    commit = commit[:t_idx + 1]
                    break
            r.out_tokens.extend(commit)
            r.out_logprobs.extend(lpc[:len(commit)])
            if commit:
                self._note_first_token(r)
            if self._is_done(r):
                finished.append(r)
                self._retire(i)
        if win_proposed and self.tracer.enabled:
            # per-window acceptance: Perfetto renders these as stacked
            # counter series next to the tick-phase track
            self.tracer.counter("speculation",
                                {"proposed": win_proposed,
                                 "accepted": win_accepted}, pid=PID_LOOP)
        return finished

    def _chunk_step(self, active: list, chunk_want: dict,
                    finished: list) -> _Tick:
        """Dispatch one **chunk window** step: every slot with pending prompt
        tokens feeds up to its chunk of them (K/V written at its own
        positions, attending causally against its resident prefix) while
        decode slots ride the same batch with their single next token —
        prompt ingestion interleaved with decode instead of stalling it.
        A row that exhausts its prompt inside the window samples its
        first output token at its last real position; every other
        window draw is discarded. Parked slots ride with ``n_write`` 0
        (paged: all their writes divert to scratch)."""
        W = _bucket(max(chunk_want.get(i, 1) for i in active),
                    self.max_seq)
        toks = np.zeros((self.B, W), np.int32)
        n_write = np.zeros(self.B, np.int32)
        last = np.zeros(self.B, np.int32)
        n_fed: dict = {}
        for i in active:
            r = self.slot_req[i]
            if self.slot_pending[i]:
                c = chunk_want.get(i, 1)
                toks[i, :c] = self.slot_pending[i][:c]
            else:
                c = 1
                toks[i, 0] = r.out_tokens[-1]
            n_fed[i] = c
            n_write[i] = c
            last[i] = c - 1
        temps, top_ks, seeds, ctrs = self._sampling_slots()
        if self.paged and self.use_kernel:
            self.metrics["kernel_windows"] += 1
            self.metrics["kernel_positions"] += sum(n_fed.values())
        if self.paged:
            nxt, logp, self.caches = self._chunk_fn(
                self.params, jnp.asarray(toks), self.caches,
                jnp.asarray(self.slot_len), jnp.asarray(self.block_table),
                jnp.asarray(n_write), jnp.asarray(last), temps, top_ks,
                seeds, ctrs)
        else:
            nxt, logp, self.caches = self._chunk_fn(
                self.params, jnp.asarray(toks), self.caches,
                jnp.asarray(self.slot_len), jnp.asarray(last), temps,
                top_ks, seeds, ctrs)
        self.metrics["decode_steps"] += 1
        self.metrics["chunk_steps"] += 1
        return _Tick(lambda: self._commit_chunk(active, n_fed, finished,
                                                nxt, logp))

    def _commit_chunk(self, active, n_fed, finished, nxt, logp) -> list:
        nxt, logp = np.asarray(nxt), np.asarray(logp)
        for i in active:
            r = self.slot_req[i]
            c = n_fed[i]
            self.slot_len[i] += c
            if self.slot_pending[i]:
                del self.slot_pending[i][:c]
                self.metrics["chunk_prefill_tokens"] += c
                if self.paged:
                    self._register_chunk_progress(
                        i, final=not self.slot_pending[i])
                if self.slot_pending[i]:
                    continue
            r.out_tokens.append(int(nxt[i]))
            r.out_logprobs.append(float(logp[i]))
            self._note_first_token(r)
            if self._is_done(r):
                finished.append(r)
                self._retire(i)
        return finished

    def step(self) -> list:
        """One decode step over all active slots. Equivalent to
        ``dispatch_step().commit()`` — the synchronous drain every test
        and bench compares the async loop against."""
        return self.dispatch_step().commit()

    def dispatch_step(self) -> _Tick:
        """Dispatch one decode step over all active slots (each at its
        own length) — a draft-and-verify multi-token step when the
        engine speculates and any slot has room to, a chunk-window step
        when any slot owes more than one pending prompt token (prompt
        ingestion interleaved with everyone else's decode). Parked slots
        ride the batch but emit nothing.

        All host-side planning (capacity retires, chunk budgeting,
        speculative windows, block growth) happens here, then the jitted
        device call is *launched* — JAX async dispatch returns before
        the computation finishes. The returned :class:`_Tick`'s
        ``commit()`` blocks on the result and applies per-slot
        bookkeeping, returning finished requests. Between dispatch and
        commit the engine's slot state must not be mutated (no
        ``cancel``/``add_requests``) — that window is for *planning*
        (``admission_costs`` etc.), which only reads."""
        finished, self._finished_at_admit = self._finished_at_admit, []
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return _Tick(lambda: finished)
        # any slot past capacity would write out of bounds — finish it now
        for i in list(active):
            if self.slot_len[i] >= self.max_seq:
                finished.append(self.slot_req[i])
                self._retire(i)
                active.remove(i)
        # chunk plan: pending prompt tokens each slot feeds this step,
        # budgeted per tick across slots in admission order (every slot
        # still makes >= 1 token of progress on a dry budget)
        chunk_want: dict = {}
        budget = self.prefill_budget
        for i in sorted(active, key=lambda j: self._admit_order[j]):
            if not self.slot_pending[i]:
                continue
            c = min(len(self.slot_pending[i]),
                    max(self._chunk_for(self.slot_req[i]), 1))
            if budget is not None:
                c = max(1, min(c, budget))
                budget -= c
            chunk_want[i] = c
        chunking = any(c > 1 for c in chunk_want.values())
        # plan speculative windows before securing write sites, so the
        # watermark (window) blocks are granted in the same pass. A
        # chunk tick skips speculation: the window belongs to the
        # chunks, pending rows ride plain in a verify batch anyway, and
        # speculation resumes the moment the prompts drain.
        n_spec = np.zeros(self.B, np.int32)
        if self.spec_k and not chunking:
            for i in active:
                r = self.slot_req[i]
                if self.slot_pending[i]:
                    continue                  # catch-up rides plain
                k = self._spec_window(r) - 1
                if k <= 0:
                    continue
                n_spec[i] = max(0, min(
                    k, self.max_seq - 1 - int(self.slot_len[i]),
                    r.max_new_tokens - len(r.out_tokens) - 1))
        if self.paged and active:
            if chunking:
                want = {i: chunk_want.get(i, 1) for i in active}
            elif n_spec.any():
                want = {i: int(n_spec[i]) + 1 for i in active}
            else:
                want = None
            secured = self._grow_or_park(active, want)
            for i in active:
                # pool pressure degrades the window (possibly to 0: the
                # slot rides this step non-speculatively); a degraded
                # chunk just feeds fewer tokens this step
                n_spec[i] = min(n_spec[i], secured[i] - 1)
                if i in chunk_want:
                    chunk_want[i] = min(chunk_want[i], secured[i])
            chunking = any(chunk_want.get(i, 0) > 1 for i in active)
            finished.extend(self._finished_at_admit)
            self._finished_at_admit = []
        if not active:
            return _Tick(lambda: finished)
        if self.spec_k and any(n_spec[i] > 0 for i in active):
            return self._spec_step(active, n_spec, finished)
        if chunking:
            return self._chunk_step(active, chunk_want, finished)
        tok = np.zeros((self.B, 1), np.int32)
        for i, r in enumerate(self.slot_req):
            if r is None:
                continue            # parked rows too: their scatter lands
            if self.slot_pending[i]:            # in the scratch block
                tok[i, 0] = self.slot_pending[i][0]   # catch-up prompt token
            else:
                tok[i, 0] = r.out_tokens[-1]
        samp = self._sampling_slots()
        if self.paged and self.use_kernel:
            self.metrics["kernel_positions"] += len(active)
        if self.paged:
            nxt, logp, self.caches = self._decode(
                self.params, jnp.asarray(tok), self.caches,
                jnp.asarray(self.slot_len), jnp.asarray(self.block_table),
                *samp)
        else:
            nxt, logp, self.caches = self._decode(
                self.params, jnp.asarray(tok), self.caches,
                jnp.asarray(self.slot_len), *samp)
        self.metrics["decode_steps"] += 1
        return _Tick(lambda: self._commit_decode(active, finished, nxt,
                                                 logp))

    def _commit_decode(self, active, finished, nxt, logp) -> list:
        nxt, logp = np.asarray(nxt), np.asarray(logp)
        for i in active:
            r = self.slot_req[i]
            self.slot_len[i] += 1
            if self.slot_pending[i]:
                # a shared admission catching up on its un-shared prompt
                # suffix: the fed token was a *prompt* token, so its
                # logits only matter once the suffix is exhausted — then
                # the sample is the first genuinely generated token
                self.slot_pending[i].pop(0)
                if self.paged:
                    self._register_chunk_progress(
                        i, final=not self.slot_pending[i])
                if self.slot_pending[i]:
                    continue
            r.out_tokens.append(int(nxt[i]))
            r.out_logprobs.append(float(logp[i]))
            self._note_first_token(r)
            if self._is_done(r):
                finished.append(r)
                self._retire(i)
        return finished

    # ------------------------------------------------------------- run
    def run(self, requests: list) -> list:
        """Serve a list of requests to completion (batched, slots recycled
        as soon as they free up, preempted requests re-admitted)."""
        pending = list(requests)
        done: list = []
        while pending or self.active or self._waiting \
                or self._finished_at_admit:
            n = self.add_requests(pending)
            del pending[:n]
            done.extend(self.step())
        return done
