"""Continuous-batching scheduler: admit, prefill, decode, retire — every step.

The Orca (OSDI '22) iteration-level scheduling loop over the paged KV pool:

* submit() enqueues a request and returns a concurrent.futures.Future.
* Each engine iteration ADMITS pending requests into free decode slots
  (FIFO; a request is admitted only when the page pool can cover its whole
  lifetime — prompt pages plus worst-case growth — so decode can never hit
  a mid-flight out-of-pages), runs one shape-BUCKETED prefill per admission
  (prompt padded to the next rung of the system-wide ``BucketLadder`` —
  compile_service/buckets.py, the SAME ladder the bucketed TrainStep and
  stored compile artifacts key on, so there is no separate per-engine
  bucket mechanism and the thunder trace cache serves every prompt length
  from a handful of specializations), then packs ALL active sequences into ONE compiled decode step
  over the page pool and retires finished sequences, returning their pages
  to the free-list immediately.

Four throughput stages compose on top of that loop, each OFF by default so
the baseline engine behaves exactly as before (docs/serving.md):

* prefix sharing (``prefix_sharing=True``) — admission consults a
  content-keyed ``PrefixCache`` and maps already-cached prompt pages into
  the new request's table (refcounted, copy-on-write on first divergence);
  prefill then runs only on the unshared suffix, and a fully covered
  prompt skips prefill entirely (one re-decoded token recovers the
  first-token logits bit-identically).
* chunked prefill (``chunk_tokens=N``) — prompts longer than N are split
  into page-aligned chunks interleaved into decode iterations under a
  ``prefill_budget`` tokens-per-iteration cap, bounding the decode-latency
  spike a long prompt used to inject.
* speculative decoding (``draft_gpt=...``) — a small draft model proposes
  ``spec_k`` tokens per iteration with the SAME position-keyed sampler;
  one packed target verify step scores all k+1 positions and the accepted
  prefix (capped at k — no bonus token, which keeps the draft KV valid)
  commits. Accepted tokens are bit-identical to plain decode.
* SLO-aware lanes (``submit(..., lane="batch")``) — interactive requests
  admit first; under page pressure or SLO burn the engine preempts batch
  sequences (pages spilled, request requeued at the front of the batch
  lane) and re-prefills them on resume — cheap when prefix sharing holds
  their pages in cache, and bit-identical thanks to position-keyed
  sampling.

The plain decode path keeps ONE decode step in flight: a pass of the loop
dispatches the next step, whose tokens are the sampler's output of the step
before and never leave the device, and only then fetches and commits that
step before's tokens, so admission, uploads, the dispatch and the runtime's
wake-up all run under a program the chip already has. An activation JOINS
that pipeline: the prompt's program and the sampler of its first token are
dispatched behind the step in flight and nothing is fetched; the slot is live
at once, its first token is written into the next step's tokens on the device
(``_merge``, as is a token the host owns: a resumed victim's, a prefix hit's),
and the host reads it only after that next step is dispatched, behind the
fetch and commit of the step before, and commits it before the slot's token of
that step. So what is in flight is at most one decode step, the prompt
programs dispatched since, and their first tokens. What needs the host level
with the device lands all of it first (``_land``): a preemption, a failure,
stop and drain, a pass with no live sequence, and the speculative path, which
fetches before it dispatches. A sequence whose last token by count is in
flight is left out of the next step, a request that wants one token is never
activated, and a step with no live sequence is never dispatched; an end the
host learns at a commit (``eos_id``, a cancelled Future; a first token's too)
costs one step whose token is thrown away.

Generation by diffusion over blocks (``block_diffusion=``, docs/serving.md) is
a third decode order on the same pipeline: a pass carries TWO adjacent blocks
of K positions a sequence (``runner.block_cfn``): the block it has finished,
whose keys and values this pass SETTLES, and the next one, which it denoises.
Its sampler (``_block_sample``) fills some of that block's masked positions
and, once none is left, makes it the next pass's block to settle. The blocks,
their masked flags and their positions stay on the device from pass to pass.
One pass is in flight; the host reads the record of the pass before it
(``_commit_block_pass``) and assumes nothing about how many positions a pass
filled. Prompts' whole blocks go through the chunk program under the
block-causal mask; an activation merges the prompt's tail and mask tokens into
the next pass's blocks on the device (``_activate_block``).

Per-request observability rides the existing bus: request-id-tagged spans,
``serve.*`` counters, and flight-recorder records per decode iteration
(docs/serving.md, docs/observability.md). The loop itself runs under
``engine:*`` phases (``observability.runtime.phase``: bus span + profiler
annotation), one innermost phase at every instant, so a device trace can
put each idle gap down to admit, prefill, upload, dispatch, fetch or commit.

Sampling is position-keyed — token at position p draws from
``fold_in(PRNGKey(seed), p)`` — so a request's stream is identical whether
it runs solo (inference.GPTInference.generate) or continuously batched.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_service.buckets import BucketLadder
from ..observability import events as _obs
from ..observability import flight_recorder as _obs_flight
from ..observability import memory_watch as _obs_mem
from ..observability import metrics as _obs_metrics
from ..observability import runtime as _obs_runtime
from ..observability import telemetry as _obs_tel
from ..observability import tracing as _obs_trace
from ..observability.slo import SLOMonitor, SLOPolicy
from .kv_pages import PagedKV, PagedKVCache, PagedLatent, PrefixCache, Recurrent
from .runner import ROUTING_COUNTERS, PagedGPTRunner, quantize_for_serving

_NULL = contextlib.nullcontext()


@dataclass
class RequestResult:
    """What a request's Future resolves to."""

    request_id: int
    tokens: np.ndarray          # prompt + generated, (prompt_len + n_new,)
    new_tokens: np.ndarray      # generated only, (n_new,)
    ttft_s: float               # submit -> first token
    tbot_s: float               # mean time between output tokens
    n_new_tokens: int = 0
    finish_reason: str = "length"   # "length" | "eos" | "cancelled"
    # per-request SLO-met flag stamped at retirement when the engine has an
    # SLOPolicy attached (the goodput numerator); None without a policy
    slo_met: Optional[bool] = None
    queue_s: float = 0.0        # submit -> admitted (pages reserved, slot taken)
    # the pages of every position the sequence held when it retired, in position order. They
    # are back in the pool; their rows stay as written until another sequence takes them, which
    # is how a test reads what the engine cached for a request it served alone
    pages: tuple = ()
    # window layers only: the window pages the sequence held when it retired, as (page index,
    # page) pairs in position order (their rows stay as written, as ``pages``' do), and the most
    # it held at once
    window_pages: tuple = ()
    window_pages_peak: int = 0
    # generation by diffusion over blocks only: (position, pass) of every generated position in
    # the order it was filled, and, where ``engine.record_block_states`` was set, the block going
    # INTO each pass: (first position, its tokens (K,), its masked flags (K,)). A "pass" here is
    # one run of ONE block, numbered from 0 over the request: every denoise pass of a block and
    # then its settling, none masked, which comes before the next block's first denoise pass
    # though one dispatch ran both (what a loop of one block a forward would record)
    unmasked: tuple = ()
    block_states: tuple = ()


@dataclass
class _Request:
    request_id: int
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float
    seed: int
    eos_id: Optional[int]
    future: Future
    t_submit: float
    lane: str = "interactive"
    # end-to-end trace id (observability/tracing.py), minted at submit()
    # ONLY when the bus is enabled; None means every downstream trace site
    # exits on one attribute read (the zero-work-when-disabled contract)
    trace_id: Optional[str] = None
    t_admit: float = 0.0         # first admission (a resumed request keeps it)
    t_first: float = 0.0
    t_last: float = 0.0
    tokens: List[int] = field(default_factory=list)
    pages: List[int] = field(default_factory=list)
    # pages of the window layers' pools, by page index (position // page_size):
    # only those that intersect the window are held
    win_pages: Dict[int, int] = field(default_factory=dict)
    win_peak: int = 0
    bucket: int = 0
    # admission-time routing state (set by _reserve_pages each admission —
    # a preempted request is re-routed from scratch on resume)
    admit_seq: int = -1          # monotone admission order (preemption victims)
    admit_mode: str = ""         # "prefill" | "chunk" | "hit"
    prompt_eff: Optional[np.ndarray] = None  # prompt (+ committed tokens on resume)
    covered: int = 0             # prefix-cache token coverage of prompt_eff
    n_shared: int = 0            # leading shared pages in .pages
    chunk_pos: int = -1          # next chunk start (chunk mode only)
    # generation by diffusion over blocks: the prompt's last L mod K tokens, which open the
    # first generated block; the position the next fetched pass's record must carry (the first
    # of its two blocks: K before the block being denoised) and how many leading tokens of the
    # first generated block were given; block runs recorded; RequestResult.unmasked / .block_states
    block_tail: Optional[np.ndarray] = None
    block_pos: int = 0
    block_given: int = 0
    n_passes: int = 0
    unmasked: List[tuple] = field(default_factory=list)
    block_states: List[tuple] = field(default_factory=list)


@dataclass
class _Step:
    """A decode step in flight: dispatched, its sampled tokens not fetched. A
    sequence activated since the step before is in it from its first step on,
    fed the first token that ``_First`` holds; that token is read and committed
    before this step lands."""

    # (max_batch, 1) int32 on the device: the next step's tokens; of a pass over blocks the
    # (max_batch, 5 K + 3) record ``_block_sample`` makes for the host
    nxt: jax.Array
    reqs: Dict[int, _Request]    # slot -> the sequence that was in the step
    t0: float                    # when the pass that dispatched it began its decode
    # what the program counted of its own work (ROUTING_COUNTERS, int32 on the device), where
    # it was traced with the bus on and its layers route; it lands with the tokens
    counted: Optional[jax.Array] = None


@dataclass
class _First:
    """A new sequence's first token in flight: sampled behind its prompt's
    program, not read by the host. It is read before the first step that holds
    the sequence becomes the step in flight (``_read_firsts``)."""

    req: _Request
    slot: int                    # where the prompt was prefilled; the sequence's, if it was activated
    tok: jax.Array               # (1,) int32 on the device
    t0: float = 0.0              # when a whole-prompt prefill began; 0.0 behind a final chunk


def _sample_tokens(logits, seeds, pos, temps):
    """Position-keyed sampling: token at position p for request seed s draws
    from fold_in(PRNGKey(s), p). temps == 0 -> greedy argmax."""

    def one(l, s, p, t):
        key = jax.random.fold_in(jax.random.PRNGKey(s), p)
        safe_t = jnp.where(t > 0, t, 1.0)
        sampled = jax.random.categorical(key, l / safe_t)
        return jnp.where(t > 0, sampled, jnp.argmax(l, -1))

    with jax.named_scope("head"):  # a trace-time name: the sampler is the head's last step
        return jax.vmap(one)(logits, seeds, pos, temps).astype(jnp.int32)


def _sample_step(logits, seeds, pos, temps):
    """The decode step's sampler: tokens for the positions after ``pos``, as
    the (max_batch, 1) column the next step's program takes, so that they
    need not leave the device in between."""
    return _sample_tokens(logits, seeds, pos + 1, temps)[:, None]


def _merge_token(toks, slot, tok):
    """The (max_batch, 1) tokens of the next decode step with ``tok`` (1,), a
    token that step's sampler did not make, in row ``slot``."""
    return toks.at[slot, 0].set(tok[0])


@dataclass(frozen=True)
class _BlockSpec:
    """``ServingEngine(block_diffusion=)`` checked: generation by diffusion over blocks of ``K``
    positions, ``n`` the positions a denoise pass fills at least."""

    K: int
    n: int
    dynamic: bool
    threshold: float
    mask_id: int

    @classmethod
    def of(cls, spec: dict, vocab: int) -> "_BlockSpec":
        known = {"block_length", "denoising_steps", "strategy", "threshold", "mask_id"}
        if set(spec) - known or not {"block_length", "mask_id"} <= set(spec):
            raise ValueError(f"block_diffusion= takes the keys {sorted(known)} (block_length and "
                             f"mask_id always), not {sorted(spec)}")
        K, S = int(spec["block_length"]), int(spec.get("denoising_steps", spec["block_length"]))
        strategy = spec.get("strategy", "low_confidence_dynamic")
        if K < 1 or not 1 <= S <= K:
            raise ValueError(f"block_diffusion=: block_length={K} must be >= 1 and denoising_steps={S} "
                             f"between 1 and it")
        if strategy not in ("low_confidence_dynamic", "low_confidence_static"):
            raise ValueError(f"block_diffusion=: strategy {strategy!r} is neither "
                             f"'low_confidence_dynamic' nor 'low_confidence_static'")
        if not 0 <= int(spec["mask_id"]) < vocab:
            raise ValueError(f"block_diffusion=: mask_id={spec['mask_id']} is no row of the "
                             f"embedding's {vocab}")
        return cls(K, -(-K // S), strategy == "low_confidence_dynamic",
                   float(spec.get("threshold", 0.9)), int(spec["mask_id"]))


def _block_sample(logits, toks, masked, pos, end, live, seeds, temps, *, spec: _BlockSpec):
    """What follows a pass over blocks, for every slot at once. A slot's state is what the pass
    was given: ``toks`` (B, 2 K) two adjacent blocks, the one to settle and the one being
    denoised; ``masked`` (B, K) bool, the second's positions not filled yet; ``pos`` (B,) the
    first position of the FIRST (K before the block being denoised: -K while that is a
    sequence's block at 0); ``end`` (B,) where the sequence's last block ends; ``live`` (B, 2)
    bool, which of the two the pass ran. ``logits`` (B, K, V) are the second block's rows'.

    The second block is DENOISED: each masked position's candidate ``x0`` (temperature 0: the
    argmax; else the position-keyed draw, ``fold_in(PRNGKey(seed), position)``: a position is
    filled once; never the mask token, whose logit is left out) and its confidence ``c =
    softmax(logits / T)[x0]``; filled are the ``n`` masked positions of largest ``c`` (ties to
    the lower position), or under the dynamic strategy every masked position with ``c >
    threshold`` where those are at least ``n``. Once no position is left masked the block is
    FINISHED and the slot moves on by K: the finished block is the next pass's first, to be
    settled there (its keys and values as that pass writes them, over its final tokens, are the
    ones that stay), beside the sequence's next block, all masked, as the second; after a
    sequence's LAST block the second is not live, and the pass that settles the last block
    denoises nothing. A first block has been settled by the pass that carried it: it is not
    live in the next one, so a settled row is written once. An idle slot has neither live and
    ``end`` 0. Returns the next pass's (toks, masked, pos, end, live) and the (B, 5 K + 3)
    int32 record the host reads a pass later: pos, live, the first block's tokens, the second
    going in (tokens, masked) and as denoised (tokens, masked)."""
    B, K, _ = logits.shape
    i32 = jnp.int32
    cur = toks[:, K:]

    def candidates(draws: bool):
        def one(l, s, p, t):
            l = l.astype(jnp.float32).at[spec.mask_id].set(-jnp.inf)  # the mask token is no candidate
            scaled = l / jnp.where(t > 0, t, 1.0)
            x0 = jnp.argmax(l, -1)
            if draws:
                drawn = jax.random.categorical(jax.random.fold_in(jax.random.PRNGKey(s), p), scaled)
                x0 = jnp.where(t > 0, drawn, x0)
            return x0.astype(i32), jnp.exp(scaled[x0] - jax.nn.logsumexp(scaled))

        return lambda: jax.vmap(jax.vmap(one, in_axes=(0, None, 0, None)))(logits, seeds, at, temps)

    with jax.named_scope("unmask"):
        at = (pos + K)[:, None] + jnp.arange(K, dtype=i32)[None, :]
        # a pass whose every sequence is greedy draws nothing: the noise of a draw is a number a
        # vocabulary entry and row (2.3 of the sampler's 3.3 ms at 256 rows of 151,936: PR 41)
        x0, c = jax.lax.cond(jnp.any(temps > 0), candidates(True), candidates(False))
        n_fill = jnp.minimum(spec.n, jnp.sum(masked, -1, dtype=i32))[:, None]
        c_masked = jnp.where(masked, c, -jnp.inf)
        rank = jnp.argsort(jnp.argsort(-c_masked, axis=-1, stable=True), axis=-1, stable=True)
        fill = masked & (rank < n_fill)
        if spec.dynamic:
            high = masked & (c > spec.threshold)
            fill = jnp.where(jnp.sum(high, -1, dtype=i32)[:, None] >= n_fill, high, fill)
        toks_dn, masked_dn = jnp.where(fill, x0, cur), masked & ~fill
        done = live[:, 1] & ~jnp.any(masked_dn, -1)  # finished by this pass: settled in the next
        opens = done & (pos + 2 * K < end)           # ... beside the sequence's next block
        rec = jnp.concatenate([pos[:, None], live.astype(i32), toks[:, :K], cur, masked.astype(i32),
                               toks_dn, masked_dn.astype(i32)], 1)
        # the block as it stands, twice: to be settled if it is finished (else not live), and
        # to be denoised further, where the sequence's next block, all masked, does not take its place
        nxt = jnp.concatenate([toks_dn, jnp.where(done[:, None], spec.mask_id, toks_dn)], 1)
        return (nxt.astype(i32), jnp.where(done[:, None], opens[:, None], masked_dn),
                jnp.where(done, pos + K, pos), end,
                jnp.stack([done, jnp.where(done, opens, live[:, 1])], 1), rec)


def _merge_block(toks, masked, pos, end, live, slot, row_toks, row_masked, row_pos, row_end, row_live):
    """The next pass's blocks with slot ``slot``'s replaced: a sequence activated since the
    last dispatch (nothing to settle; its prompt's tail and mask tokens to denoise), or a slot
    gone idle."""
    return (toks.at[slot].set(row_toks), masked.at[slot].set(row_masked),
            pos.at[slot].set(row_pos), end.at[slot].set(row_end), live.at[slot].set(row_live))


class ServingEngine:
    """Continuous-batching inference over a models.litgpt.GPT (or MoEGPT), or
    over any model whose ``serving()`` gives its layers (serving/runner.py):
    what state the engine keeps — pages of every position, pages of a window
    that are freed as they leave it, per-slot recurrent arrays — follows from
    what those layers declare, not from a keyword here. With recurrent layers
    ``prefix_sharing=True`` and ``draft_gpt=`` raise ValueError (docs/serving.md).

    max_batch   decode slots (sequences packed into one decode step)
    page_size   tokens per KV page
    n_pages     pool size per layer (default: full residency for max_batch
                sequences of max_seq tokens, plus the reserved null page)
    max_seq     per-sequence length cap (prompt + generated)

    Throughput stages (all off by default; see the module docstring):

    prefix_sharing  consult/populate a content-keyed PrefixCache at admission
    chunk_tokens    split prompts longer than this into page-aligned chunks
                    (default max_seq: whole-prompt prefill, never chunked
                    unless a prefix hit leaves an unaligned-free suffix)
    prefill_budget  chunk-prefill tokens per engine iteration (default
                    chunk_tokens: one chunk per iteration)
    draft_gpt       draft model for speculative decoding (same vocab; its
                    KV pool shares the target allocator page-for-page)
    spec_k          draft tokens proposed per iteration (default 4 with a
                    draft, 0 without)
    preemption      allow spilling batch-lane sequences for interactive
                    admission / SLO burn (on; only bites with lanes in use)
    quantize        weight-only quantization applied before tracing:
                    None/"none" or "int8" (int8 x bf16 decode compute via
                    the Pallas dequant-in-kernel linear on TPU)
    block_diffusion generation by diffusion over blocks (docs/serving.md): a dict
                    ``{"block_length": K, "denoising_steps": S, "strategy":
                    "low_confidence_dynamic" | "low_confidence_static",
                    "threshold": tau, "mask_id": m}``. A pass carries a block of K
                    positions a sequence and unmasks some of them; the block's keys
                    and values stay only from a run of it with none masked, which
                    rides in the next block's first pass.
                    Needs layers that cache keys and values of every position;
                    ``prefix_sharing`` and ``draft_gpt`` are refused with it
    """

    def __init__(self, gpt, *, max_batch: int = 8, page_size: int = 16,
                 n_pages: Optional[int] = None, max_seq: Optional[int] = None,
                 dtype=jnp.bfloat16, min_bucket: Optional[int] = None,
                 slo: Optional[SLOPolicy] = None, prefix_sharing: bool = False,
                 chunk_tokens: Optional[int] = None,
                 prefill_budget: Optional[int] = None, draft_gpt=None,
                 spec_k: Optional[int] = None, preemption: bool = True,
                 quantize: Optional[str] = None, block_diffusion: Optional[dict] = None):
        # weight-only quantization must precede BOTH the program tracing and
        # the named_parameters snapshot below (runner.quantize_for_serving)
        gpt = quantize_for_serving(gpt, quantize)
        cfg = gpt.cfg
        self.gpt = gpt
        self.cfg = cfg
        self.max_batch = max_batch
        self.page_size = page_size
        self.max_seq = max_seq or cfg.block_size
        self.block = (_BlockSpec.of(block_diffusion, getattr(cfg, "padded_vocab_size", cfg.vocab_size))
                      if block_diffusion is not None else None)
        self.runner = PagedGPTRunner(gpt, page_size=page_size,
                                     block_length=self.block.K if self.block else None)
        # a model with a position table can serve no more positions than it has
        rope_rows = getattr(self.runner.model, "max_positions", None)
        if rope_rows is not None and self.max_seq > rope_rows:
            raise ValueError(
                f"max_seq={self.max_seq} exceeds the model's rope cache "
                f"({rope_rows} positions); build the GPT with a larger block_size")
        if self.max_seq % page_size:
            raise ValueError(f"max_seq={self.max_seq} must be a multiple of "
                             f"page_size={page_size}")
        self.n_pages_max = self.max_seq // page_size  # page-table width
        if n_pages is None:
            n_pages = 1 + max_batch * self.n_pages_max
        self.min_bucket = max(page_size, min_bucket or page_size)
        # ONE bucket ladder (compile_service/buckets.py) owns the rounding
        # rule, page-alignment validation, and the per-rung traffic stats
        # that used to live in a separate ShapeKeyedMRU of _BucketEntry
        # records — prompt buckets, the bucketed TrainStep, and stored
        # artifact keys all route through the same object
        self.ladder = BucketLadder(self.min_bucket, self.max_seq,
                                   page_size=page_size)
        self.dtype = dtype

        if chunk_tokens is None:
            chunk_tokens = self.max_seq
        if chunk_tokens % page_size or not (self.min_bucket <= chunk_tokens
                                            <= self.max_seq):
            raise ValueError(
                f"chunk_tokens={chunk_tokens} must be a page-aligned length "
                f"in [{self.min_bucket}, {self.max_seq}]")
        self.chunk_tokens = chunk_tokens
        # final (short) chunks round on a capped child of the SAME ladder,
        # so chunk programs specialize over strictly fewer rungs
        self.chunk_ladder = self.ladder.subladder(chunk_tokens)
        self.prefill_budget = prefill_budget or chunk_tokens
        if self.prefill_budget < page_size:
            raise ValueError(f"prefill_budget={self.prefill_budget} must be "
                             f">= page_size={page_size}")
        self.preemption = preemption

        # the cached state follows from what the model's layers declare
        layers = [layer.cache for layer in self.runner.model.layers]
        self.recurrent = any(isinstance(d, Recurrent) for d in layers)
        self.latent = any(isinstance(d, PagedLatent) for d in layers)
        # state that is not pages of every position: say so rather than run wrongly
        if self.recurrent:
            if prefix_sharing:
                raise ValueError(
                    "prefix_sharing=True cannot serve a model with recurrent layers: a shared "
                    "page holds keys and values, not the scan state at its boundary, so a "
                    "request that skipped the shared prefix would start from the wrong state")
            if draft_gpt is not None:
                raise ValueError(
                    "draft_gpt= (speculative decoding) cannot serve a model with recurrent "
                    "layers: verify writes k+1 positions and rolls the rejected ones back by "
                    "not committing them, and a scan state cannot be rolled back")
        if any(isinstance(d, PagedKV) and d.window for d in layers):
            if prefix_sharing:
                raise ValueError(
                    "prefix_sharing=True cannot serve a model with window layers: a prefix hit "
                    "shares the pages of every position only, so the window layers would find "
                    "no keys for the end of the prefix that was skipped")
            if draft_gpt is not None:
                raise ValueError(
                    "draft_gpt= (speculative decoding) cannot serve a model with window "
                    "layers: verify writes k+1 positions and window pages are taken for one "
                    "position a step, so the others would be written to no page")
        if self.block is not None:
            if not self.runner.blocks:
                raise ValueError(
                    "block_diffusion= cannot serve a model with window, recurrent or latent layers: "
                    "a pass writes two blocks of K positions and attends them under a mask of its own "
                    "(the last position of a row's block), which only layers that cache keys "
                    "and values of every position run (serving/runner.py: DenseBlock.verify)")
            if prefix_sharing:
                raise ValueError(
                    "prefix_sharing=True cannot go with block_diffusion=: a prompt's last L mod K "
                    "tokens are not prefilled (they open the first generated block), so the page "
                    "they share with the block holds rows no prompt alone determines")
            if draft_gpt is not None:
                raise ValueError(
                    "draft_gpt= (speculative decoding) cannot go with block_diffusion=: a pass "
                    "already yields up to K tokens a sequence, and the verify step's accept rule "
                    "is the one-token-a-step sampler's")
            if self.chunk_tokens % self.block.K:
                raise ValueError(
                    f"chunk_tokens={self.chunk_tokens} must be a multiple of block_length="
                    f"{self.block.K}: a prompt chunk ends on a block's edge, or its last rows "
                    f"would attend keys the next chunk has yet to write")
        self.params = {k: p.data for k, p in gpt.named_parameters()}
        # what the programs are given beside the weights goes to the weights' device, committed
        # to it as what the programs return is (``_upload``)
        self._device = next(iter(next(iter(self.params.values())).devices()))
        self.cache = PagedKVCache(
            len(layers), n_pages, page_size, n_kv_heads=0, head_dim=0, dtype=dtype, layers=layers,
            max_batch=max_batch, device=self._device,
            # the most pages one prefill program writes: a whole chunk, or its bucket
            prefill_pages=max(chunk_tokens, self.ladder.bucket_for(chunk_tokens)) // page_size)
        self.window = self.cache.window
        self._sampler = jax.jit(_sample_tokens)
        self._step_sampler = jax.jit(_sample_step)
        self._merge = jax.jit(_merge_token)
        if self.block is not None:
            K, spec = self.block.K, self.block

            def serve_unmask(*block):  # the executable's name in a device trace
                return _block_sample(*block, spec=spec)

            self._block_sampler = jax.jit(serve_unmask)
            self._block_merge = jax.jit(_merge_block)
            # every slot's two blocks (``_block_sample``: tokens, the second's masked flags, the
            # first's position, the sequence's end, which are live): on the device from pass to
            # pass, the sampler's output the next pass's input
            self._idle_block = (np.zeros((2 * K,), np.int32), np.zeros((K,), bool), np.int32(0),
                                np.int32(0), np.zeros((2,), bool))
            self._blk = tuple(jax.device_put(np.stack([a] * max_batch), self._device)
                              for a in self._idle_block)
        # with it set a retired request's result carries the block going into each of its passes
        self.record_block_states = False

        self.prefix = (PrefixCache(self.cache.allocator, page_size)
                       if prefix_sharing else None)

        self.draft_gpt = draft_gpt
        self.spec_k = (int(spec_k) if spec_k is not None
                       else (4 if draft_gpt is not None else 0))
        if self.spec_k < 0:
            raise ValueError(f"spec_k={self.spec_k} must be >= 0")
        if self.spec_k and draft_gpt is None:
            raise ValueError("spec_k > 0 requires a draft_gpt")
        if draft_gpt is not None:
            dcfg = draft_gpt.cfg
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab_size={dcfg.vocab_size} != target "
                    f"vocab_size={cfg.vocab_size}")
            if draft_gpt.cos.shape[0] < self.max_seq:
                raise ValueError(
                    f"draft rope cache ({draft_gpt.cos.shape[0]} positions) "
                    f"shorter than max_seq={self.max_seq}")
            # the draft pool SHARES the target allocator: one allocation and
            # one page table cover both models, so sharing/CoW/preemption
            # bookkeeping never runs twice
            self.draft_runner = PagedGPTRunner(draft_gpt, page_size=page_size)
            self.draft_cache = PagedKVCache(
                dcfg.n_layer, n_pages, page_size, n_kv_heads=0, head_dim=0, dtype=dtype,
                allocator=self.cache.allocator, device=self._device,
                layers=[layer.cache for layer in self.draft_runner.model.layers])
            self.draft_params = {k: p.data
                                 for k, p in draft_gpt.named_parameters()}
        else:
            self.draft_cache = None
            self.draft_runner = None
            self.draft_params = None

        # host-side packed decode state; pos/toks change every step and are
        # re-uploaded, while seeds/temps/page tables only change at
        # (un)assignment — their device copies are cached under _pt_dirty
        self._page_tables = np.zeros((max_batch, self.n_pages_max), np.int32)
        self._win_tables = np.zeros((max_batch, self.n_pages_max), np.int32)
        self._pos = np.zeros((max_batch,), np.int32)
        self._toks = np.zeros((max_batch,), np.int32)
        self._seeds = np.zeros((max_batch,), np.uint32)
        self._temps = np.zeros((max_batch,), np.float32)
        self._pt_dev = None
        self._seeds_dev = None
        self._temps_dev = None
        self._pt_dirty = True
        self._slots: List[Optional[_Request]] = [None] * max_batch
        self._inflight: Optional[_Step] = None  # the plain decode path's step in flight
        self._firsts: deque = deque()  # _First: first tokens not read yet, oldest first
        # slot -> (1,) token on the device that the next step is fed in place of the sampler's
        # output of the step before: every sequence activated since the last dispatch
        self._feeds: Dict[int, jax.Array] = {}
        # a chunk's program takes the decode step's rows too, and in a pass with a chunk due
        # the step rides in it (one read of the weights for both), where every layer of the
        # model can run both kinds of rows at once and the decode path is the plain one; the
        # rows of a chunk dispatch no step rides in are idle slots, all of them
        # ... and a pass's two blocks a slot ride in no chunk's program: the two run apart
        self._mixes = self.runner.mixes and draft_gpt is None and self.block is None
        self._idle_rows = (
            self._upload(self._toks[:, None]),
            tuple(self._upload(self._page_tables) for _ in self.runner.page_kinds),
            self._upload(self._pos)) if self._mixes else None

        self._pending: deque = deque()        # interactive lane (admits first)
        self._pending_batch: deque = deque()  # batch lane (preemptible)
        self._chunking: Dict[int, _Request] = {}  # slot -> mid-chunk-prefill
        self._admit_counter = 0
        self._lock = threading.Lock()
        self._next_id = 0
        # submitted-but-unresolved count: _has_work()/drain() key off this
        # rather than scanning pending+slots, which is momentarily EMPTY
        # between a pop from the queue and the slot assignment (a drain
        # racing the loop thread would return mid-prefill otherwise)
        self._outstanding = 0
        self._stopped = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.decode_steps = 0
        self.peak_pages_in_use = 0
        # stage counters (host truth; mirrored onto the serve.* bus when
        # observability is on — benchmark rates derive from the bus copies)
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.preempted = 0
        self.resumed = 0
        self.block_passes = 0    # passes over blocks fetched, and those of them that settled
        self.block_commits = 0   # a block for at least one sequence
        self.blocks_done = 0     # blocks settled, over the sequences, and those of them whose
        self.block_settles_joined = 0  # pass denoised the sequence's next block too

        # SLO measurement substrate (observability/slo.py): a declarative
        # policy gets a sliding-window monitor (breach events/counters) and
        # per-request SLO-met accounting at retirement — the goodput gauge
        # ROADMAP #2's admission lanes will schedule against. Without a
        # policy the retirement path pays one `is None` test.
        self.slo_policy = slo
        self.slo_monitor = SLOMonitor(slo, source="serving") if slo is not None else None
        self.requests_retired = 0       # non-cancelled retirements
        self.requests_slo_met = 0

        # OOM forensics: hand the memory watcher a live view of the page
        # pool so a RESOURCE_EXHAUSTED bundle names pool pressure and
        # fragmentation, not just device bytes (last engine wins — one
        # engine per process is the deployed shape)
        _obs_mem.register_pool_state(self._pool_state)

    # -- public API -------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32, *, temperature: float = 0.0,
               seed: Optional[int] = None, eos_id: Optional[int] = None,
               lane: str = "interactive") -> Future:
        """Enqueue one generation request; thread-safe. The Future resolves
        to a RequestResult (or a ValueError for an inadmissible request).
        lane="interactive" admits ahead of lane="batch"; batch sequences may
        be preempted (spilled and later resumed, stream unchanged) when an
        interactive request is page-starved or the SLO budget is burning."""
        fut: Future = Future()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with self._lock:
            rid = self._next_id
            self._next_id += 1
        if lane not in ("interactive", "batch"):
            fut.set_exception(ValueError(
                f"request {rid}: lane={lane!r} must be 'interactive' or 'batch'"))
            return fut
        L = int(prompt.shape[0])
        worst = self._pages_needed(L, max_new_tokens)
        usable = self.cache.n_pages - 1
        if L < 1 or self._life(L, max_new_tokens) > self.max_seq or max_new_tokens < 1:
            fut.set_exception(ValueError(
                f"request {rid}: prompt_len={L} + max_new_tokens={max_new_tokens} "
                f"must fit max_seq={self.max_seq} (and both be >= 1)"
                + (f", the last block of {self.block.K} whole" if self.block else "")))
            return fut
        if worst > usable:
            fut.set_exception(ValueError(
                f"request {rid}: needs {worst} pages, pool has {usable}"))
            return fut
        # seeds canonicalized mod 2^32 (the packed sampler array is uint32);
        # inference.generate applies the same mask, keeping the documented
        # solo-vs-batched stream equivalence for any Python int seed
        req = _Request(rid, prompt, max_new_tokens, float(temperature),
                       int(seed if seed is not None else rid) & 0xFFFFFFFF,
                       eos_id, fut, time.perf_counter(), lane=lane)
        if _obs.enabled():
            req.trace_id = _obs_trace.new_trace_id()
        with self._lock:
            if self._stopped:
                # stop() already flushed the queue; a late submit must fail
                # loudly rather than enqueue a Future nothing will resolve
                fut.set_exception(RuntimeError("serving engine stopped"))
                return fut
            (self._pending if lane == "interactive"
             else self._pending_batch).append(req)
            self._outstanding += 1
        if _obs.enabled():
            _obs_metrics.record_serve("requests")
            _obs_trace.trace_event(req.trace_id, "submitted",
                                   request=rid, lane=lane, prompt_len=L,
                                   max_new=max_new_tokens)
        return fut

    def start(self) -> None:
        """Run the scheduling loop on a background thread."""
        if self._thread is not None:
            return
        with self._lock:
            self._stopped = False
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, name="tt-serving",
                                        daemon=True)
        self._thread.start()

    def stop(self, drain: bool = False) -> None:
        """Stop the loop thread. drain=True finishes outstanding requests
        first; otherwise every in-flight and pending Future is FAILED (with
        pages returned) — a stopped engine must never leave a waiter
        hanging on a Future that nothing will ever resolve."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if drain:
            self._stop.clear()
            self.drain()
            self._stop.set()
        self._land()  # what the step in flight finished is delivered, not failed
        exc = RuntimeError("serving engine stopped")
        for i, req in enumerate(self._slots):
            if req is not None:
                self._fail(req, exc)
                self._clear_slot(i)
        for req in list(self._chunking.values()):
            self._fail(req, exc)
        self._chunking.clear()
        with self._lock:
            # flag + flush under ONE lock section: a racing submit either
            # lands before the flush (failed here) or sees _stopped and
            # fails itself — no window leaves an unresolvable Future
            self._stopped = True
            pending = list(self._pending) + list(self._pending_batch)
            self._pending = deque()
            self._pending_batch = deque()
        for req in pending:
            self._fail(req, exc)

    def drain(self) -> None:
        """Block until every submitted request resolved. With the
        background thread running this only WAITS (stepping inline too
        would race the thread over slots and pool state); without it, the
        loop runs inline (deterministic test/benchmark driver)."""
        if self._thread is not None:
            while self._has_work():
                time.sleep(1e-3)
            return
        while self._has_work():
            self._step_once()
        self._land()  # a step whose every token is thrown away (an early end) may be left

    def warmup(self, prompt_lens, max_new_tokens: int = 3) -> None:
        """Pre-compile the decode step and the prefill bucket for each
        prompt length (steady state then never recompiles). Three tokens a
        request: the prefill's, one from a step fed it on the device by the
        merge and one from a step fed the sampler's output of the step before."""
        for L in prompt_lens:
            self.submit(np.zeros((L,), np.int32), max_new_tokens)
        self.drain()

    def stats(self) -> dict:
        usable = self.cache.n_pages - 1
        out = {
            "pages_in_use": self.cache.allocator.n_used,
            "page_pool_utilization": round(self.cache.utilization(), 4),
            "peak_page_pool_utilization": round(self.peak_pages_in_use / usable, 4)
            if usable else 0.0,
            "page_fragmentation": round(self.page_fragmentation(), 4),
            "active": sum(1 for s in self._slots if s is not None),
            "pending": len(self._pending) + len(self._pending_batch),
            "chunking": len(self._chunking),
            "decode_steps": self.decode_steps,
            "prefill_buckets": self.ladder.mru(),
            "bucket_hits": self.ladder.hits(),
            "prefix_hits": self.prefix_hits,
            "prefix_tokens_saved": self.prefix_tokens_saved,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "preempted": self.preempted,
            "resumed": self.resumed,
        }
        if self.block is not None:
            out.update(block_passes=self.block_passes, block_commits=self.block_commits,
                       blocks_done=self.blocks_done, block_settles_joined=self.block_settles_joined)
        if self.prefix is not None:
            out["prefix_cache_pages"] = len(self.prefix)
        if self.slo_policy is not None:
            out["requests_retired"] = self.requests_retired
            out["requests_slo_met"] = self.requests_slo_met
            out["goodput"] = (round(self.requests_slo_met / self.requests_retired, 4)
                              if self.requests_retired else None)
            out["slo"] = self.slo_monitor.status()
        return out

    def page_fragmentation(self) -> float:
        """Internal fragmentation of the page pool: the fraction of
        allocated page capacity NOT holding resident tokens. Worst-case
        lifetime reservation at admission means a request holds
        ``bucket + growth`` pages from its first prefill, so early in a
        long generation most of its reserved capacity is air — this gauge
        is the difference between "the pool is full" and "the pool is full
        of tokens", which picks between raising n_pages and tightening
        admission."""
        n_used = self.cache.allocator.n_used
        if not n_used:
            return 0.0
        resident = 0
        # lock-free slot scan: a torn read skews one gauge sample, while
        # taking self._lock here would deadlock callers that already hold
        # it (the post-mortem path can fire from anywhere)
        for req in list(self._slots):
            if req is None:
                continue
            prompt = req.prompt_eff if req.prompt_eff is not None else req.prompt
            resident += len(prompt) + len(req.tokens)
        frac = 1.0 - resident / (n_used * self.page_size)
        return max(0.0, min(1.0, frac))

    def _pool_state(self) -> dict:
        """Page-pool snapshot for OOM forensic bundles (memory_watch)."""
        usable = self.cache.n_pages - 1
        return {
            "pages_in_use": self.cache.allocator.n_used,
            "n_pages": self.cache.n_pages,
            "page_size": self.page_size,
            "utilization": round(self.cache.utilization(), 4),
            "peak_utilization": (round(self.peak_pages_in_use / usable, 4)
                                 if usable else 0.0),
            "fragmentation": round(self.page_fragmentation(), 4),
            "active": sum(1 for s in self._slots if s is not None),
            "pending": len(self._pending) + len(self._pending_batch),
        }

    def goodput(self) -> Optional[float]:
        """Cumulative fraction of retired (non-cancelled) requests whose
        per-request SLO-met flag was True; None without a policy or before
        the first retirement. (The SLOMonitor additionally keeps a
        sliding-window goodput for burn-rate/breach evaluation.)"""
        if self.slo_policy is None or not self.requests_retired:
            return None
        return self.requests_slo_met / self.requests_retired

    def reset_slo_accounting(self) -> None:
        """Zero the goodput counters and restart the sliding-window monitor
        (same policy). Benchmarks call this after warmup() so roll-out
        traffic doesn't pollute goodput or the breach windows — the engine
        owns every field involved, so new accounting state added here can't
        silently desync external callers."""
        self.requests_retired = 0
        self.requests_slo_met = 0
        if self.slo_policy is not None:
            self.slo_monitor = SLOMonitor(self.slo_policy, source="serving")

    # -- scheduling loop --------------------------------------------------
    def _has_work(self) -> bool:
        return self._outstanding > 0

    def _loop(self) -> None:
        while not self._stop.is_set():
            if not self._has_work():
                self._land()  # only a step whose every token is thrown away can be left
                # one phase per idle stretch, not per sleep: an idle engine
                # must not flood the bus's ring
                with _obs_runtime.phase("engine:wait"):
                    while not (self._has_work() or self._stop.is_set()):
                        time.sleep(1e-3)
                continue
            try:
                self._step_once()
            except Exception as e:  # pragma: no cover - scheduler-bug net
                # per-request failures are contained in _prefill/_decode
                # (futures failed, pages freed); anything reaching here is a
                # scheduler bug — keep the thread alive for other requests
                # rather than silently hanging every future forever
                import warnings

                warnings.warn(f"serving loop error (contained): {e!r}")
                time.sleep(1e-2)

    def _life(self, L: int, max_new: int) -> int:
        """Positions a request writes over its lifetime: prompt and answer, and under
        ``block_diffusion=`` the last block whole."""
        K = self.block.K if self.block is not None else 1
        return -(-(L + max_new) // K) * K

    def _pages_needed(self, L: int, max_new: int) -> int:
        """Worst-case pages over the request lifetime: the bucketed prefill
        writes bucket//page_size pages, growth extends to L+max_new tokens.
        Reserving the max at admission means decode can never hit a
        mid-flight out-of-pages (the admission policy; docs/serving.md).
        Window pages need no reservation a request: their pool holds every
        slot's worst case (``__init__``), which is small by construction."""
        life = PagedKVCache.pages_for(self._life(L, max_new), self.page_size)
        if self.block is not None:  # whole blocks of the prompt go through the chunk program
            whole = L // self.block.K * self.block.K
            return max(life, self._final_chunk_end(whole, 0) // self.page_size) if whole else life
        return max(self.ladder.bucket_for(L) // self.page_size, life)

    def _step_once(self) -> None:
        obs_on = _obs.enabled()
        with (_obs_runtime.phase(
                "engine:iteration", step=self.decode_steps,
                active=sum(1 for s in self._slots if s is not None),
                chunking=len(self._chunking),
                pending=len(self._pending) + len(self._pending_batch))
              if obs_on else _NULL):
            # a prefill that admission starts opens its own engine:prefill
            # inside this phase, so admit's own time is what is left of it
            with _obs_runtime.phase("engine:admit"):
                self._maybe_preempt_for_slo()
                self._admit()
            if not self._advance_prefills():  # else the decode step rode in a chunk's program
                self._decode()

    def _admit(self) -> None:
        while True:
            free_slots = [i for i, s in enumerate(self._slots)
                          if s is None and i not in self._chunking]
            if not free_slots:
                return
            req = queue = None
            with self._lock:
                for q in (self._pending, self._pending_batch):
                    while q and q[0].future.cancelled():
                        # cancelled while queued: drop before allocating
                        # anything (a preempted victim's pages were already
                        # spilled, so there is nothing to return either)
                        q.popleft()
                        self._outstanding -= 1
                    if req is None and q:
                        req, queue = q[0], q
            if req is None:
                return
            if not self._reserve_pages(req):
                # head-of-line within the lane pair: interactive starvation
                # may evict batch-lane victims; otherwise wait for retirements
                if (req.lane == "interactive" and self.preemption
                        and self._preempt_one()):
                    continue
                return
            with self._lock:
                queue.popleft()
            self.peak_pages_in_use = max(self.peak_pages_in_use,
                                         self.cache.allocator.n_used)
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            if req.admit_mode == "hit":
                self._admit_hit(req, free_slots[0])
            elif req.admit_mode == "chunk":
                self._start_chunk(req, free_slots[0])
            elif req.admit_mode == "block":  # a prompt shorter than a block: nothing to prefill
                self._activate_block(req, free_slots[0])
            else:
                with self._prefill_phase(req):
                    self._prefill(req, free_slots[0])

    def _reserve_pages(self, req: _Request) -> bool:
        """Route one request (prefix hit / chunked / whole-prompt prefill)
        and reserve its worst-case pages: shared pages come from the prefix
        cache (already incref'd by match), private ones from the free-list.
        On shortage every side effect is undone and False is returned — the
        request stays at its queue head."""
        if self.block is not None:
            return self._reserve_block_pages(req)
        ps = self.page_size
        resumed = bool(req.tokens)
        # a resumed victim re-prefills prompt + all-but-the-last committed
        # token: the last one re-enters decode exactly where the spill cut it
        prompt_eff = (req.prompt if not resumed else
                      np.concatenate([req.prompt,
                                      np.asarray(req.tokens[:-1], np.int32)]))
        L_eff = len(prompt_eff)
        lifetime = PagedKVCache.pages_for(
            len(req.prompt) + req.max_new_tokens, ps)
        shared: List[int] = []
        covered = 0
        if self.prefix is not None:
            shared, covered = self.prefix.match(prompt_eff)
            if req.trace_id is not None:
                _obs_trace.trace_event(req.trace_id, "prefix_lookup",
                                       request=req.request_id, covered=covered,
                                       shared_pages=len(shared),
                                       hit=bool(shared))
        n_shared = len(shared)
        if covered == L_eff and n_shared:
            # full coverage: no prefill at all. The first decode step
            # re-writes position L_eff-1 (the copy-on-write trigger) and
            # recovers the first-token logits bit-identically; a resumed
            # victim needs no logits, only a CoW fork if its next write
            # lands in the shared tail page.
            fork_n = 0 if (resumed and L_eff % ps == 0) else 1
            priv = lifetime - n_shared
            mode = "hit"
        elif covered > 0 or L_eff > self.chunk_tokens:
            end = self._final_chunk_end(L_eff, covered)
            priv = max(lifetime, end // ps) - n_shared
            fork_n = 0
            mode = "chunk"
        else:
            priv = max(lifetime, self.ladder.bucket_for(L_eff) // ps)
            fork_n = 0
            mode = "prefill"
        need = priv + fork_n
        if not self.cache.allocator.can_alloc(need):
            # cache-only pages are reclaimable: evicting them drops the
            # cache's reference, never a live sequence's (or ours — the
            # matched pages above hold our incref and survive eviction)
            if self.prefix is not None:
                self.prefix.evict_until(need)
            if not self.cache.allocator.can_alloc(need):
                if shared:
                    self.cache.allocator.free(shared)
                return False
        req.prompt_eff = prompt_eff
        req.covered = covered
        req.n_shared = n_shared
        req.admit_mode = mode
        req.pages = shared + (self.cache.allocator.alloc(priv) if priv else [])
        if not req.t_admit:
            req.t_admit = time.perf_counter()
        if req.trace_id is not None:
            _obs_trace.trace_event(
                req.trace_id, "admitted", request=req.request_id, mode=mode,
                covered=covered, shared_pages=n_shared, pages=len(req.pages),
                queued_ms=round((req.t_admit - req.t_submit) * 1e3, 3))
        return True

    def _reserve_block_pages(self, req: _Request) -> bool:
        """``_reserve_pages`` under ``block_diffusion=``: the whole blocks of the prompt (of a
        resumed victim: of prompt and committed tokens, which end on a block's edge) go
        through the chunk program, block-causal; the last ``L mod K`` tokens open the first
        generated block. Pages for the whole lifetime, the last block whole."""
        K, ps = self.block.K, self.page_size
        whole = (np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
                 if req.tokens else req.prompt)
        Lb = len(whole) // K * K
        need = PagedKVCache.pages_for(self._life(len(req.prompt), req.max_new_tokens), ps)
        if Lb:
            need = max(need, self._final_chunk_end(Lb, 0) // ps)
        if not self.cache.allocator.can_alloc(need):
            return False
        req.prompt_eff, req.block_tail = whole[:Lb], whole[Lb:]
        req.covered = req.n_shared = 0
        req.admit_mode = "chunk" if Lb else "block"
        req.pages = self.cache.allocator.alloc(need)
        if not req.t_admit:
            req.t_admit = time.perf_counter()
        if req.trace_id is not None:
            _obs_trace.trace_event(
                req.trace_id, "admitted", request=req.request_id, mode=req.admit_mode,
                covered=0, shared_pages=0, pages=len(req.pages),
                queued_ms=round((req.t_admit - req.t_submit) * 1e3, 3))
        return True

    def _final_chunk_end(self, L_eff: int, covered: int) -> int:
        """Absolute end of the final chunk's page write-out: intermediate
        chunks are exactly chunk_tokens, the final one rounds up on the
        capped chunk ladder — unless that rung would cross max_seq (and so
        the rope table), in which case it falls back to the exact page-
        aligned remainder."""
        C = self.chunk_tokens
        s = covered + ((L_eff - covered - 1) // C) * C
        rung = self.chunk_ladder.bucket_for(L_eff - s)
        if s + rung > self.max_seq:
            rung = PagedKVCache.pages_for(L_eff - s, self.page_size) * self.page_size
        return s + rung

    def _admit_hit(self, req: _Request, slot: int) -> None:
        """Admit a fully prefix-covered request without running prefill."""
        ps = self.page_size
        resumed = bool(req.tokens)
        L_eff = len(req.prompt_eff)
        if not (resumed and L_eff % ps == 0):
            # the first write (position L_eff-1 fresh, L_eff resumed) lands
            # in the last shared page: detach it now. fork() only pays the
            # device copy when other owners remain.
            old = req.pages[req.n_shared - 1]
            new = self.cache.allocator.fork(old)
            if new != old:
                req.pages[req.n_shared - 1] = new
                try:
                    self.cache.copy_page(old, new)
                    if self.draft_cache is not None:
                        self.draft_cache.copy_page(old, new)
                except Exception as e:
                    self._fail(req, e)
                    self._drop_lost_pools(e)
                    return
        saved = L_eff if resumed else L_eff - 1
        self.prefix_hits += 1
        self.prefix_tokens_saved += saved
        if _obs.enabled():
            _obs_metrics.record_serve("prefix_hits")
            _obs_metrics.record_serve("prefix_tokens_saved", delta=saved)
        if resumed:
            self._on_resume(req)
            self._activate(req, slot, pos=L_eff, tok=req.tokens[-1])
        else:
            # t_first stays 0.0: TTFT is stamped when the first token
            # commits in decode (the re-decoded prompt token is not output)
            self._activate(req, slot, pos=L_eff - 1,
                           tok=int(req.prompt_eff[-1]))

    def _start_chunk(self, req: _Request, slot: int) -> None:
        """Reserve a slot for chunked prefill; chunks run under the
        per-iteration token budget in _advance_prefills."""
        req.chunk_pos = req.covered
        if req.covered:
            self.prefix_hits += 1
            self.prefix_tokens_saved += req.covered
            if _obs.enabled():
                _obs_metrics.record_serve("prefix_hits")
                _obs_metrics.record_serve("prefix_tokens_saved",
                                          delta=req.covered)
        self._chunking[slot] = req

    def _on_resume(self, req: _Request) -> None:
        self.resumed += 1
        if _obs.enabled():
            _obs_metrics.record_serve("resumed", event=True,
                                      request=req.request_id,
                                      n_tokens=len(req.tokens))
            _obs_trace.trace_event(req.trace_id, "resumed",
                                   request=req.request_id,
                                   n_tokens=len(req.tokens))

    def _preempt_one(self) -> bool:
        """Spill the most recently admitted batch-lane sequence: free its
        pages (shared ones just decref — the prefix cache keeps them warm)
        and requeue it at the FRONT of the batch lane for resume."""
        def newest():
            batch = [i for i, r in enumerate(self._slots) if r is not None and r.lane == "batch"]
            return max(batch, key=lambda i: self._slots[i].admit_seq, default=None)

        if newest() is None:
            return False  # and nothing has landed: a page-starved head of line drains no pipeline
        self._land()  # the victim keeps the token it has in flight, which may have been its last
        victim = newest()
        if victim is None:
            return False
        req = self._slots[victim]
        self._free_pages(req)
        self._clear_slot(victim)
        with self._lock:
            self._pending_batch.appendleft(req)
        self.preempted += 1
        if _obs.enabled():
            _obs_metrics.record_serve("preempted", event=True,
                                      request=req.request_id,
                                      n_tokens=len(req.tokens))
            _obs_trace.trace_event(req.trace_id, "preempted",
                                   request=req.request_id,
                                   n_tokens=len(req.tokens))
        return True

    def _maybe_preempt_for_slo(self) -> None:
        """Burn-rate-driven preemption: when the SLO monitor reports a
        breached or burning target while interactive requests queue, shed
        one batch sequence per iteration to shorten the interactive path."""
        if (not self.preemption or self.slo_monitor is None
                or not self._pending):
            return
        status = self.slo_monitor.status()
        burning = bool(status.get("breached")) or any(
            t.get("burn_rate") is not None and t["burn_rate"] >= 1.0
            for t in status.get("targets", {}).values())
        if burning:
            self._preempt_one()

    def _fail(self, req: _Request, exc: Exception) -> None:
        """Contain one request's failure: return its pages, fail its Future
        (waiters see the error instead of hanging), keep the engine alive."""
        # RESOURCE_EXHAUSTED through serving dispatch: dump the forensic
        # bundle (census + page-pool state) BEFORE freeing this request's
        # pages, so the bundle shows the pool as the allocator saw it
        _obs_mem.maybe_post_mortem(exc, step=self.decode_steps, source="serve")
        self._land()  # the other sequences' tokens in flight are theirs whatever failed here
        self._free_pages(req)
        try:
            req.future.set_exception(exc)
        except InvalidStateError:
            pass  # caller's cancel() raced the done() window — already dead
        with self._lock:
            self._outstanding -= 1
        if _obs.enabled():
            _obs_metrics.record_serve("failed", event=True,
                                      request=req.request_id,
                                      error=type(exc).__name__)
            _obs_trace.trace_event(req.trace_id, "failed",
                                   request=req.request_id,
                                   error=type(exc).__name__)

    def _free_pages(self, req: _Request) -> None:
        """Return every page ``req`` holds, of both kinds."""
        if req.pages:
            self.cache.allocator.free(req.pages)
            req.pages = []
        if req.win_pages:
            self.cache.window_allocator.free(list(req.win_pages.values()))
            req.win_pages = {}

    # -- window pages -----------------------------------------------------
    # A window layer reads key positions > pos - window only, so a sequence
    # holds window pages for the page indices that intersect that span and no
    # others: they are taken as its positions reach a new page and handed
    # back as soon as every position in them is too old. The window pool is
    # sized for every slot's span plus one prefill program's writes, so these
    # allocations cannot fail while the books are right.
    def _window_cover(self, req: _Request, lo: int, hi: int) -> None:
        """Hold a window page for every page index positions [lo, hi) touch."""
        ps = self.page_size
        missing = [j for j in range(lo // ps, (hi - 1) // ps + 1) if j not in req.win_pages]
        if missing:
            req.win_pages.update(zip(missing, self.cache.window_allocator.alloc(len(missing))))
            req.win_peak = max(req.win_peak, len(req.win_pages))

    def _window_trim(self, req: _Request, pos: int) -> None:
        """Free the window pages the query at ``pos`` (the next position to be
        written) and every later one cannot see: those wholly older than
        ``pos - window + 1``, and those past ``pos`` that a padded bucket
        wrote."""
        ps = self.page_size
        oldest = pos - self.window + 1
        dead = [j for j in req.win_pages if (j + 1) * ps <= oldest or j * ps > pos]
        if dead:
            self.cache.window_allocator.free([req.win_pages.pop(j) for j in dead])
            if _obs.enabled():
                _obs_metrics.record_serve("window_pages_freed", delta=len(dead))

    def _window_row(self, req: _Request) -> np.ndarray:
        row = np.zeros((self.n_pages_max,), np.int32)
        for j, page in req.win_pages.items():
            row[j] = page
        return row

    def _tables_for(self, req: _Request) -> tuple:
        """One (1, n_pages_max) table row a page kind, as the chunk program
        takes them."""
        rows = [self.cache.page_table_row(req.pages, self.n_pages_max)]
        if self.window:
            rows.append(self._window_row(req))
        return tuple(jnp.asarray(r[None, :]) for r in rows)

    def _recurrent_reset(self) -> None:
        """A prefill that starts a sequence starts its recurrent state from
        zero (the program does, in place of the slot's old rows)."""
        if self.recurrent and _obs.enabled():
            _obs_metrics.record_serve("recurrent_resets")

    def _drop_lost_pools(self, exc: Exception) -> bool:
        """After a failed dispatch. The programs consume the pools they are
        given, so a step that failed once execution had begun leaves none
        behind: every cached key and value is gone. Then allocate fresh
        pools and fail or drop whatever held pages — the active and the
        chunking sequences, the prefix cache's nodes — so the allocator ends
        with every page free and the next request is served. Where the
        arrays are alive (the failure came before execution) nothing is
        lost and nothing is done. Returns whether pools were lost."""
        self._land()
        lost = [c for c in (self.cache, self.draft_cache)
                if c is not None and c.pools_deleted()]
        if not lost:
            return False
        for c in lost:
            c.reset_pools()
        for i, req in enumerate(self._slots):
            if req is not None:
                self._fail(req, exc)
                self._clear_slot(i)
        for slot in list(self._chunking):
            self._fail(self._chunking.pop(slot), exc)
        if self.prefix is not None:
            self.prefix.clear()
        return True

    def _prefill_phase(self, req: _Request):
        """engine:prefill for one request's whole-prompt prefill or one of
        its chunks: host preparation, uploads, the dispatch of the program and
        of its first token's sampler. The token is read later (``_read_firsts``)."""
        return _obs_runtime.phase("engine:prefill", request=req.request_id,
                                  trace_id=req.trace_id)

    def _prefill(self, req: _Request, slot: int) -> None:
        obs_on = _obs.enabled()
        resumed = bool(req.tokens)
        L = len(req.prompt_eff) if req.prompt_eff is not None else len(req.prompt)
        prompt_eff = req.prompt_eff if req.prompt_eff is not None else req.prompt
        bucket = self.ladder.touch(L)
        req.bucket = bucket
        n_prompt_pages = bucket // self.page_size
        idx = np.zeros((1, bucket), np.int32)
        idx[0, :L] = prompt_eff
        t0 = time.perf_counter()
        try:
            page_ids = [req.pages[:n_prompt_pages]]
            if self.window:
                self._window_cover(req, 0, bucket)
                page_ids.append([req.win_pages[j] for j in range(n_prompt_pages)])
            page_ids = tuple(jnp.asarray(ids, jnp.int32) for ids in page_ids)
            last, slot_dev = jnp.asarray(L - 1, jnp.int32), jnp.asarray(slot, jnp.int32)
            self._recurrent_reset()
            with (_obs_runtime.step_span("serve_prefill", request=req.request_id,
                                         bucket=bucket, prompt_len=L)
                  if obs_on else _NULL):
                logits, state = self.runner.prefill_cfn(
                    self.params, jnp.asarray(idx), page_ids, self.cache.state, last, slot_dev)
                self.cache.rebind(state)
                if self.draft_cache is not None:
                    # the draft pool must hold the prompt too — same pages,
                    # same positions, draft weights (logits discarded)
                    _, dstate = self.draft_runner.prefill_cfn(
                        self.draft_params, jnp.asarray(idx), page_ids,
                        self.draft_cache.state, last, slot_dev)
                    self.draft_cache.rebind(dstate)
                if not resumed:
                    tok0 = self._sample_first(req, logits, L)
        except Exception as e:
            self._fail(req, e)
            self._drop_lost_pools(e)
            return
        if self.window:
            self._window_trim(req, L)
        if self.prefix is not None:
            self.prefix.insert(prompt_eff, req.pages)
        if resumed:
            # the spilled stream already owns its next token; no sampling
            # (and t_first keeps the FIRST life's stamp — TTFT is end-to-end)
            self._record_prefill(req, t0, time.perf_counter())
            self._on_resume(req)
            self._activate(req, slot, pos=L, tok=req.tokens[-1])
            return
        self._join(_First(req, slot, tok0, t0), pos=L)

    def _record_prefill(self, req: _Request, t0: float, t_done: float) -> None:
        """The records of a whole-prompt prefill that began at ``t0`` (bus on):
        at ``t_done`` its first token was read, or a resumed one's program was
        dispatched."""
        if not _obs.enabled():
            return
        L, ms = len(req.prompt_eff), (t_done - t0) * 1e3
        util = round(self.cache.utilization(), 4)
        _obs_metrics.record_serve("prefills", event=True,
                                  request=req.request_id, bucket=req.bucket,
                                  prompt_len=L, ms=round(ms, 3),
                                  pool_utilization=util)
        _obs_metrics.record_serve("prefill_tokens", delta=L)
        _obs_tel.observe("serve.prefill_ms", ms)
        _obs_tel.set_gauge("serve.pool_utilization", util)
        _obs_tel.set_gauge("serve.pages_in_use", self.cache.allocator.n_used)
        _obs_tel.set_gauge("serve.page_fragmentation",
                           round(self.page_fragmentation(), 4))
        _obs_trace.trace_event(req.trace_id, "prefill",
                               request=req.request_id,
                               dur_ms=ms, bucket=req.bucket,
                               prompt_len=L)

    def _sample_first(self, req: _Request, logits, pos: int) -> jax.Array:
        """Dispatch the sampler of ``req``'s first token, the one at position
        ``pos``, behind its prompt's program: (1,) int32 that stays on the device
        for the next step, its copy to the host started."""
        tok = self._sampler(logits, jnp.asarray([req.seed], jnp.uint32),
                            jnp.asarray([pos], jnp.int32),
                            jnp.asarray([req.temperature], jnp.float32))
        tok.copy_to_host_async()
        return tok

    def _join(self, first: _First, *, pos: int) -> None:
        """A prompt's program and its first token's sampler are dispatched: the
        sequence takes its slot now, to be fed that token on the device, and the
        host reads the token behind the next dispatch (``_read_firsts``). A
        request that wants one token takes no slot. With a draft model the next
        step is fed from the host, so everything lands here."""
        self._firsts.append(first)
        if first.req.max_new_tokens > 1:
            self._activate(first.req, first.slot, pos=pos, tok=first.tok)
        if self.draft_cache is not None:
            self._land()

    def _advance_prefills(self) -> bool:
        """Run queued prefill chunks under the per-iteration token budget.
        At least one chunk always runs when any is pending (progress even
        when a single chunk exceeds the budget); chunks from multiple
        requests share the budget in slot order. Returns whether this pass's
        decode step rode in a chunk's program (``_run_chunk``): the first
        dispatch that finds live rows takes them, so a pass makes one step."""
        rode = False
        spent = 0
        for slot in sorted(self._chunking):
            req = self._chunking[slot]
            while spent < self.prefill_budget:
                with self._prefill_phase(req):
                    try:
                        n_toks, logits, carried = self._run_chunk(req, slot, ride=not rode)
                    except Exception as e:
                        # a failed dispatch that carried a step has dropped lost pools already
                        if self._chunking.pop(slot, None) is not None:
                            self._fail(req, e)
                        if self._drop_lost_pools(e) or not self._chunking:
                            return rode  # no chunking sequence is left
                        break
                    rode = rode or carried
                    spent += n_toks
                    if req.chunk_pos >= len(req.prompt_eff):
                        del self._chunking[slot]
                        self._finish_chunked(req, slot, logits)
                        break
            if spent >= self.prefill_budget:
                break
        return rode

    def _run_chunk(self, req: _Request, slot: int, ride: bool = False):
        """One page-aligned chunk of req's effective prompt: write K/V pages,
        attend everything written so far (shared prefix pages included).
        Returns (tokens_spent, logits, carried) — logits only meaningful when
        this was the final chunk. Where the engine mixes and ``ride`` allows,
        the pass's decode step is dispatched in the same program (``carried``):
        its rows share every read of the weights with the chunk's, and the step
        before it is fetched and committed behind it as ``_decode`` does."""
        ps = self.page_size
        L_eff = len(req.prompt_eff)
        start = req.chunk_pos
        remaining = L_eff - start
        if remaining > self.chunk_tokens:
            cb = self.chunk_tokens
            last_rel = cb - 1  # logits discarded, but recurrent layers keep the state at this
            # index: it must be the chunk's last position
        else:
            cb = self.chunk_ladder.touch(remaining)
            if start + cb > self.max_seq:
                # the rounded rung would cross max_seq (and the rope table):
                # fall back to the exact page-aligned remainder
                cb = PagedKVCache.pages_for(remaining, ps) * ps
            last_rel = remaining - 1
        idx = np.zeros((1, cb), np.int32)
        n_real = min(cb, remaining)
        idx[0, :n_real] = req.prompt_eff[start:start + n_real]
        if self.window:
            self._window_cover(req, start, start + cb)
        rows = self._tables_for(req)
        where = (jnp.asarray(start, jnp.int32), jnp.asarray(last_rel, jnp.int32),
                 jnp.asarray(slot, jnp.int32))
        if start == 0:
            self._recurrent_reset()
        obs_on = _obs.enabled()
        t0 = time.perf_counter()
        live = self._live_slots() if ride and self._mixes else []
        logits = None

        def program(toks, tables, pos):  # the chunk's, with the rows of a decode step
            nonlocal logits
            logits, step_logits, state, *counted = self.runner.chunk_cfn(
                self.params, jnp.asarray(idx), rows, self.cache.state, *where, (toks, tables, pos))
            self.cache.rebind(state)
            return step_logits, counted

        with (_obs_runtime.step_span("serve_prefill", request=req.request_id,
                                     bucket=cb, prompt_len=L_eff, chunk=True,
                                     start=start)
              if obs_on else _NULL):
            if live:
                failed = self._decode_step(live, program)
                if failed is not None:
                    raise failed
            elif self._mixes:
                program(*self._idle_rows)
            else:
                logits, state = self.runner.chunk_cfn(
                    self.params, jnp.asarray(idx), rows, self.cache.state, *where)
                self.cache.rebind(state)
                if self.draft_cache is not None:
                    _, dstate = self.draft_runner.chunk_cfn(
                        self.draft_params, jnp.asarray(idx), rows, self.draft_cache.state, *where)
                    self.draft_cache.rebind(dstate)
        req.chunk_pos = min(start + cb, L_eff)
        if self.window:
            self._window_trim(req, req.chunk_pos)
        if obs_on:
            if live:
                _obs_metrics.record_serve("decode_mixed")
            _obs_metrics.record_serve("prefill_tokens", delta=n_real)
            self._record_chunk_pages(np.asarray([start]), cb)
            dur_ms = (time.perf_counter() - t0) * 1e3
            _obs_tel.observe("serve.prefill_ms", dur_ms)
            _obs_trace.trace_event(req.trace_id, "prefill_chunk",
                                   request=req.request_id, dur_ms=dur_ms,
                                   start=start, tokens=n_real)
        return cb, logits, bool(live)

    def _finish_chunked(self, req: _Request, slot: int, logits) -> None:
        """Final chunk done: register the prompt's full pages in the prefix
        cache, sample the first token (fresh requests), activate the slot."""
        obs_on = _obs.enabled()
        L_eff = len(req.prompt_eff)
        req.bucket = self.ladder.bucket_for(L_eff)
        if self.prefix is not None:
            self.prefix.insert(req.prompt_eff, req.pages)
        if obs_on:
            util = round(self.cache.utilization(), 4)
            _obs_metrics.record_serve("prefills", event=True,
                                      request=req.request_id,
                                      bucket=req.bucket, prompt_len=L_eff,
                                      chunked=True, pool_utilization=util)
            _obs_tel.set_gauge("serve.pool_utilization", util)
            _obs_tel.set_gauge("serve.pages_in_use",
                               self.cache.allocator.n_used)
            _obs_tel.set_gauge("serve.page_fragmentation",
                               round(self.page_fragmentation(), 4))
        if self.block is not None:  # no first token: the first block is generated as every other
            if req.tokens:
                self._on_resume(req)
            self._activate_block(req, slot)
            return
        if req.tokens:
            self._on_resume(req)
            self._activate(req, slot, pos=L_eff, tok=req.tokens[-1])
            return
        try:
            tok0 = self._sample_first(req, logits, L_eff)
        except Exception as e:
            self._fail(req, e)
            return
        self._join(_First(req, slot, tok0), pos=L_eff)

    def _activate(self, req: _Request, slot: int, *, pos: int, tok) -> None:
        """``req`` takes ``slot`` and is in the next decode step, writing
        position ``pos``. Nothing lands: the step in flight, if there is one,
        does not hold the slot, and the next one is fed ``tok`` for it on the
        device in place of the sampler's output (``_dispatch``). ``tok`` is a
        first token in flight, (1,) on the device, or an int the host owns (a
        resumed victim's last token, a prefix hit's last prompt token)."""
        if _obs.enabled():
            _obs_metrics.record_serve("activations")
            if self._inflight is not None:
                _obs_metrics.record_serve("activations_joined")
        if isinstance(tok, int):
            self._toks[slot] = tok
            tok = self._upload(np.array([tok], np.int32))
        self._take_slot(req, slot, pos, tok)

    def _activate_block(self, req: _Request, slot: int) -> None:
        """``_activate`` under ``block_diffusion=``: ``req`` takes ``slot`` and its first block,
        the prompt's tail and mask tokens after it, is merged into the next pass's blocks on
        the device (``_dispatch_block``) as the block to denoise, with none to settle: what is
        before it went through the chunk program. Nothing lands."""
        spec, tail = self.block, req.block_tail
        K = spec.K
        if _obs.enabled():
            _obs_metrics.record_serve("activations")
            if self._inflight is not None:
                _obs_metrics.record_serve("activations_joined")
        toks = np.full((2 * K,), spec.mask_id, np.int32)
        toks[K:K + len(tail)] = tail
        pos = len(req.prompt_eff)
        req.block_pos, req.block_given = pos - K, len(tail)
        # what a preempted victim had filled of a block it never settled is filled anew
        req.unmasked = [u for u in req.unmasked if u[0] < pos]
        self._take_slot(req, slot, pos, (toks, np.arange(K) >= len(tail), np.int32(pos - K),
                                         np.int32(self._life(len(req.prompt), req.max_new_tokens)),
                                         np.array([False, True])))

    def _take_slot(self, req: _Request, slot: int, pos: int, feed) -> None:
        self._feeds[slot] = feed
        self._slots[slot] = req
        self._page_tables[slot] = self.cache.page_table_row(req.pages,
                                                            self.n_pages_max)
        if self.window:
            self._window_cover(req, pos, pos + 1)
            self._win_tables[slot] = self._window_row(req)
        self._pos[slot] = pos
        self._seeds[slot] = req.seed
        self._temps[slot] = req.temperature
        self._pt_dirty = True

    def _clear_slot(self, i: int) -> None:
        self._slots[i] = None
        self._feeds.pop(i, None)
        if self.block is not None:
            self._feeds[i] = self._idle_block  # the slot's block on the device goes idle too
        self._rest_slot(i)
        self._toks[i] = 0
        self._seeds[i] = 0
        self._temps[i] = 0.0

    def _rest_slot(self, i: int) -> None:
        """Slot i as the decode program sees an idle one: position 0 and a
        null-page row, so a step writes to the null page and leaves the slot's
        recurrent rows alone."""
        self._page_tables[i] = 0
        self._win_tables[i] = 0
        self._pos[i] = 0
        self._pt_dirty = True

    def _upload(self, host: np.ndarray) -> jax.Array:
        """A copy of ``host`` on the weights' device. A copy, because the engine
        goes on writing its packed host arrays while the step that was given
        them is in flight, and the CPU backend's arrays share the memory they
        were made from. Committed to the device, as what a program returns is:
        ``jax.jit`` builds an executable anew for an operand that is committed
        where it was not, so a program fed a token from the host and one fed
        the sampler's output must look alike, or the first chunk that meets
        live rows compiles."""
        return jax.device_put(np.array(host), self._device)

    def _upload_packed_state(self) -> None:
        # page tables / seeds / temps only change at slot (un)assignment;
        # re-upload them then, not per token (pos/toks change every step)
        if self._pt_dirty:
            self._pt_dev = ((self._upload(self._page_tables), self._upload(self._win_tables))
                            if self.window else (self._upload(self._page_tables),))
            self._seeds_dev = self._upload(self._seeds)
            self._temps_dev = self._upload(self._temps)
            self._pt_dirty = False

    def _commit(self, i: int, req: _Request, tok: int, t_now: float) -> bool:
        """Commit one generated token to slot i; returns False when the
        request finished (retired, slot cleared). The slot's position is its
        caller's to advance."""
        if req.t_first == 0.0:
            # prefix-hit admissions skip prefill: TTFT stamps at the first
            # committed token instead
            req.t_first = t_now
        req.tokens.append(tok)
        req.t_last = t_now
        self._toks[i] = tok
        if self._finished(req, tok):
            self._retire(req)
            self._clear_slot(i)
            return False
        return True

    def _window_step(self, i: int) -> None:
        """Before the step that writes slot i's position is dispatched: take
        that position's window page where it enters a new one, hand back the
        page that has just left the window. Both follow from the position
        alone. A page handed back may still be read by the step in flight; the
        device runs what is dispatched in order, so whoever writes it next does
        so after that read."""
        pos, ps = int(self._pos[i]), self.page_size
        if pos % ps == 0 or (pos - self.window + 1) % ps == 0:
            req = self._slots[i]
            self._window_cover(req, pos, pos + 1)
            self._window_trim(req, pos)
            self._win_tables[i] = self._window_row(req)
            self._pt_dirty = True

    def _decode(self) -> None:
        """One pass of the plain decode path: dispatch the next step, then
        fetch and commit the one before it."""
        if self.draft_cache is not None and self.spec_k > 0:
            self._spec_decode()
            return
        live = ([i for i, req in enumerate(self._slots) if req is not None]
                if self.block is not None else self._live_slots())
        if not live:
            self._land()  # a step with no live sequence is never dispatched
            return
        self._decode_step(live, self._decode_program)

    def _live_slots(self) -> List[int]:
        """The slots the next decode step carries: every sequence that wants a
        token beyond those it may have in flight, a first token not read yet
        and one of the step in flight. One whose last token by count is in
        flight idles from this step on."""
        prev = self._inflight
        unread = {id(first.req) for first in self._firsts}
        live = []
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            flying = (id(req) in unread) + (prev is not None and prev.reqs.get(i) is req)
            if len(req.tokens) + flying < req.max_new_tokens:
                live.append(i)
            else:
                self._rest_slot(i)
        return live

    def _decode_program(self, toks, tables, pos):
        logits, state, *counted = self.runner.decode_cfn(
            self.params, toks, self.cache.state, tables, pos)
        self.cache.rebind(state)
        return logits, counted

    def _decode_step(self, live: List[int], program) -> Optional[Exception]:
        """Dispatch the decode step of the ``live`` slots through ``program``
        (``_decode_program``, or a chunk's program that takes the step's rows
        beside its own: ``_run_chunk``), then fetch and commit the step before
        it, and then the first tokens of the sequences activated since: their
        prompts' programs ran behind that step, and the chip has the new step
        queued while the host waits for them. Returns what a failed dispatch
        raised (``_dispatch``), else None."""
        # taken out while the next step is dispatched: a failure's clean-up, which
        # lands "the step in flight", then finds none, and prev is settled below
        prev, self._inflight = self._inflight, None
        t0 = time.perf_counter()
        with _obs_runtime.phase("engine:upload"):
            if self.window:
                for i in live:
                    self._window_step(i)
            self._upload_packed_state()
        step = failed = None
        with (_obs_runtime.step_span("serve_decode", active=len(live))
              if _obs.enabled() else _NULL):
            try:
                step = self._dispatch(live, prev, t0, program)
            except Exception as e:
                failed = e
            fetched = self._fetch(prev)
        self._commit_step(prev, fetched)
        self._read_firsts()
        self._inflight = step
        return failed

    def _dispatch(self, live: List[int], prev: Optional[_Step], t0: float, program) -> _Step:
        """Enqueue the decode step of the ``live`` slots — ``program(toks,
        tables, pos)`` runs it, rebinds the cache and returns (logits (max_batch,
        V), what the program counted) — and its sampler. The token a sequence
        feeds is the one the sampler produced for it in ``prev``, the step in
        flight, still on the device; with no step in flight the tokens go up
        from the host. A sequence activated since the last dispatch was in
        neither: its token (``_feeds``) is merged in on the device, one small
        program a token, so a first token need not have reached the host. After
        a failure every live sequence has failed, its pages returned, and the
        exception goes on to the caller."""
        if self.block is not None:
            return self._dispatch_block(live, prev, t0)
        phase = _obs_runtime.phase
        try:
            with phase("engine:upload"):
                toks = prev.nxt if prev is not None else self._upload(self._toks[:, None])
                for slot, tok in self._feeds.items():
                    toks = self._merge(toks, self._upload(np.int32(slot)), tok)
                pos = self._upload(self._pos)
            with phase("engine:dispatch"):
                logits, counted = program(toks, self._pt_dev, pos)
                # the NEXT token's position is pos+1 (this step wrote pos)
                nxt = self._step_sampler(logits, self._seeds_dev, pos, self._temps_dev)
                # the host's copy starts as the sampler ends, not when the fetch asks
                nxt.copy_to_host_async()
                for c in counted:
                    c.copy_to_host_async()
        except Exception as e:
            # the packed step failed: every live sequence is implicated —
            # fail their futures and return their pages rather than hanging
            # the whole engine (pending requests still get admitted)
            self._fail_live(live, e)
            raise
        self._feeds.clear()
        self.decode_steps += 1
        if _obs.enabled():
            # what the step read and held follows from the positions it was given
            _obs_metrics.record_serve("decode_steps")
            _obs_metrics.record_serve("decode_overlapped", delta=int(prev is not None))
            self._record_state(len(live))
            self._record_paged_pages()
        self._pos[live] += 1
        return _Step(nxt, {i: self._slots[i] for i in live}, t0, *counted)

    def _fail_live(self, live: List[int], e: Exception) -> None:
        """The packed step failed: every live sequence is implicated."""
        self._land()  # a first token not read yet may end its sequence before the failure does
        for i in live:
            if self._slots[i] is not None:
                self._fail(self._slots[i], e)
                self._clear_slot(i)
        self._drop_lost_pools(e)

    def _dispatch_block(self, live: List[int], prev: Optional[_Step], t0: float) -> _Step:
        """``_dispatch`` under ``block_diffusion=``: enqueue ONE pass over every slot's two
        blocks and its sampler (``_block_sample``). The blocks, their masked flags and
        positions never leave the device: the sampler's output is the next pass's input, and
        the host reads the small record of this pass behind the next dispatch. A slot whose
        pass settles a block and denoises the next, one in a later denoise pass of its block
        and one settling its sequence's last block share the dispatch; which a slot is in, the
        device knows and the host learns from the record. A sequence activated since the last
        dispatch, and a slot gone idle, are merged in first (``_feeds``)."""
        phase = _obs_runtime.phase
        try:
            with phase("engine:upload"):
                blk = self._blk
                for slot, row in self._feeds.items():
                    blk = self._block_merge(*blk, self._upload(np.int32(slot)),
                                            *(self._upload(a) for a in row))
            with phase("engine:dispatch"):
                toks, _, pos, _, live2 = blk
                logits, state, *counted = self.runner.block_cfn(
                    self.params, toks, self.cache.state, self._pt_dev, pos, live2)
                self.cache.rebind(state)
                *nxt, rec = self._block_sampler(logits, *blk, self._seeds_dev, self._temps_dev)
                self._blk = tuple(nxt)
                rec.copy_to_host_async()
                for c in counted:
                    c.copy_to_host_async()
        except Exception as e:
            self._fail_live(live, e)
            raise
        self._feeds.clear()
        self.decode_steps += 1
        if _obs.enabled():
            _obs_metrics.record_serve("decode_steps")
            _obs_metrics.record_serve("decode_overlapped", delta=int(prev is not None))
            self._record_state(len(live))
            # the host holds the position of the block a sequence denoises, as of the last record
            # it read: the two blocks' queries start K before it
            self._record_chunk_pages(np.maximum(self._pos[live] - self.block.K, 0), 2 * self.block.K)
        return _Step(rec, {i: self._slots[i] for i in live}, t0, *counted)

    def _fetch(self, step: Optional[_Step]) -> Optional[np.ndarray]:
        """The sampled tokens of ``step`` on the host, (max_batch,). None where
        there is no step, or it failed: then the sequences that were in it
        have failed."""
        if step is None:
            return None
        with _obs_runtime.phase("engine:fetch"):
            try:
                nxt = np.asarray(step.nxt)
                return nxt if self.block is not None else nxt[:, 0]
            except Exception as e:
                for i, req in step.reqs.items():
                    if self._slots[i] is req:
                        self._fail(req, e)
                        self._clear_slot(i)
                self._drop_lost_pools(e)
                return None

    def _commit_step(self, step: Optional[_Step], nxt: Optional[np.ndarray]) -> None:
        """Commit the tokens ``_fetch`` gave for ``step`` (None: nothing to
        commit) to the sequences that were in it. One that ended at a commit since the step was dispatched (``eos_id``,
        a cancelled Future) or was failed has left its slot: its token is
        thrown away."""
        if nxt is None:
            return
        if self.block is not None:
            self._commit_block_pass(step, nxt)
            return
        with _obs_runtime.phase("engine:commit"):
            # with the bus on, the step's own records count as commit too
            t_now = time.perf_counter()
            kept = [(i, req) for i, req in step.reqs.items() if self._slots[i] is req]
            if _obs.enabled():
                dur_ms = (t_now - step.t0) * 1e3
                _obs_metrics.record_serve("tokens", delta=len(kept))
                if step.counted is not None:  # computed before the tokens that were just fetched
                    for name, n in zip(ROUTING_COUNTERS, np.asarray(step.counted)):
                        _obs_metrics.record_serve(name, delta=int(n))
                if len(kept) < len(step.reqs):
                    _obs_metrics.record_serve("decode_discarded",
                                              delta=len(step.reqs) - len(kept))
                _obs_flight.record_step(dur_ms, fn="serve_decode", active=len(step.reqs))
                # online decode-step latency percentiles, dispatch to commit
                # (unsampled, like the flight recorder — TT_OBS_SAMPLE only
                # thins the spans)
                _obs_tel.observe("serve.decode_ms", dur_ms)
                # ONE shared trace event per step carrying every participant
                # (volume scales with steps, not steps × batch width)
                _obs_trace.trace_step(
                    [req.trace_id for req in step.reqs.values()], "decode",
                    dur_ms=dur_ms, step=self.decode_steps, active=len(step.reqs))
            for i, req in kept:
                self._commit(i, req, int(nxt[i]), t_now)

    def _commit_block_pass(self, step: _Step, rec: np.ndarray) -> None:
        """``_commit_step`` under ``block_diffusion=``: what the pass ``step`` did for each
        sequence that was in it, from the record its sampler made (``_block_sample``), the
        block it SETTLED first and then the block it denoised, as a loop of one block a
        forward would have run them. A settled block's keys and values are in the pool as
        they stay: its generated tokens go to ``req.tokens`` now (a block is what a stream
        could show), ``t_first`` is the first block's, and the sequence may end here
        (``max_new_tokens`` reached: the first that many are returned; ``eos_id`` among the
        block's: through it; a cancelled Future); what the pass did for the next block is
        then thrown away. A DENOISE filled positions: which and with what is read off the
        record, never assumed, and they are noted (``RequestResult.unmasked``) and counted as
        tokens produced. Only the pass that settles a sequence's LAST block denoises nothing:
        that one is a commit pass and nothing else."""
        K = self.block.K
        obs_on = _obs.enabled()
        with _obs_runtime.phase("engine:commit"):
            t_now = time.perf_counter()
            kept = [(i, req) for i, req in step.reqs.items() if self._slots[i] is req]
            filled = commits = joined = cut = 0
            for i, req in kept:
                pos, settles, denoises = int(rec[i, 0]), bool(rec[i, 1]), bool(rec[i, 2])
                first, toks_in, masked_in, toks_dn, masked_dn = (
                    rec[i, 3 + j * K:3 + (j + 1) * K] for j in range(5))
                if pos != req.block_pos:  # the books are wrong: never commit another block's rows
                    self._fail(req, RuntimeError(
                        f"request {req.request_id}: a pass ran the blocks at {pos}, the host "
                        f"expected those at {req.block_pos}"))
                    self._clear_slot(i)
                    continue
                if settles:
                    if self.record_block_states:
                        req.block_states.append((pos, first.copy(), np.zeros((K,), bool)))
                    req.n_passes += 1
                    commits += 1
                    joined += denoises
                    new = [int(t) for t in first[req.block_given:]]
                    req.block_given = 0
                    if req.t_first == 0.0:
                        req.t_first = t_now
                    req.t_last = t_now
                    if req.eos_id is not None and req.eos_id in new:
                        new = new[:new.index(req.eos_id) + 1]
                    req.tokens.extend(new)
                    del req.tokens[req.max_new_tokens:]
                    if self._finished(req, req.tokens[-1]):
                        cut += denoises  # the next block's rows rode along for nothing
                        self._retire(req)
                        self._clear_slot(i)
                        continue
                if denoises:
                    masked_in, masked_dn = masked_in.astype(bool), masked_dn.astype(bool)
                    if self.record_block_states:
                        req.block_states.append((pos + K, toks_in.copy(), masked_in))
                    req.n_passes += 1
                    new = np.flatnonzero(masked_in & ~masked_dn)
                    req.unmasked.extend((pos + K + int(j), req.n_passes - 1) for j in new)
                    filled += len(new)
                    if not masked_dn.any():  # finished: the next pass settles it, K further on
                        req.block_pos = pos + K
                        self._pos[i] = pos + 2 * K
                    if req.future.cancelled():
                        self._retire(req)
                        self._clear_slot(i)
            self.block_passes += 1
            self.block_commits += int(commits > 0)
            self.blocks_done += commits
            self.block_settles_joined += joined
            if obs_on:
                dur_ms = (t_now - step.t0) * 1e3
                _obs_metrics.record_serve("tokens", delta=filled)
                _obs_metrics.record_serve("block_passes")
                _obs_metrics.record_serve("block_commits", delta=int(commits > 0))
                _obs_metrics.record_serve("blocks_done", delta=commits)
                _obs_metrics.record_serve("block_slot_passes", delta=len(kept))
                _obs_metrics.record_serve("block_slot_commits", delta=commits)
                _obs_metrics.record_serve("block_settles_joined", delta=joined)
                if step.counted is not None:
                    for name, n in zip(ROUTING_COUNTERS, np.asarray(step.counted)):
                        _obs_metrics.record_serve(name, delta=int(n))
                discarded = len(step.reqs) - len(kept) + cut
                if discarded:
                    _obs_metrics.record_serve("decode_discarded", delta=discarded)
                _obs_flight.record_step(dur_ms, fn="serve_decode", active=len(step.reqs),
                                        unmasked=filled)
                _obs_tel.observe("serve.decode_ms", dur_ms)
                if kept:
                    _obs_tel.observe("serve.tokens_per_pass", filled / len(kept))
                _obs_trace.trace_step(
                    [req.trace_id for req in step.reqs.values()], "block_pass",
                    dur_ms=dur_ms, step=self.decode_steps, active=len(step.reqs), unmasked=filled)

    def _land(self) -> None:
        """Bring the host level with the device: fetch and commit the step in
        flight, if there is one, and then the first tokens not read yet (none
        of their sequences is in that step). The one place that does, for what
        cannot go on beside a step in flight: a preemption (the victim keeps
        its token in flight), a failure's clean-up, stop and drain, a pass with
        no live sequence, the speculative path. An activation is not among
        them (``_activate``)."""
        step, self._inflight = self._inflight, None
        self._commit_step(step, self._fetch(step))
        self._read_firsts()

    def _read_firsts(self) -> None:
        """Read and commit the first tokens in flight, oldest first; each wait
        ends when its prompt's program does, which is when ``t_first`` is
        stamped. A token that ends its request retires it and frees the slot:
        the token a step in flight made for the slot is then thrown away at
        that step's commit, and the next prefill into the slot starts its
        recurrent rows anew. A read that fails fails that request."""
        while self._firsts:
            first = self._firsts.popleft()  # out before anything below can land again
            req, slot = first.req, first.slot
            try:
                with _obs_runtime.phase("engine:fetch"):
                    tok = int(np.asarray(first.tok)[0])
            except Exception as e:
                self._fail(req, e)
                if self._slots[slot] is req:
                    self._clear_slot(slot)
                self._drop_lost_pools(e)
                continue
            with _obs_runtime.phase("engine:commit"):
                t_now = time.perf_counter()
                if first.t0:
                    self._record_prefill(req, first.t0, t_now)
                req.t_first = req.t_last = t_now
                req.tokens.append(tok)
                activated = self._slots[slot] is req  # not one that wanted this token only
                if activated:
                    self._toks[slot] = tok
                if self._finished(req, tok):
                    self._retire(req)
                    if activated:
                        self._clear_slot(slot)

    def _record_state(self, active: int) -> None:
        """What the cached state of this decode step's sequences took, summed
        step by step (bus on): pages of the pools without a window (again as
        ``state.latent_pages`` where layers cache latent rows), pages of the
        window pools, bytes of recurrent state. Over ``serve.tokens`` (the
        sequences, summed the same way) they give the state a sequence holds.
        Beside them the most pages of each kind in use at one step since the
        bus was reset (``state.full_pages_peak``, ``state.window_pages_peak``)."""
        full = self.cache.allocator.n_used
        _obs_metrics.record_serve("state.shared_kv_pages", delta=full)
        _obs_metrics.record_serve_peak("state.full_pages_peak", full)
        if self.latent:
            _obs_metrics.record_serve("state.latent_pages", delta=full)
        if self.window:
            window = self.cache.window_allocator.n_used
            _obs_metrics.record_serve("state.window_pages", delta=window)
            _obs_metrics.record_serve_peak("state.window_pages_peak", window)
        if self.recurrent:
            _obs_metrics.record_serve("state.recurrent_bytes",
                                      delta=active * self.cache.recurrent_bytes_per_slot())

    def _record_pages(self, name: str, kinds, ends, window_first, window_span: int) -> None:
        """``serve.paged.<name>pages_live``: pages [0, ends) a sequence of the
        pools of ``kinds`` without a window and [window_first, ends) of the
        window pools; ``<name>pages_spanned``: what a grid of one program a
        table entry stepped over, the table's width a sequence or the pages
        ``window_span`` positions can touch."""
        seqs = len(ends)
        live = spanned = 0
        if any(isinstance(d, kinds) and not d.window for d in self.cache.layers):
            live += int(ends.sum())
            spanned += seqs * self.n_pages_max
        if self.window:
            live += int((ends - window_first).sum())
            spanned += seqs * min(self.n_pages_max, -(-window_span // self.page_size) + 1)
        _obs_metrics.record_serve(f"paged.{name}pages_live", delta=live)
        _obs_metrics.record_serve(f"paged.{name}pages_spanned", delta=spanned)

    def _record_paged_pages(self) -> None:
        """Pages the paged decode kernel walked in this decode step (bus on),
        summed over every slot of the packed program (an idle slot reads one)
        and over the page kinds, beside what a grid of one program a table
        entry stepped over: the table's width a slot, or the window's span."""
        ps, window = self.page_size, self.window or 0
        lens = self._pos.astype(np.int64) + 1
        self._record_pages("", (PagedKV, PagedLatent), -(-lens // ps),
                           np.maximum(lens - window, 0) // ps, window)

    def _record_chunk_pages(self, first_pos: np.ndarray, n_queries: int) -> None:
        """Pages the queries of this chunk or verify dispatch can see (bus on):
        each sequence's ``n_queries`` positions from ``first_pos`` on, summed
        over the sequences of the program and over the page kinds a paged chunk
        kernel reads, beside the table's width a sequence, or the span of the
        queries' windows."""
        ps, window = self.page_size, self.window or 0
        first_pos = np.asarray(first_pos, np.int64)
        ends = np.minimum(-(-(first_pos + n_queries) // ps), self.n_pages_max)
        self._record_pages("chunk_", PagedKV, ends, np.maximum(first_pos - window + 1, 0) // ps,
                           n_queries + window - 1)

    def _spec_decode(self) -> None:
        """Speculative decode iteration: k draft decode steps propose, one
        packed target verify step scores all k+1 positions, the accepted
        prefix commits (capped at k — NO bonus token, which is what keeps
        the draft pool valid through the new position without a catch-up
        pass). The draft proposes with the SAME position-keyed sampler, so
        a perfect draft accepts everything and every committed token is
        bit-identical to plain decode either way."""
        active = [i for i, s in enumerate(self._slots) if s is not None]
        if not active:
            return
        obs_on = _obs.enabled()
        phase = _obs_runtime.phase
        k = self.spec_k
        K1 = k + 1
        t0 = time.perf_counter()
        with phase("engine:upload"):
            self._upload_packed_state()
        try:
            # k draft rounds and the verify step each have their own
            # upload, dispatch and fetch: (k + 1) of each a decode step
            with (_obs_runtime.step_span("serve_decode", active=len(active),
                                         spec_k=k)
                  if obs_on else _NULL):
                base_pos = self._pos.copy()
                cand = [self._toks.copy()]
                for j in range(1, k + 1):
                    with phase("engine:upload"):
                        cur = jnp.asarray(cand[-1][:, None])
                        pos = jnp.asarray(base_pos + (j - 1))
                    with phase("engine:dispatch"):
                        dlog, dstate = self.draft_runner.decode_cfn(
                            self.draft_params, cur, self.draft_cache.state, self._pt_dev, pos)
                        self.draft_cache.rebind(dstate)
                        dj = self._sampler(
                            dlog, self._seeds_dev, jnp.asarray(base_pos + j),
                            self._temps_dev)
                    with phase("engine:fetch"):
                        cand.append(np.asarray(dj))
                with phase("engine:upload"):
                    toks_mat = np.stack(cand, axis=1)  # (max_batch, k+1)
                    toks = jnp.asarray(toks_mat)
                    pos = jnp.asarray(base_pos)
                with phase("engine:dispatch"):
                    vlog, state = self.runner.verify_cfn(
                        self.params, toks, self.cache.state, self._pt_dev, pos)
                    self.cache.rebind(state)
                    if obs_on:
                        self._record_chunk_pages(base_pos, K1)
                    B = toks_mat.shape[0]
                    pos_flat = (base_pos[:, None] + 1
                                + np.arange(K1, dtype=np.int32)[None, :]).reshape(-1)
                    samples = self._sampler(
                        jnp.reshape(vlog, (B * K1, -1)),
                        jnp.asarray(np.repeat(self._seeds, K1)),
                        jnp.asarray(pos_flat),
                        jnp.asarray(np.repeat(self._temps, K1)))
                with phase("engine:fetch"):
                    samples = np.asarray(samples).reshape(B, K1)
        except Exception as e:
            for i in active:
                self._fail(self._slots[i], e)
                self._clear_slot(i)
            self._drop_lost_pools(e)
            return
        with phase("engine:commit"):
            t_now = time.perf_counter()
            self.decode_steps += 1
            # participant ids captured BEFORE commits (a finishing commit clears
            # its slot); only read when tracing is on
            trace_ids = ([self._slots[i].trace_id for i in active]
                         if obs_on else [])
            committed_total = 0
            accepted_total = 0
            for i in active:
                req = self._slots[i]
                m = 0
                while m < k and toks_mat[i, m + 1] == samples[i, m]:
                    m += 1
                # commit the accepted samples; min(m+1, k) keeps the draft pool
                # valid (a bonus k+1th token would advance the target one
                # position past anything the draft ever wrote)
                n = min(m + 1, k)
                self.spec_proposed += k
                self.spec_accepted += m
                accepted_total += m
                for j in range(n):
                    committed_total += 1
                    self._pos[i] += 1
                    if not self._commit(i, req, int(samples[i, j]), t_now):
                        break
            if obs_on:
                _obs_metrics.record_serve("decode_steps")
                _obs_metrics.record_serve("tokens", delta=committed_total)
                _obs_metrics.record_serve("spec_proposed", delta=k * len(active))
                _obs_metrics.record_serve("spec_accepted", delta=accepted_total)
                _obs_flight.record_step((t_now - t0) * 1e3, fn="serve_decode",
                                        active=len(active), spec_k=k,
                                        committed=committed_total)
                _obs_tel.observe("serve.decode_ms", (t_now - t0) * 1e3)
                _obs_trace.trace_step(trace_ids, "spec_verify",
                                      dur_ms=(t_now - t0) * 1e3,
                                      step=self.decode_steps, spec_k=k,
                                      accepted=accepted_total,
                                      committed=committed_total)

    def _finished(self, req: _Request, tok: int) -> bool:
        if req.future.cancelled():
            # the caller gave up: stop decoding and free the pages now
            return True
        if req.eos_id is not None and tok == req.eos_id:
            return True
        return len(req.tokens) >= req.max_new_tokens

    def _retire(self, req: _Request) -> None:
        held, win_held = tuple(req.pages), tuple(sorted(req.win_pages.items()))
        self._free_pages(req)
        n_new = len(req.tokens)
        # t_first == 0.0 only for a prefix-hit request cancelled before its
        # first committed token — report a zero TTFT rather than a negative
        ttft = (req.t_first - req.t_submit) if req.t_first else 0.0
        # what t_first stamps: the first token, or under block_diffusion= the first block
        first = self.block.K if self.block is not None else 1
        tbot = ((req.t_last - req.t_first) / (n_new - first)) if n_new > first else 0.0
        if req.future.cancelled():
            # a client-side cancel is not a completion: tag it so latency
            # percentiles (obs_summary) aren't polluted by truncated samples
            reason = "cancelled"
        elif (req.eos_id is not None and req.tokens
              and req.tokens[-1] == req.eos_id):
            reason = "eos"
        else:
            reason = "length"
        obs_on = _obs.enabled()
        slo_met = None
        if reason != "cancelled":
            ttft_ms = ttft * 1e3
            # a one-token request has no between-token interval: exclude it
            # from the tbot population (online AND offline percentiles use
            # the same rule) rather than stream a 0.0 placeholder
            tbot_ms = tbot * 1e3 if n_new > first else None
            if self.slo_policy is not None:
                slo_met = self.slo_policy.request_met(ttft_ms, tbot_ms)
                self.requests_retired += 1
                self.requests_slo_met += int(slo_met)
            if obs_on:
                # streaming percentiles: the online mirror of the offline
                # serving section's TTFT/TBOT populations (cancelled
                # requests excluded from both); per-lane series alongside
                # the aggregate so SLO triage can split interactive vs batch
                _obs_tel.observe("serve.ttft_ms", ttft_ms)
                _obs_tel.observe(f"serve.ttft_ms.{req.lane}", ttft_ms)
                if tbot_ms is not None:
                    _obs_tel.observe("serve.tbot_ms", tbot_ms)
                    _obs_tel.observe(f"serve.tbot_ms.{req.lane}", tbot_ms)
            if self.slo_monitor is not None:
                self.slo_monitor.observe_request(
                    ttft_ms=ttft_ms, tbot_ms=tbot_ms, met=bool(slo_met),
                    tokens=n_new)
        if obs_on:
            util = round(self.cache.utilization(), 4)
            _obs_tel.set_gauge("serve.pool_utilization", util)
            _obs_tel.set_gauge("serve.pages_in_use", self.cache.allocator.n_used)
            _obs_tel.set_gauge("serve.page_fragmentation",
                               round(self.page_fragmentation(), 4))
            if self.slo_policy is not None and self.requests_retired:
                _obs_tel.set_gauge(
                    "serve.goodput",
                    round(self.requests_slo_met / self.requests_retired, 4))
            _obs_metrics.record_serve(
                "cancelled" if reason == "cancelled" else "retired",
                event=True, request=req.request_id, n_new=n_new,
                ttft_ms=round(ttft * 1e3, 3), tbot_ms=round(tbot * 1e3, 3),
                finish=reason, lane=req.lane, pool_utilization=util)
            _obs_trace.trace_event(req.trace_id, "retired",
                                   request=req.request_id, finish=reason,
                                   n_new=n_new, ttft_ms=round(ttft * 1e3, 3),
                                   lane=req.lane)
        result = RequestResult(
            request_id=req.request_id,
            tokens=np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)]),
            new_tokens=np.asarray(req.tokens, np.int32),
            ttft_s=ttft,
            tbot_s=tbot,
            n_new_tokens=n_new,
            finish_reason=reason,
            slo_met=slo_met,
            queue_s=req.t_admit - req.t_submit,
            pages=held,
            window_pages=win_held,
            window_pages_peak=req.win_peak,
            unmasked=tuple(req.unmasked),
            block_states=tuple(req.block_states),
        )
        try:
            # a cancel() from the caller thread can land at ANY point, so a
            # done() pre-check would still race — set and swallow the loss
            # (pages are already freed above either way)
            req.future.set_result(result)
        except InvalidStateError:
            pass
        with self._lock:
            self._outstanding -= 1
