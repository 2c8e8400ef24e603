"""Paged model programs: bucketed prefill and the packed decode step.

The serving analog of `inference.GPTInference._forward_cached`: the GPT
module structure is reused and the blocks run manually under the thunder
jit, but the KV state is the shared page pool (kv_pages.py) instead of a
per-request dense cache.

Two compiled programs:

* prefill — per prompt-length BUCKET (power-of-two): dense causal attention
  over the padded prompt, page write-out of the prompt's K/V, logits at the
  true last token. One thunder specialization per bucket; buckets come from
  the system-wide BucketLadder (compile_service/buckets.py), which also
  keeps the steady-state MRU bookkeeping.
* decode — ONE program for the whole engine: every active sequence
  contributes one token; k/v land in the pool at (page_table[pos//ps],
  pos%ps) through ltorch.paged_write (a pallas writer of the aligned tiles on
  TPU, a row scatter on CPU) and attention runs over the pages
  (ltorch.paged_attention — pallas kernel on TPU, jax gather on CPU).
* chunk_prefill — one CHUNK of a long (or prefix-shared) prompt: page-
  aligned writes starting at an arbitrary page boundary `start_pos`, with
  write-then-attend paged attention (ltorch.paged_chunk_attention) so the
  chunk's queries see both the previously written pages (including pages
  SHARED from the prefix cache) and their own chunk. The scheduler
  interleaves chunks into decode iterations under a token budget.
* verify — the speculative-decoding target step: k+1 tokens per packed
  sequence (the current token plus k draft proposals) processed in ONE
  program with logits at every position; the scheduler samples all k+1
  positions with the position-keyed sampler and commits the accepted
  prefix. Rolled-back positions are simply never committed — their page
  slots hold stale values that the next committed token overwrites.

* block — a pass of generation by diffusion over blocks (``block_cfn``): the
  verify program with another mask. TWO adjacent blocks a slot, 2K rows: the
  block the sequence has just finished, run once more over its final tokens so
  that its keys and values stay (it is SETTLED), and the next block, which is
  denoised; true positions for rope and the writes, the last position of a
  row's own block as its coverage, the head over the second block's K rows
  only. Either half of a slot may be inactive (``live``): its rows write to the
  null page and route to no expert (docs/serving.md).

All are pure functional: cached state goes in, updated state comes out. Every
program CONSUMES the state it is given (`donated_argnums`): the caller rebinds
the returned arrays and never reads the old ones again, so XLA writes the new
keys, values and recurrent state in place and copies no pool.

The programs know nothing of what a layer is. A served model is a list of
LAYERS, each of which says what it caches (``kv_pages.PagedKV`` with or
without a window, ``PagedLatent`` rows with no head axis, ``ReadsKV`` of
another layer's pools, ``Recurrent`` per-slot arrays, or ``None``) and gives its work for a whole prompt (``prefill``), a
chunk that starts from stored state (``chunk``), one decode token
(``decode``) and, where speculative decoding can use it, ``verify``. The
runner hands each layer the ``Step`` (where the program's tokens are: pages,
positions, slot) and the layer's own arrays, and collects what comes back.
``DenseBlock`` below is the dense rope GPT block as one such layer; a model
that is no dense GPT brings its own through ``model.serving()``
(models/sambay.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

from ..core.trace import named_scope
from ..inference import cached_sdpa, split_qkv_rope
from ..observability import events as _obs_events
from ..observability import runtime as _obs_runtime
from ..ops import clang, ltorch
from .kv_pages import PagedKV, PagedLatent


def _annotated(cfn, name: str):
    """Wrap one compiled serving program so each dispatch runs under a
    host-side profiler annotation (``annotate_call`` — a shared no-op
    context when the bus is disabled, so the hot path pays one enabled()
    read). The wrapper keeps ``_cfn`` pointing at the real compiled
    function, which is the fallback attribute ``last_compile_report``
    already resolves through."""

    @functools.wraps(cfn)
    def dispatch(*args, **kwargs):
        with _obs_runtime.annotate_call(name):
            return cfn(*args, **kwargs)

    dispatch._cfn = cfn
    return dispatch


LANES = 128  # a TPU vector register's lanes: what a pool row should fill

# What a decode program whose layers route tokens to experts counts of one step, summed over
# its layers (``serve.<name>`` on the bus): rows routed (live tokens x experts a token), those
# on experts held here, held experts with a row, the most rows on one held expert; and, only
# where the layers have zero-compute (identity) experts, a fifth: rows that chose one of those.
# A layer leaves its counts in ``step.shared["counted"]`` when it is traced with the bus on, the
# program then has one small output more (as many numbers as its layers count, in this order),
# and the scheduler records it as the step's tokens land.
ROUTING_COUNTERS = ("moe.rows_routed", "moe.rows_held", "moe.experts_touched", "moe.rows_max",
                    "moe.rows_zero")


def heads_a_row(n_kv_heads: int, head_size: int) -> int:
    """KV heads a pool row holds side by side: as many as fill the 128 lanes,
    where the head count divides. The paged decode kernel copies whole pages
    out of HBM itself, which Mosaic takes only of rows that fill the lanes (a
    narrower row is padded to 128 in HBM anyway), so a head of 64 is cached two
    a row, as models/sambay.py caches a pair."""
    r = LANES // head_size if LANES % head_size == 0 else 1
    return r if n_kv_heads % r == 0 else 1


def _pack_heads(x, r: int):
    """(B, Hkv, T, D) keys or values -> (B, Hkv // r, T, r * D): ``r``
    neighbouring heads side by side in one row."""
    if r == 1:
        return x
    B, Hkv, T, D = x.shape
    x = ltorch.permute(ltorch.reshape(x, (B, Hkv // r, r, T, D)), (0, 1, 3, 2, 4))
    return ltorch.reshape(x, (B, Hkv // r, T, r * D))


def _spread_queries(q, r: int, g: int):
    """(B, H, T, D) queries -> (B, H, T, r * D) against rows of ``r`` packed
    heads: each query over the lanes of its own key head (query head h reads
    key head h // g), zeros over the others', so the packed row's product is
    the one head's and a paged kernel sees Hkv // r heads of r * g queries."""
    if r == 1:
        return q
    B, H, T, D = q.shape
    q = ltorch.reshape(q, (B, H // (r * g), r, g, T, D))
    zeros = ltorch.zeros_like(q[:, :, 0])
    rows = [ltorch.cat([q[:, :, j] if i == j else zeros for i in range(r)], -1) for j in range(r)]
    return ltorch.reshape(ltorch.stack(rows, 2), (B, H, T, r * D))


def _own_lanes(y, r: int, g: int):
    """(B, H, T, r * D) attention output over packed value rows -> (B, H, T, D):
    each query head's own value head."""
    if r == 1:
        return y
    B, H, T, RD = y.shape
    y = ltorch.reshape(y, (B, H // (r * g), r, g, T, r, RD // r))
    return ltorch.reshape(ltorch.stack([y[:, :, j, :, :, j] for j in range(r)], 2),
                          (B, H, T, RD // r))


def _page_blocks(x, ps: int):
    """(1, Hkv, T, D) keys or values -> (T // ps, Hkv, ps, D): the head-major
    page blocks the pool stores (kv_pages.py)."""
    _, Hkv, T, D = x.shape
    return ltorch.permute(ltorch.reshape(x, (Hkv, T // ps, ps, D)), (1, 0, 2, 3))


def _write_pages(pool, page_ids, rows, ps: int):
    """pool[page_ids] = the page blocks of ``rows`` (1, Hkv, T, D): a prompt's or a chunk's
    whole pages."""
    with named_scope("kv_write"):
        return ltorch.index_put(pool, (page_ids,), _page_blocks(rows, ps))


def _write_tokens(state, page, slot, k_tok, v_tok):
    """Both pools of a layer, ``state`` (keys, values) (P, Hkv, ps, D), with a
    program's new rows k_tok / v_tok (B, K1, Hkv, D) at page / slot ((B,) or
    (B * K1,): the ``Step``'s ``page_of`` and ``slot_in_page``):
    ``ltorch.paged_write``, a Pallas writer on the chip."""
    B, K1 = k_tok.shape[:2]
    with named_scope("kv_write"):
        return ltorch.paged_write(state[0], state[1], ltorch.reshape(page, (B, K1)),
                                  ltorch.reshape(slot, (B, K1)), k_tok, v_tok)


def quantize_for_serving(gpt, mode: Optional[str]):
    """Apply weight-only quantization to a GPT before its paged programs are
    traced. ``mode``: None/``"none"`` is a no-op; ``"int8"`` swaps every
    Linear's weights for symmetric per-output-channel int8 + f32 scales
    (transforms/quantization.py), so the packed decode step's matmuls run
    int8 x bf16 with the dequant in-register — the Pallas int8_linear kernel
    on TPU (executors/pallasex.py; weights stay int8-resident in HBM, which
    is the decode-bandwidth win), XLA's dequant-matmul elsewhere.

    Must run BEFORE PagedGPTRunner traces the programs and before the engine
    snapshots ``named_parameters`` — both see the quantized module."""
    if mode in (None, "none"):
        return gpt
    if mode != "int8":
        raise ValueError(f"unknown serving quantization mode: {mode!r}")
    from ..transforms.quantization import QuantizeInt8Transform

    QuantizeInt8Transform().transform_module(gpt)
    return gpt


def bucket_len(n: int, *, minimum: int, maximum: int) -> int:
    """Next power-of-two >= n, floored at `minimum` (>= page_size so every
    bucket is page-aligned) and capped at `maximum` (= max_seq).

    Compat shim: the rounding rule now lives in the system-wide
    ``compile_service.buckets.BucketLadder`` (one ladder shared by serving
    prompt buckets, the bucketed TrainStep, and artifact keys)."""
    return _ladder(minimum, maximum).bucket_for(n)


@functools.lru_cache(maxsize=64)
def _ladder(minimum: int, maximum: int):
    from ..compile_service.buckets import BucketLadder

    return BucketLadder(minimum, maximum)


class Step:
    """Where one program's tokens are, for the layers it runs.

    Every program sets ``program`` (the name of the layer method it calls),
    ``states`` (one tuple of arrays a layer, replaced as the layers run, so a
    later layer that reads an earlier one's pools finds the written ones) and
    ``shared`` (what a layer or the model leaves for later layers: rope rows,
    a memory). Pages come by kind, ``"full"`` or ``"window"``
    (``PagedKV.kind``). Beyond that:

    prefill  ``page_ids[kind]`` (Lb / page_size,) the pages the bucket writes;
             ``last`` the true last token; ``slot`` the decode slot it is for
    chunk    ``tables[kind]`` (1, n_pages_max) the sequence's whole table;
             ``chunk_pages[kind]`` the pages this chunk writes; ``start_pos``;
             ``q_pos`` (1, T) absolute positions and ``mask_pos`` (1, T) what
             each row's attention covers (``q_pos``; under a block length the
             last position of the row's block); ``last`` relative to the
             chunk; ``slot``
    decode   ``tables[kind]`` (B, n_pages_max); ``pos`` (B,) write positions;
             ``page_of[kind]`` (B,) and ``slot_in_page`` (B,) where each
             token's k/v lands; ``seq_lens`` (B,); ``live`` (B,) bool, false
             for idle slots (they carry pos 0 and a null-page row)
    verify   as decode with K1 tokens a sequence: ``pos_mat`` (B, K1) the TRUE
             positions, for rope and the writes; ``mask_pos`` (B, K1) what each
             row's attention covers (keys at positions <= it): ``pos_mat`` in a
             speculative step, the last position of the row's block in a block
             pass (``block`` true: K1 = 2K, and ``live`` (B, K1) says which
             rows, a block of a slot's two together, are run for a sequence;
             the others carry position 0 and the null page);
             ``page_of[kind]`` and ``slot_in_page`` flat (B * K1,)
    mixed    a chunk's T rows and after them one row a decode slot: ``chunk``
             and ``decode``, the two programs' own ``Step``s; ``states`` and
             ``shared`` are this step's
    """

    def __init__(self, program: str, page_size: int, **where):
        self.program = program
        self.page_size = page_size
        self.states: list = []
        self.shared: dict = {}
        self.__dict__.update(where)


def _token_pages(tables: dict, pos, ps: int) -> tuple:
    """For write positions ``pos`` ((B,) or (B, K)): the page of each kind each
    lands in and its slot there. Positions at/past a table's coverage (draft
    proposal steps near the max_new/max_seq cap run up to spec_k - 1 positions
    ahead) go to the null page: garbage logits for those slots are never
    committed (scheduler accept rule), and the null page is masked
    everywhere."""
    B = pos.shape[0]
    pos2 = ltorch.reshape(pos, (B, -1))
    page_of = {}
    for kind, table in tables.items():
        cover = table.shape[1] * ps
        page = ltorch.gather(table, 1, ltorch.floor_divide(
            ltorch.clamp(pos2, max=cover - 1), ps))
        page = ltorch.where(ltorch.lt(pos2, cover), page, 0)
        page_of[kind] = ltorch.reshape(page, tuple(pos.shape))
    return page_of, ltorch.remainder(pos, ps)


class DenseBlock:
    """One block of a dense rope GPT (models/litgpt.py Block, models/moe.py
    MoEBlock) as a served layer: paged keys and values of every position.
    The q/k/v split with rope and the residual/MLP tail are shared with the
    dense engine (inference.split_qkv_rope / the block's own ``tail``) — one
    implementation, so solo and batched decode can never drift. Heads
    narrower than the lanes are cached ``pack`` a row (``heads_a_row``) and
    the queries of the paged programs spread to match: a pool of
    ``n_query_groups // pack`` heads, ``pack * head_size`` wide."""

    def __init__(self, block, cfg):
        self.block = block
        self.cfg = cfg
        self.pack = heads_a_row(cfg.n_query_groups, cfg.head_size)
        self.cache = PagedKV(cfg.n_query_groups // self.pack, self.pack * cfg.head_size,
                             self.pack * cfg.head_size)
        self.scale = 1.0 / math.sqrt(cfg.head_size)  # of a key head, not of the packed row
        moe = getattr(block, "moe", None)
        if moe is not None and moe.cfg.capacity_factor is not None:
            # an expert's capacity follows from how many rows the program has: decode rows
            # beside a chunk's would be dropped where alone they are not
            self.mixed = None

    def _qkv(self, step, x):
        return split_qkv_rope(self.block, self.cfg, self.block.norm_1(x),
                              step.shared["cos"], step.shared["sin"])

    def _rows(self, k, v):
        """k and v (B, n_query_groups, T, hs) as the pool's rows."""
        return _pack_heads(k, self.pack), _pack_heads(v, self.pack)

    def _tokens(self, k, v):
        """The pool rows of k and v (B, n_query_groups, T, hs), a token each:
        two (B, T, heads, width)."""
        return tuple(ltorch.permute(rows, (0, 2, 1, 3)) for rows in self._rows(k, v))

    def _paged(self, attend, q, kp, vp, table, where):
        """``attend`` (a paged attention of ops/ltorch.py) for q (B, n_head, T,
        hs) against the pools -> (B, n_head, T, hs)."""
        g = self.cfg.n_head // self.cfg.n_query_groups
        y = attend(_spread_queries(q, self.pack, g), kp, vp, table, where, self.scale)
        return _own_lanes(y, self.pack, g)

    def _tail(self, step, x, h):
        """The block's output from its input and its attention's output (``block.tail``)."""
        return self.block.tail(x, h)

    def _proj(self, x, y, T: int):
        """y (B, n_head, T, hs) attention output -> what the attention adds to ``x``."""
        cfg = self.cfg
        y = ltorch.reshape(ltorch.permute(y, (0, 2, 1, 3)),
                           (x.shape[0], T, cfg.n_head * cfg.head_size))
        return self.block.attn.proj(y)

    def prefill(self, step, x, state):
        """Dense causal attention over the padded prompt and page write-out
        of its K/V. Padding tokens beyond ``step.last`` write garbage K/V
        into the tail pages — causality keeps them out of every real token's
        attention and seq_lens masks them out of later paged decode."""
        from ..models.litgpt import _repeat_kv

        cfg = self.cfg
        T = x.shape[1]
        ps = step.page_size
        page_ids = step.page_ids["full"]
        q_per_kv = cfg.n_head // cfg.n_query_groups
        with named_scope("attn"):
            q, k, v = self._qkv(step, x)
            k_rows, v_rows = self._rows(k, v)
            kp = _write_pages(state[0], page_ids, k_rows, ps)
            vp = _write_pages(state[1], page_ids, v_rows, ps)
            kq = _repeat_kv(k, q_per_kv) if cfg.n_query_groups != cfg.n_head else k
            vq = _repeat_kv(v, q_per_kv) if cfg.n_query_groups != cfg.n_head else v
            h = self._proj(x, cached_sdpa(q, kq, vq, 0), T)
        return self._tail(step, x, h), (kp, vp)

    def _decode_rows(self, step, q, k, v, state):
        """One token a sequence, q (B, n_head, 1, hs), k and v (B, n_query_groups, 1,
        hs): each token's k/v to its page and slot, then attention over the
        sequence's pages. Returns (y (B, n_head, 1, hs), new state)."""
        kp, vp = _write_tokens(state, step.page_of["full"], step.slot_in_page, *self._tokens(k, v))

        def attend(q4, kp, vp, table, seq_lens, scale):  # one query a sequence: (B, H, D)
            B, H, _, D = q4.shape
            y = ltorch.paged_attention(ltorch.reshape(q4, (B, H, D)), kp, vp, table, seq_lens, scale)
            return ltorch.reshape(y, (B, H, 1, y.shape[-1]))

        return self._paged(attend, q, kp, vp, step.tables["full"], step.seq_lens), (kp, vp)

    def _chunk_rows(self, step, q, k, v, state):
        """A chunk's rows, q (1, n_head, T, hs): the chunk WRITES its pages first
        and then attends the whole table with per-query coverage k_pos <=
        start_pos + t, so it sees every previously written page — including
        pages shared from the prefix cache (copy-on-write sharing; the chunk
        itself only ever writes UNSHARED pages, because shared coverage always
        ends at or before the chunk start). Pad tokens past ``step.last`` on
        the final chunk write garbage K/V into reserved-but-unused page slots;
        every real query masks them out by position, and decode overwrites
        each slot before seq_lens ever admits it."""
        ps = step.page_size
        k_rows, v_rows = self._rows(k, v)
        kp = _write_pages(state[0], step.chunk_pages["full"], k_rows, ps)
        vp = _write_pages(state[1], step.chunk_pages["full"], v_rows, ps)
        y = self._paged(ltorch.paged_chunk_attention, q, kp, vp, step.tables["full"], step.mask_pos)
        return y, (kp, vp)

    def decode(self, step, x, state):
        with named_scope("attn"):
            y, state = self._decode_rows(step, *self._qkv(step, x), state)
            h = self._proj(x, y, 1)
        return self._tail(step, x, h), state

    def chunk(self, step, x, state):
        with named_scope("attn"):
            y, state = self._chunk_rows(step, *self._qkv(step, x), state)
            h = self._proj(x, y, x.shape[1])
        return self._tail(step, x, h), state

    def mixed(self, step, x, state):
        """A chunk's T rows and, after them, one row a decode slot, x (1, T + B,
        D): ONE operand of the norm, the q/k/v projection, the rope, the output
        projection and the tail, so the block's weights are read once for both.
        Only the writes and the attention split, each kind of row through the
        body its own program runs (``step.chunk``, ``step.decode``: the two
        programs' ``Step``s). The two write to different pages: a decode row
        to its own sequence's, an idle one to the null page."""
        T = step.chunk.T

        def seqs(a):  # the decode rows, a sequence each: (1, H, B, hs) <-> (B, H, 1, hs)
            return ltorch.permute(a, (2, 1, 0, 3))

        with named_scope("attn"):
            q, k, v = self._qkv(step, x)
            y_c, state = self._chunk_rows(step.chunk, q[:, :, :T], k[:, :, :T], v[:, :, :T], state)
            y_d, state = self._decode_rows(step.decode, seqs(q[:, :, T:]), seqs(k[:, :, T:]),
                                           seqs(v[:, :, T:]), state)
            h = self._proj(x, ltorch.cat([y_c, seqs(y_d)], 2), x.shape[1])
        return self._tail(step, x, h), state

    def verify(self, step, x, state):
        """Writes k/v for ALL k+1 tokens at positions pos..pos+k. Rollback is
        free: the scheduler commits only the accepted prefix; rejected
        positions hold stale k/v that the next committed token's write
        replaces before any mask admits it. A row's coverage is ``step.mask_pos``:
        its own position in a speculative verify step, its block's LAST position
        in a pass of generation by diffusion over blocks (every row of a block
        sees the whole block; the rows of the second of a slot's two blocks see
        the first as written here, just above), which is what makes the two one
        method."""
        with named_scope("attn"):
            q, k, v = self._qkv(step, x)
            kp, vp = _write_tokens(state, step.page_of["full"], step.slot_in_page,
                                   *self._tokens(k, v))
            y = self._paged(ltorch.paged_chunk_attention, q, kp, vp, step.tables["full"],
                            step.mask_pos)
            h = self._proj(x, y, x.shape[1])
        return self._tail(step, x, h), (kp, vp)


class RoutedBlock(DenseBlock):
    """A dense-attention block whose tail routes its tokens to held experts
    (models/block_moe.py: ``block.experts`` a ``moe.HeldExperts``): the tail is told
    which rows are padding (``step.shared["live"]``: an idle slot, a bucket's tail enter
    no group and read no panel) and, in the decode program and in a block pass traced
    with the bus on, counts its routing (``ROUTING_COUNTERS``)."""

    # a pass's two blocks a slot ride in no chunk's program yet: the engine runs the two apart
    mixed = None

    def _tail(self, step, x, h):
        counted = None
        if (step.program == "decode" or getattr(step, "block", False)) and _obs_events.enabled():
            counted = step.shared.setdefault("counted", [])  # a trace-time gate, as in latent_moe.Block
        return self.block.tail(x, h, step.shared["live"], counted)


class DenseGPT:
    """models.litgpt.GPT (or moe.MoEGPT, block_moe.BlockMoE) as a served model: its
    blocks as layers, its rope table gathered once a program at the program's
    positions, its embedding and its untied head."""

    def __init__(self, gpt):
        self.gpt = gpt
        self.cfg = gpt.cfg
        self.layers = [(RoutedBlock if hasattr(block, "experts") else DenseBlock)(block, gpt.cfg)
                       for block in gpt.h]
        self.routes = any(isinstance(layer, RoutedBlock) for layer in self.layers)
        self.max_positions = gpt.cos.shape[0]  # the rope table's rows

    def begin(self, step) -> None:
        """Rope rows for the program's positions, into ``step.shared``.
        Decode and verify clamp positions past the table: those slots'
        logits are garbage and the accept rule never commits them. A mixed
        program's rows are gathered by position, all of them at once."""
        with named_scope("attn/rope"):
            step.shared["cos"], step.shared["sin"] = self._rope_rows(step)
        if self.routes:
            with named_scope("moe_router"):
                step.shared["live"] = self._live_rows(step)

    @staticmethod
    def _live_rows(step):
        """(rows,) bool: the program's rows that are no padding, for the expert layers."""
        from ..core import dtypes, prims

        if step.program in ("prefill", "chunk"):
            t = prims.iota(step.T, dtype=dtypes.int32, device=step.last.device)
            return ltorch.le(t, step.last)
        if step.program == "decode":
            return step.live
        B, K1 = step.pos_mat.shape
        if getattr(step, "block", False):  # a block pass says which rows are (``live``)
            return ltorch.reshape(step.live, (B * K1,))
        # a speculative step: a sequence's rows are live together, idle slots carry position 0
        live = ltorch.gt(step.pos_mat[:, 0], 0)
        return ltorch.reshape(ltorch.expand(ltorch.unsqueeze(live, 1), (B, K1)), (B * K1,))

    def _rope_rows(self, step):
        from ..core import prims

        gpt, n_elem = self.gpt, self.cfg.rope_n_elem
        cos_t, sin_t = clang.ensure_proxy(gpt.cos), clang.ensure_proxy(gpt.sin)
        if step.program == "prefill":
            cos, sin = cos_t[:step.T], sin_t[:step.T]
        elif step.program == "chunk":
            cos = prims.dynamic_slice(cos_t, (step.start_pos, 0), (step.T, n_elem))
            sin = prims.dynamic_slice(sin_t, (step.start_pos, 0), (step.T, n_elem))
        elif step.program == "mixed":  # (T + B, n_elem): the chunk's rows, then the decode rows'
            rows = ltorch.cat([ltorch.reshape(step.chunk.q_pos, (-1,)),
                               ltorch.clamp(step.decode.pos, max=self.max_positions - 1)], 0)
            cos, sin = clang.take(cos_t, rows, 0), clang.take(sin_t, rows, 0)
        else:
            pos = step.pos if step.program == "decode" else step.pos_mat
            B = pos.shape[0]
            rows = ltorch.reshape(ltorch.clamp(pos, max=self.max_positions - 1), (-1,))
            cos = ltorch.reshape(clang.take(cos_t, rows, 0), (B, 1, -1, n_elem))
            sin = ltorch.reshape(clang.take(sin_t, rows, 0), (B, 1, -1, n_elem))
        return cos, sin

    def embed(self, toks):
        return self.gpt.wte(toks)

    def head(self, x):
        return self.gpt.lm_head(self.gpt.ln_f(x))


class PagedGPTRunner:
    """Traces and caches the paged prefill/decode programs for one model."""

    def __init__(self, gpt, *, page_size: int, block_length: Optional[int] = None):
        from .. import jit as _jit
        from ..nn.module import functional_params

        self.gpt = gpt
        self.cfg = gpt.cfg
        self.page_size = page_size
        # generation by diffusion over blocks of this many positions: a prompt chunk's rows
        # attend BLOCK-causally, and ``block_cfn`` runs a pass
        self.block_length = block_length
        # a model that is no dense GPT says how it is served; a GPT is its blocks
        self.model = gpt.serving() if hasattr(gpt, "serving") else DenseGPT(gpt)
        self.page_kinds = tuple(k for k in ("full", "window") if any(
            isinstance(layer.cache, (PagedKV, PagedLatent)) and layer.cache.kind == k
            for layer in self.model.layers))
        # the chunk program takes the decode step's rows too where EVERY layer can run both
        # kinds of rows through its weights at once (a ``mixed`` beside chunk and decode)
        self.mixes = all(callable(getattr(layer, "mixed", None)) for layer in self.model.layers)
        # a pass over blocks needs layers whose ``verify`` takes the mask apart from the
        # positions: paged keys and values of every position (``DenseBlock``), no other
        self.blocks = all(isinstance(layer, DenseBlock) for layer in self.model.layers)

        def prefill(params, idx, page_ids, state, last_pos, slot):
            with functional_params(gpt, params):
                return self._forward_prefill(idx, page_ids, state, last_pos, slot)

        def decode(params, toks, state, tables, pos):
            with functional_params(gpt, params):
                return self._forward_decode(toks, state, tables, pos)

        def chunk_prefill(params, idx, table_rows, state, start_pos, last_rel, slot, rows=None):
            with functional_params(gpt, params):
                return self._forward_chunk(idx, table_rows, state, start_pos, last_rel, slot, rows)

        def verify(params, toks, state, tables, pos):
            with functional_params(gpt, params):
                return self._forward_verify(toks, state, tables, pos)

        def block(params, toks, state, tables, pos, live):
            with functional_params(gpt, params):
                return self._forward_verify(toks, state, tables, pos, live)

        prefill.__name__ = "serve_prefill"
        decode.__name__ = "serve_decode"
        chunk_prefill.__name__ = "serve_chunk_prefill"
        verify.__name__ = "serve_verify"
        block.__name__ = "serve_block"
        # the calling convention, not a knob: every call site passes the
        # cache's state and rebinds the returned one on its next line, so
        # the state is given up (its position in each signature above)
        # where a sequence's decode rows run now in the decode program and now beside a chunk's
        # rows, both programs round at every op: by default XLA keeps a fused chain's
        # intermediates in float32, which chains it fuses follows from the shapes, and a row
        # would get other bits in one program than in the other (a greedy token at a near-tie)
        both = {"round_every_op": True} if self.mixes else {}
        self.prefill_cfn = _annotated(_jit(prefill, donated_argnums=(3,)), "serve_prefill")
        self.decode_cfn = _annotated(_jit(decode, donated_argnums=(2,), **both), "serve_decode")
        self.chunk_cfn = _annotated(_jit(chunk_prefill, donated_argnums=(3,), **both),
                                    "serve_chunk_prefill")
        self.verify_cfn = _annotated(_jit(verify, donated_argnums=(2,)), "serve_verify")
        self.block_cfn = _annotated(_jit(block, donated_argnums=(2,)), "serve_block")

    def _by_kind(self, per_kind) -> dict:
        """The programs take page ids and tables as one array a page kind, in
        the order of ``page_kinds``."""
        return dict(zip(self.page_kinds, per_kind))

    def _embed(self, toks):
        with named_scope("embed"):  # whatever the served model is; `head` likewise, at its calls
            return self.model.embed(toks)

    def _run_layers(self, step, x, state):
        step.states = list(state)
        for i, layer in enumerate(self.model.layers):
            x, step.states[i] = getattr(layer, step.program)(step, x, step.states[i])
        return x, tuple(tuple(s) for s in step.states)

    # -- prefill ----------------------------------------------------------
    def _forward_prefill(self, idx, page_ids, state, last_pos, slot):
        """idx (1, Lb) bucketed prompt; page_ids: for each page kind the
        (Lb/page_size,) pages to write; last_pos scalar int32 — the true last
        token; slot scalar int32 — the decode slot the sequence will take
        (where its recurrent state goes). Returns (logits (1, V), new state)."""
        from ..core import prims

        B, T = idx.shape
        step = Step("prefill", self.page_size, T=T, page_ids=self._by_kind(page_ids),
                    last=last_pos, slot=slot)
        self.model.begin(step)
        x, state = self._run_layers(step, self._embed(idx), state)
        with named_scope("head"):  # logits at the TRUE last token (the bucket pads past it)
            x_last = prims.dynamic_slice(x, (0, last_pos, 0), (B, 1, x.shape[-1]))
            return self.model.head(x_last)[:, 0], state

    # -- decode -----------------------------------------------------------
    def _decode_step(self, tables, pos) -> Step:
        step = Step("decode", self.page_size, tables=self._by_kind(tables), pos=pos)
        with named_scope("kv_write"):
            step.page_of, step.slot_in_page = _token_pages(step.tables, pos, self.page_size)
        step.seq_lens = pos + 1  # attention covers the token being written
        step.live = ltorch.gt(pos, 0)
        return step

    @staticmethod
    def _counted(step):
        """What the layers counted of the step (``ROUTING_COUNTERS``), summed
        over them, as a tuple the program appends to what it returns: empty
        where none counted (bus off at trace time, or no layer routes)."""
        counted = step.shared.get("counted")
        if not counted:
            return ()
        total = counted[0]
        for c in counted[1:]:
            total = total + c
        return (total,)

    def _forward_decode(self, toks, state, tables, pos):
        """toks (Bcap, 1) current tokens; tables: for each page kind the
        (Bcap, n_pages_max) int32 page table; pos (Bcap,) int32 — each
        sequence's write position (= tokens already cached; idle slots carry
        pos 0 and a null-page row). Returns (logits (Bcap, V), new state), and
        after them the int32 ``ROUTING_COUNTERS`` of the step (four, or five) where
        the layers counted them (bus on at trace time)."""
        step = self._decode_step(tables, pos)
        self.model.begin(step)
        x, state = self._run_layers(step, self._embed(toks), state)
        with named_scope("head"):
            logits = self.model.head(x[:, -1])
        return (logits, state) + self._counted(step)

    # -- chunked prefill --------------------------------------------------
    def _forward_chunk(self, idx, table_rows, state, start_pos, last_rel, slot, rows=None):
        """idx (1, Cb) one page-aligned chunk of a prompt (Cb a multiple of
        page_size); table_rows: for each page kind the sequence's FULL
        (1, n_pages_max) page table; start_pos scalar int32 (multiple of
        page_size) — the chunk's absolute first position; last_rel scalar
        int32 — the true last prompt token RELATIVE to the chunk (only
        meaningful on the final chunk; earlier chunks' logits are discarded
        by the scheduler); slot as in prefill. Returns (logits (1, V), new
        state).

        With ``rows`` — the decode step's ``(toks, tables, pos)`` as
        ``_forward_decode`` takes them — the decode rows ride in this program
        (``self.mixes``): every layer runs its ``mixed`` over the chunk's T rows
        and the Bcap decode rows as one operand of its weights, the head takes
        the chunk's last row and the decode rows together, and the program
        returns (chunk logits (1, V), decode logits (Bcap, V), new state) and
        the decode rows' routing counters as the decode program does. Idle
        slots are what they are there, so the same executable serves a chunk
        with no live decode row."""
        from ..core import dtypes, prims

        B, T = idx.shape  # B == 1
        ps = self.page_size
        tables = self._by_kind(table_rows)
        step = Step("chunk", ps, T=T, tables=tables, start_pos=start_pos, last=last_rel,
                    slot=slot)
        if rows is None:
            self.model.begin(step)
        step.chunk_pages = {
            kind: ltorch.reshape(prims.dynamic_slice(
                row, (0, ltorch.floor_divide(start_pos, ps)), (1, T // ps)), (T // ps,))
            for kind, row in tables.items()}
        step.q_pos = ltorch.reshape(
            prims.iota(T, dtype=dtypes.int32, device=idx.device) + start_pos, (1, T))
        K = self.block_length
        step.mask_pos = step.q_pos if K is None else ltorch.floor_divide(step.q_pos, K) * K + (K - 1)
        if rows is None:
            x, state = self._run_layers(step, self._embed(idx), state)
            with named_scope("head"):
                x_last = prims.dynamic_slice(x, (0, last_rel, 0), (B, 1, x.shape[-1]))
                return self.model.head(x_last)[:, 0], state
        toks, dec_tables, pos = rows
        n = toks.shape[0]
        both = Step("mixed", ps, chunk=step, decode=self._decode_step(dec_tables, pos))
        self.model.begin(both)
        x = self._embed(ltorch.cat([idx, ltorch.reshape(toks, (1, n))], 1))
        x, state = self._run_layers(both, x, state)
        with named_scope("head"):
            x_last = prims.dynamic_slice(x, (0, last_rel, 0), (1, 1, x.shape[-1]))
            logits = self.model.head(ltorch.cat([x_last, x[:, T:]], 1))[0]  # (1 + Bcap, V)
            return (logits[:1], logits[1:], state) + self._counted(both)

    # -- speculative verify -----------------------------------------------
    def _forward_verify(self, toks, state, tables, pos, live=None):
        """toks (Bcap, k+1): each sequence's current token followed by its k
        draft proposals; pos (Bcap,) int32 — the position of toks[:, 0].
        Returns (logits (Bcap, k+1, V), new state) — logits at every
        position, so ONE packed target step scores every proposal.

        With ``live`` the rows are a pass of generation by diffusion over blocks
        (``block_cfn``): toks (Bcap, 2K) TWO adjacent blocks a slot, pos the first
        position of the first, live (Bcap, 2) bool which of the two are run. The first
        is the block the sequence has finished, over its final tokens: the keys and
        values this pass writes for it are the ones that stay (it is SETTLED). The
        second is the block being denoised, the mask token where a position is not
        filled yet: its keys and values are replaced by the next pass's. A row covers
        the keys up to the LAST position of its own block, so the first block's rows
        see nothing of the second and the second's see the first as this pass wrote
        it. A block that is not live (no block to settle: the pass after a prompt, a
        block's later denoise passes; nothing to denoise: a sequence's last block
        being settled; an idle slot, both) carries its rows as padding: position 0,
        the null page, no expert. Returns (logits (Bcap, K, V) of the SECOND block's
        rows, new state) and after them the routing counters, as the decode program
        does."""
        from ..core import dtypes, prims

        B, K1 = toks.shape
        block = live is not None
        offs = prims.iota(K1, dtype=dtypes.int32, device=toks.device)
        pos_mat = ltorch.reshape(pos, (B, 1)) + ltorch.reshape(offs, (1, K1))  # (B, K1)
        step = Step("verify", self.page_size, tables=self._by_kind(tables), pos_mat=pos_mat,
                    mask_pos=pos_mat, block=block)
        if block:
            K = K1 // 2
            step.live = ltorch.reshape(ltorch.expand(ltorch.unsqueeze(live, 2), (B, 2, K)), (B, K1))
            step.pos_mat = pos_mat = ltorch.where(step.live, pos_mat, 0)
            step.mask_pos = ltorch.floor_divide(pos_mat, K) * K + (K - 1)
        self.model.begin(step)
        with named_scope("kv_write"):
            page_of, slot = _token_pages(step.tables, pos_mat, self.page_size)
            if block:
                page_of = {k: ltorch.where(step.live, v, 0) for k, v in page_of.items()}
            step.page_of = {k: ltorch.reshape(v, (B * K1,)) for k, v in page_of.items()}
            step.slot_in_page = ltorch.reshape(slot, (B * K1,))
        x, state = self._run_layers(step, self._embed(toks), state)
        with named_scope("head"):
            logits = self.model.head(x[:, K1 // 2:] if block else x)  # (B, K or K1, V)
        return (logits, state) + (self._counted(step) if block else ())
