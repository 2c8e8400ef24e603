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
  pos%ps) via a batched index_put and attention runs over the pages
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

All are pure functional: pools go in, updated pools come out. Every program
CONSUMES the pools it is given (`donated_argnums`): the caller rebinds the
returned ones and never reads the old arrays again, so XLA writes the new
keys and values in place and copies no pool.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..inference import block_mix, cached_sdpa, split_qkv_rope
from ..observability import runtime as _obs_runtime
from ..ops import clang, ltorch


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


def _page_blocks(x, ps: int):
    """(1, Hkv, T, D) keys or values -> (T // ps, Hkv, ps, D): the head-major
    page blocks the pool stores (kv_pages.py)."""
    _, Hkv, T, D = x.shape
    return ltorch.permute(ltorch.reshape(x, (Hkv, T // ps, ps, D)), (1, 0, 2, 3))


def _write_tokens(pool, page, slot, tok):
    """pool[page[n], :, slot[n]] = tok[n] on a head-major pool
    (P, Hkv, ps, D): page/slot (N,) int32, tok (N, Hkv, D). One joint
    index_put over (page, head, slot) rows of D."""
    from ..core import dtypes, prims

    N, Hkv, D = tok.shape

    def rows(v, shape):  # v, viewed as `shape`, spread over the (N, Hkv) rows
        return ltorch.reshape(ltorch.expand(ltorch.reshape(v, shape), (N, Hkv)),
                              (N * Hkv,))

    heads = prims.iota(Hkv, dtype=dtypes.int32, device=tok.device)
    return ltorch.index_put(
        pool, (rows(page, (N, 1)), rows(heads, (1, Hkv)), rows(slot, (N, 1))),
        ltorch.reshape(tok, (N * Hkv, D)))


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


class PagedGPTRunner:
    """Traces and caches the paged prefill/decode programs for one GPT."""

    def __init__(self, gpt, *, page_size: int):
        from .. import jit as _jit
        from ..nn.module import functional_params

        self.gpt = gpt
        self.cfg = gpt.cfg
        self.page_size = page_size

        def prefill(params, idx, page_ids, kps, vps, last_pos):
            with functional_params(gpt, params):
                return self._forward_prefill(idx, page_ids, kps, vps, last_pos)

        def decode(params, toks, kps, vps, page_table, pos):
            with functional_params(gpt, params):
                return self._forward_decode(toks, kps, vps, page_table, pos)

        def chunk_prefill(params, idx, page_table_row, kps, vps, start_pos, last_rel):
            with functional_params(gpt, params):
                return self._forward_chunk(idx, page_table_row, kps, vps,
                                           start_pos, last_rel)

        def verify(params, toks, kps, vps, page_table, pos):
            with functional_params(gpt, params):
                return self._forward_verify(toks, kps, vps, page_table, pos)

        prefill.__name__ = "serve_prefill"
        decode.__name__ = "serve_decode"
        chunk_prefill.__name__ = "serve_chunk_prefill"
        verify.__name__ = "serve_verify"
        # the calling convention, not a knob: every call site passes the
        # cache's pools and rebinds the returned ones on its next line, so
        # kps and vps are given up (their positions in each signature above)
        self.prefill_cfn = _annotated(_jit(prefill, donated_argnums=(3, 4)), "serve_prefill")
        self.decode_cfn = _annotated(_jit(decode, donated_argnums=(2, 3)), "serve_decode")
        self.chunk_cfn = _annotated(_jit(chunk_prefill, donated_argnums=(3, 4)),
                                    "serve_chunk_prefill")
        self.verify_cfn = _annotated(_jit(verify, donated_argnums=(2, 3)), "serve_verify")

    # block plumbing (qkv split/rope, residual/MoE tail) is shared with the
    # dense engine: inference.split_qkv_rope / inference.block_mix — one
    # implementation, so solo and batched decode can never drift

    # -- prefill ----------------------------------------------------------
    def _forward_prefill(self, idx, page_ids, kps, vps, last_pos):
        """idx (1, Lb) bucketed prompt; page_ids (Lb/page_size,) pages to
        write; last_pos scalar int32 — the true last token. Returns
        (logits (1, V), new k pools, new v pools). Padding tokens beyond
        last_pos write garbage K/V into the tail pages — causality keeps
        them out of every real token's attention and seq_lens masks them
        out of later paged decode."""
        from ..core import prims
        from ..models.litgpt import _repeat_kv

        cfg = self.cfg
        gpt = self.gpt
        B, T = idx.shape
        ps = self.page_size
        n_elem = cfg.rope_n_elem
        cos = clang.ensure_proxy(gpt.cos)[:T]
        sin = clang.ensure_proxy(gpt.sin)[:T]
        q_per_kv = cfg.n_head // cfg.n_query_groups
        x = gpt.wte(idx)
        new_kps, new_vps = [], []
        for li, block in enumerate(gpt.h):
            q, k, v = split_qkv_rope(block, cfg, block.norm_1(x), cos, sin)
            new_kps.append(ltorch.index_put(kps[li], (page_ids,), _page_blocks(k, ps)))
            new_vps.append(ltorch.index_put(vps[li], (page_ids,), _page_blocks(v, ps)))
            kq = _repeat_kv(k, q_per_kv) if cfg.n_query_groups != cfg.n_head else k
            vq = _repeat_kv(v, q_per_kv) if cfg.n_query_groups != cfg.n_head else v
            y = cached_sdpa(q, kq, vq, 0)
            y = ltorch.reshape(ltorch.permute(y, (0, 2, 1, 3)),
                               (B, T, cfg.n_head * cfg.head_size))
            x = block_mix(block, cfg, x, block.attn.proj(y))
        # logits at the TRUE last token (the bucket pads past it)
        x_last = prims.dynamic_slice(x, (0, last_pos, 0), (B, 1, cfg.n_embd))
        logits = gpt.lm_head(gpt.ln_f(x_last))[:, 0]
        return logits, tuple(new_kps), tuple(new_vps)

    # -- decode -----------------------------------------------------------
    def _forward_decode(self, toks, kps, vps, page_table, pos):
        """toks (Bcap, 1) current tokens; page_table (Bcap, n_pages_max)
        int32; pos (Bcap,) int32 — each sequence's write position (= tokens
        already cached; idle slots carry pos 0 and a null-page row).
        Returns (logits (Bcap, V), new k pools, new v pools).

        Positions at/past the table's coverage (draft proposal steps near
        the max_new/max_seq cap run the decode program up to spec_k - 1
        positions ahead) clamp the rope gather and redirect the k/v write to
        the null page — garbage logits for those slots are never committed
        (scheduler accept rule), and the null page is masked everywhere."""
        cfg = self.cfg
        gpt = self.gpt
        B, T = toks.shape  # T == 1
        ps = self.page_size
        rope_rows = gpt.cos.shape[0]
        pos_r = ltorch.clamp(pos, max=rope_rows - 1)
        # per-sequence rope rows: gather cos/sin at each slot's position
        cos = ltorch.reshape(clang.take(clang.ensure_proxy(gpt.cos), pos_r, 0),
                             (B, 1, 1, cfg.rope_n_elem))
        sin = ltorch.reshape(clang.take(clang.ensure_proxy(gpt.sin), pos_r, 0),
                             (B, 1, 1, cfg.rope_n_elem))
        npm = page_table.shape[1]
        in_bounds = ltorch.lt(pos, npm * ps)
        page_of = ltorch.gather(page_table, 1, ltorch.reshape(
            ltorch.floor_divide(ltorch.clamp(pos, max=npm * ps - 1), ps),
            (B, 1)))[:, 0]  # (B,) page id
        page_of = ltorch.where(in_bounds, page_of, 0)
        slot = ltorch.remainder(pos, ps)
        seq_lens = pos + 1  # attention covers the token being written
        x = gpt.wte(toks)
        new_kps, new_vps = [], []
        for li, block in enumerate(gpt.h):
            q, k, v = split_qkv_rope(block, cfg, block.norm_1(x), cos, sin)
            k_tok = ltorch.reshape(ltorch.permute(k, (0, 2, 1, 3)),
                                   (B, cfg.n_query_groups, cfg.head_size))
            v_tok = ltorch.reshape(ltorch.permute(v, (0, 2, 1, 3)),
                                   (B, cfg.n_query_groups, cfg.head_size))
            kp = _write_tokens(kps[li], page_of, slot, k_tok)
            vp = _write_tokens(vps[li], page_of, slot, v_tok)
            new_kps.append(kp)
            new_vps.append(vp)
            q3 = ltorch.reshape(q, (B, cfg.n_head, cfg.head_size))
            y = ltorch.paged_attention(q3, kp, vp, page_table, seq_lens)
            y = ltorch.reshape(y, (B, 1, cfg.n_head * cfg.head_size))
            x = block_mix(block, cfg, x, block.attn.proj(y))
        logits = gpt.lm_head(gpt.ln_f(x[:, -1]))
        return logits, tuple(new_kps), tuple(new_vps)

    # -- chunked prefill --------------------------------------------------
    def _forward_chunk(self, idx, page_table_row, kps, vps, start_pos, last_rel):
        """idx (1, Cb) one page-aligned chunk of a prompt (Cb a multiple of
        page_size); page_table_row (1, n_pages_max) the sequence's FULL page
        table; start_pos scalar int32 (multiple of page_size) — the chunk's
        absolute first position; last_rel scalar int32 — the true last
        prompt token RELATIVE to the chunk (only meaningful on the final
        chunk; earlier chunks' logits are discarded by the scheduler).
        Returns (logits (1, V), new k pools, new v pools).

        The chunk WRITES its pages first and then attends the whole table
        with per-query coverage k_pos <= start_pos + t, so it sees every
        previously written page — including pages shared from the prefix
        cache (copy-on-write sharing; the chunk itself only ever writes
        UNSHARED pages, because shared coverage always ends at or before
        the chunk start). Pad tokens past `last_rel` on the final chunk
        write garbage K/V into reserved-but-unused page slots; every real
        query masks them out by position, and decode overwrites each slot
        before seq_lens ever admits it."""
        cfg = self.cfg
        gpt = self.gpt
        B, T = idx.shape  # B == 1
        ps = self.page_size
        n_elem = cfg.rope_n_elem
        from ..core import dtypes, prims

        cos = prims.dynamic_slice(clang.ensure_proxy(gpt.cos), (start_pos, 0),
                                  (T, n_elem))
        sin = prims.dynamic_slice(clang.ensure_proxy(gpt.sin), (start_pos, 0),
                                  (T, n_elem))
        chunk_pages = ltorch.reshape(
            prims.dynamic_slice(page_table_row,
                                (0, ltorch.floor_divide(start_pos, ps)),
                                (1, T // ps)), (T // ps,))
        q_pos = ltorch.reshape(
            prims.iota(T, dtype=dtypes.int32, device=idx.device) + start_pos, (1, T))
        x = gpt.wte(idx)
        new_kps, new_vps = [], []
        for li, block in enumerate(gpt.h):
            q, k, v = split_qkv_rope(block, cfg, block.norm_1(x), cos, sin)
            kp = ltorch.index_put(kps[li], (chunk_pages,), _page_blocks(k, ps))
            vp = ltorch.index_put(vps[li], (chunk_pages,), _page_blocks(v, ps))
            new_kps.append(kp)
            new_vps.append(vp)
            y = ltorch.paged_chunk_attention(q, kp, vp, page_table_row, q_pos)
            y = ltorch.reshape(ltorch.permute(y, (0, 2, 1, 3)),
                               (B, T, cfg.n_head * cfg.head_size))
            x = block_mix(block, cfg, x, block.attn.proj(y))
        x_last = prims.dynamic_slice(x, (0, last_rel, 0), (B, 1, cfg.n_embd))
        logits = gpt.lm_head(gpt.ln_f(x_last))[:, 0]
        return logits, tuple(new_kps), tuple(new_vps)

    # -- speculative verify -----------------------------------------------
    def _forward_verify(self, toks, kps, vps, page_table, pos):
        """toks (Bcap, k+1): each sequence's current token followed by its k
        draft proposals; pos (Bcap,) int32 — the position of toks[:, 0].
        Writes k/v for ALL k+1 tokens at positions pos..pos+k and returns
        (logits (Bcap, k+1, V), new k pools, new v pools) — logits at every
        position, so ONE packed target step scores every proposal.

        Rollback is free: the scheduler commits only the accepted prefix;
        rejected positions hold stale k/v that the next committed token's
        write replaces before any mask admits it. Writes past the table's
        coverage (proposals past the max_seq cap) redirect to the null
        page; rope gathers clamp — those positions' logits are garbage and
        the accept rule never commits them."""
        cfg = self.cfg
        gpt = self.gpt
        B, K1 = toks.shape
        ps = self.page_size
        npm = page_table.shape[1]
        n_elem = cfg.rope_n_elem
        rope_rows = gpt.cos.shape[0]
        from ..core import dtypes, prims

        offs = prims.iota(K1, dtype=dtypes.int32, device=toks.device)
        pos_mat = ltorch.reshape(pos, (B, 1)) + ltorch.reshape(offs, (1, K1))  # (B, K1)
        flat_pos = ltorch.reshape(ltorch.clamp(pos_mat, max=rope_rows - 1),
                                  (B * K1,))
        cos = ltorch.reshape(clang.take(clang.ensure_proxy(gpt.cos), flat_pos, 0),
                             (B, 1, K1, n_elem))
        sin = ltorch.reshape(clang.take(clang.ensure_proxy(gpt.sin), flat_pos, 0),
                             (B, 1, K1, n_elem))
        in_bounds = ltorch.lt(pos_mat, npm * ps)
        page_of = ltorch.gather(page_table, 1,
                                ltorch.floor_divide(
                                    ltorch.clamp(pos_mat, max=npm * ps - 1), ps))
        page_of = ltorch.where(in_bounds, page_of, 0)
        page_flat = ltorch.reshape(page_of, (B * K1,))
        slot_flat = ltorch.reshape(ltorch.remainder(pos_mat, ps), (B * K1,))
        x = gpt.wte(toks)
        new_kps, new_vps = [], []
        for li, block in enumerate(gpt.h):
            q, k, v = split_qkv_rope(block, cfg, block.norm_1(x), cos, sin)
            k_tok = ltorch.reshape(ltorch.permute(k, (0, 2, 1, 3)),
                                   (B * K1, cfg.n_query_groups, cfg.head_size))
            v_tok = ltorch.reshape(ltorch.permute(v, (0, 2, 1, 3)),
                                   (B * K1, cfg.n_query_groups, cfg.head_size))
            kp = _write_tokens(kps[li], page_flat, slot_flat, k_tok)
            vp = _write_tokens(vps[li], page_flat, slot_flat, v_tok)
            new_kps.append(kp)
            new_vps.append(vp)
            y = ltorch.paged_chunk_attention(q, kp, vp, page_table, pos_mat)
            y = ltorch.reshape(ltorch.permute(y, (0, 2, 1, 3)),
                               (B, K1, cfg.n_head * cfg.head_size))
            x = block_mix(block, cfg, x, block.attn.proj(y))
        logits = gpt.lm_head(gpt.ln_f(x))  # (B, K1, V)
        return logits, tuple(new_kps), tuple(new_vps)
