"""Live-range memory estimation and the unified budget API.

Two layers:

- **live-range estimator**: per-bsym liveness over a trace's tensor proxies
  -> a peak-HBM estimate (``peak_bytes``), per fusion region too
  (``region_peaks``). This is a static upper bound on what the compiled
  program needs resident at once (XLA may do better via rematerialization
  and buffer sharing; it cannot do worse than the sum of simultaneously
  live values plus what it chooses to duplicate).
- **budget API**: the one place VMEM/HBM fit decisions live. The ad-hoc
  estimate-and-decline checkers that grew inside ``executors/pallasex.py``
  (flash block capping, paged-attention working-set decline) now call
  through here, so every kernel/fusion budget question — "does this region
  fit VMEM?", "what is this step's peak HBM?" — has a single answer from a
  single set of budgets.

The two VMEM budgets are the chip's and are constants: ``vmem_limit()`` (16
MiB, the v4/v5 scoped-VMEM default a kernel gets when it asks for nothing;
the flash kernels were swept against it) and ``paged_vmem_limit()`` (14 MiB,
the paged kernels' claim budget). To A/B a kernel against its decomposition,
leave the Pallas executor out of ``tt.jit(fn, executors=[...])``. One
verifier setting is read from the environment: ``TT_CHECK_REGION_BUDGET``
(bytes; when set, the pass checkpoints flag any fusion region whose
live-range peak exceeds it).
"""
from __future__ import annotations

import math
import os
from typing import Optional

from ..core.prims import PrimIDs
from ..core.proxies import TensorProxy
from ..core.trace import TraceCtx

# ---------------------------------------------------------------------------
# budgets / knobs
# ---------------------------------------------------------------------------

DEFAULT_VMEM_LIMIT = 16 * 2**20
DEFAULT_PAGED_VMEM_LIMIT = 14 * 2**20


def vmem_limit() -> int:
    return DEFAULT_VMEM_LIMIT


def paged_vmem_limit() -> int:
    return DEFAULT_PAGED_VMEM_LIMIT


def within_vmem(nbytes: int, limit: Optional[int] = None) -> bool:
    """The fit decision: does an estimated working set fit the VMEM budget?"""
    return int(nbytes) <= (vmem_limit() if limit is None else int(limit))


def region_budget() -> Optional[int]:
    """Optional per-fusion-region HBM budget the pass checkpoints enforce
    (None = report only). Set via ``set_region_budget`` or
    ``TT_CHECK_REGION_BUDGET=<bytes>``."""
    if _REGION_BUDGET[0] is not None:
        return _REGION_BUDGET[0]
    v = os.environ.get("TT_CHECK_REGION_BUDGET")
    return int(v) if v else None


def set_region_budget(nbytes: Optional[int]) -> None:
    _REGION_BUDGET[0] = None if nbytes is None else int(nbytes)


_REGION_BUDGET: list = [None]


# ---------------------------------------------------------------------------
# kernel working-set estimates (moved from executors/pallasex.py)
# ---------------------------------------------------------------------------


PAGED_MAX_PAGES_PER_STEP = 8


def paged_head_block(n_kv_heads: int, g: int) -> int:
    """KV heads the paged decode kernel multiplies at once: the fewest that
    divide ``n_kv_heads`` and give the matmul 8 query rows (one f32 sublane
    tile), so a group of ``g`` < 8 rows does not leave the tile mostly empty."""
    for hb in range(1, n_kv_heads + 1):
        if n_kv_heads % hb == 0 and hb * g >= 8:
            return hb
    return n_kv_heads


def paged_decode_vmem_bytes(page_size: int, D: int, g: int, kv_itemsize: int, q_itemsize: int,
                            *, n_kv_heads: int, pages_per_step: int,
                            Dv: Optional[int] = None) -> int:
    """Estimated per-program VMEM working set of the paged-attention decode
    kernel (pallasex `_paged_attn_kernel`), which does all KV heads of one
    sequence: two buffers of ``pages_per_step`` whole pages (every KV head)
    of K and of V that its own DMAs fill, the q and output blocks Mosaic's
    pipeline double-buffers, q stacked by head block, the f32 accumulator with
    its m/l columns, and the f32 scores, probabilities and masks of one head
    block over one step's keys. ``Dv`` is the values' width where it is not
    the keys'."""
    Dv = D if Dv is None else Dv
    H = n_kv_heads * g
    hb = paged_head_block(n_kv_heads, g)
    kv = 2 * pages_per_step * n_kv_heads * page_size * (D + Dv) * kv_itemsize
    qo = 2 * H * (D + Dv) * q_itemsize
    scratch = H * D * q_itemsize + H * Dv * 4 + 2 * H * 4
    scores = 4 * (hb * g) * (pages_per_step * hb * page_size) * 4
    return kv + qo + scratch + scores


def paged_pages_per_step(page_size: int, D: int, g: int, kv_itemsize: int, q_itemsize: int,
                         *, n_kv_heads: int, Dv: Optional[int] = None) -> int:
    """Pages the paged decode kernel copies and multiplies a loop step: as
    many as fit the budget, ``PAGED_MAX_PAGES_PER_STEP`` at most (a longer
    block only adds to what a short sequence computes past its end); 0 when
    not even one page a step fits, and the checker then declines."""
    limit = paged_vmem_limit()
    for pps in range(PAGED_MAX_PAGES_PER_STEP, 0, -1):
        if within_vmem(paged_decode_vmem_bytes(page_size, D, g, kv_itemsize, q_itemsize, Dv=Dv,
                                               n_kv_heads=n_kv_heads, pages_per_step=pps), limit):
            return pps
    return 0


# query rows of one KV head (g x the tile) and keys of one loop step the chunk
# kernel multiplies at once. What a step costs beside its scores (the running
# maximum, the rescale of the accumulator: columns of f32, a vreg for 8 rows)
# goes with the rows, so the keys amortise it: 1,024 a step ran 1.25 times as
# fast as 512, 2,048 slower again (their f32 scores outgrow what they save),
# and 512 rows as fast as 1,024 (v5e, PR 32)
PAGED_CHUNK_MAX_ROWS = 512
PAGED_CHUNK_KEYS_PER_STEP = 1024


def paged_chunk_vmem_bytes(page_size: int, D: int, g: int, q_tile: int,
                           kv_itemsize: int, q_itemsize: int, *, heads: int,
                           pages_per_step: int, Dv: Optional[int] = None) -> int:
    """Estimated per-program VMEM working set of the multi-query paged-attention
    kernel (pallasex `_paged_chunk_kernel`), which does ``heads`` KV heads of
    one tile of ``q_tile`` queries: two buffers of ``pages_per_step`` pages
    (those heads) of K and of V that its own DMAs fill; the q, output and
    position blocks Mosaic's pipeline double-buffers, ``g * q_tile`` rows a
    head; the f32 accumulator with its m/l columns (a column of f32 takes a
    whole 128-lane tile a sublane group); and the f32 scores, probabilities
    and mask of one head over one step's keys. ``Dv`` is the values' width
    where it is not the keys'."""
    Dv = D if Dv is None else Dv
    rows = g * q_tile
    kv = 2 * pages_per_step * heads * page_size * (D + Dv) * kv_itemsize
    qo = 2 * heads * rows * (D + Dv) * q_itemsize + 2 * rows * 128 * 4
    scratch = heads * rows * (Dv + 2 * 128) * 4
    scores = 4 * rows * pages_per_step * page_size * 4
    return kv + qo + scratch + scores


def paged_chunk_blocks(page_size: int, D: int, g: int, T: int, kv_itemsize: int, q_itemsize: int,
                       *, n_kv_heads: int, Dv: Optional[int] = None) -> tuple:
    """(query tile, KV heads a program, pages a loop step) of the chunk kernel,
    from the shapes alone: the largest tile of the T queries that keeps a KV
    head's rows within ``PAGED_CHUNK_MAX_ROWS`` and fits (all of T where that
    is few, as in a verify step; else a divisor of T that is a multiple of 16,
    the bf16 sublane tile), then as many pages a step as fit beside it, those of
    ``PAGED_CHUNK_KEYS_PER_STEP`` keys at most, then as many of the KV heads as
    still fit (a page then crosses HBM in fewer, larger copies). (0, 0, 0)
    when not one page a step of one head fits, and the checker then declines."""
    tiles = [t for t in range(min(T, PAGED_CHUNK_MAX_ROWS // g) // 16 * 16, 0, -16) if T % t == 0]
    if g * T <= PAGED_CHUNK_MAX_ROWS or not tiles:
        tiles = [T] + [t for t in tiles if t < T]
    limit = paged_vmem_limit()

    def fits(q_tile, heads, pps):
        return within_vmem(paged_chunk_vmem_bytes(page_size, D, g, q_tile, kv_itemsize, q_itemsize,
                                                  heads=heads, pages_per_step=pps, Dv=Dv), limit)

    for q_tile in tiles:
        for pps in range(max(PAGED_CHUNK_KEYS_PER_STEP // page_size, 1), 0, -1):
            if fits(q_tile, 1, pps):
                heads = max(h for h in range(1, n_kv_heads + 1)
                            if n_kv_heads % h == 0 and fits(q_tile, h, pps))
                return q_tile, heads, pps
    return 0, 0, 0


def grouped_mlp_vmem_bytes(block_c: int, D: int, H: int,
                           w_itemsize: int, x_itemsize: int,
                           block_h: Optional[int] = None) -> int:
    """Estimated per-program VMEM working set of an expert-MLP kernel: one
    expert's three weight tiles over ``block_h`` hidden columns (the whole
    panels, ``block_h = H``, in pallasex `_grouped_mlp_kernel`, which has no
    tiles; a tile of them in `_ragged_mlp_kernel`), a (block_c, D) block of rows
    and the output block — two buffers each, as Mosaic's pipeline allocates
    them (counted once, the estimate let d 1024 x h 2816 bf16 through, which
    the compiler refuses: 34.00M against the 16.00M scoped limit) — the fused
    f32 SwiGLU intermediates (gate/up/hidden) over the tile and, where the
    hidden dimension is tiled, the f32 accumulator the tiles add up in."""
    bh = H if block_h is None else block_h
    w = 2 * 3 * D * bh * w_itemsize         # w_gate + w_up + w_down(T) tiles
    xb = 2 * block_c * D * x_itemsize       # input rows
    inter = block_c * (3 * bh) * 4          # g, u, h in f32
    out = 2 * block_c * D * x_itemsize      # output rows
    acc = block_c * D * 4 if bh < H else 0
    return w + xb + inter + out + acc


def rms_norm_block_rows(D: int, itemsize: int) -> int:
    """Rows a block of the fused RMSNorm kernel holds (pallasex `_rms_kernel`): the most of
    256, 128, ... 8 whose working set fits the scoped VMEM limit: the input and the output
    block in two buffers each and one float32 copy of the rows (what Mosaic allocates: 18.11M
    for 256 rows of 6,144 bfloat16, 12 bytes a number, against its 16M). 256 up to a width of
    4,096 in bfloat16, as it always was; 128 at 6,144."""
    return next((rows for rows in (256, 128, 64, 32, 16) if within_vmem(rows * D * (4 * itemsize + 4))), 8)


# scoped VMEM the ragged expert kernel asks Mosaic for: it streams weight tiles
# and is the faster the larger (the more contiguous) they are; three eighths of
# a v5e core's 128 MiB
RAGGED_MLP_VMEM_LIMIT = 48 * 2**20


def ragged_mlp_block_h(tile: int, D: int, H: int, w_itemsize: int, x_itemsize: int) -> int:
    """Hidden columns a weight tile of the ragged expert kernel holds: the
    most of 1024, 512, 256, 128 (or H itself) that divide H and whose working
    set fits ``RAGGED_MLP_VMEM_LIMIT``; 0 when none does, and the checker then
    declines."""
    for bh in sorted({b for b in (H, 1024, 512, 256, 128) if b <= min(H, 1024) and H % b == 0},
                     reverse=True):
        if within_vmem(grouped_mlp_vmem_bytes(tile, D, H, w_itemsize, x_itemsize, bh),
                       RAGGED_MLP_VMEM_LIMIT):
            return bh
    return 0


def latent_decode_vmem_bytes(page_size: int, row: int, v_width: int, heads: int, itemsize: int,
                             q_itemsize: int, pages_per_step: int) -> int:
    """Estimated per-program VMEM working set of the paged latent decode kernel
    (pallasex `_latent_attn_kernel`): two buffers of ``pages_per_step`` whole
    pages of rows, the q and output blocks twice, the f32 accumulator with its
    m/l columns, and the f32 scores, probabilities and mask of one step."""
    pages = 2 * pages_per_step * page_size * row * itemsize
    qo = 2 * heads * (row + v_width) * q_itemsize
    scratch = heads * v_width * 4 + 2 * heads * 4
    scores = 4 * heads * pages_per_step * page_size * 4
    return pages + qo + scratch + scores


def latent_pages_per_step(page_size: int, row: int, v_width: int, heads: int, itemsize: int,
                          q_itemsize: int) -> int:
    """Pages the latent decode kernel copies and multiplies a loop step: as
    ``paged_pages_per_step``, 0 when not one fits."""
    for pps in range(PAGED_MAX_PAGES_PER_STEP, 0, -1):
        if within_vmem(latent_decode_vmem_bytes(page_size, row, v_width, heads, itemsize,
                                                q_itemsize, pps), paged_vmem_limit()):
            return pps
    return 0


def ring_flash_vmem_bytes(block_q: int, T_blk: int, D: int,
                          q_itemsize: int, kv_itemsize: int) -> int:
    """Estimated per-program VMEM working set of one streaming ring-flash
    step (pallasex `_ring_flash_step_kernel`): the resident q block, this
    ring step's K/V shard (T_blk rows — the per-device block, not the
    global T), and the carried f32 (o, m, l) accumulator tiles. O(block)
    in the global sequence length by construction."""
    qb = block_q * D * q_itemsize
    kv = 2 * T_blk * D * kv_itemsize
    acc = block_q * D * 4 + 2 * block_q * 4  # o acc + m/l carries (f32)
    out = block_q * D * 4
    return qb + kv + acc + out


def _lane_padded(D: int) -> int:
    return -(-D // 128) * 128


def _rope_tables_vmem_bytes(block_q: int, Tk: int, Dp: int) -> int:
    """The f32 cos/sin of the rope-flash kernels: the q block's rows in two
    buffers and the whole-length tables in one (their block never changes).
    The kernels take their tables as wide as the head whatever the rotary
    width (`pallasex._rope_tables` widens a `(T, n_elem)` pair with cos 1 and
    sin 0), and a head narrower than the 128 lanes is padded to them: `Dp`
    is the head's padded width, so the estimate is the same at every width."""
    return (2 * 2 * block_q + 2 * Tk) * Dp * 4


def flash_fwd_vmem_bytes(block_q: int, block_k: int, Tk: int, D: int,
                         q_itemsize: int, kv_itemsize: int, *, rope: bool = False) -> int:
    """Estimated per-program VMEM working set of the flash forward (pallasex
    `_flash_fwd_kernel`, `_flash_rope_fwd_kernel`), which asks Mosaic for
    nothing and so gets `vmem_limit()`: whole-length K and V and the q, o and
    lse blocks, two buffers each; the f32 scores and probabilities of one
    (block_q, block_k) tile; with rope the cos/sin tables. Rows
    narrower than the 128 lanes are padded to them. Held against the v5e's
    compiler (PR 29; bf16 and f32, heads of 64 and 128, groups of 1 and 4, the
    longest multiple of 1,024 that compiles): at heads of 128 it says "fits" up
    to that length and not beyond (bf16 11,264 plain and 5,120 with rope; f32
    7,168 and 4,096); at heads of 64 the compiler takes more in some cases (up
    to 18,432 plain, 11,264-16,384 with rope), so there the estimate declines
    early and never late."""
    Dp = _lane_padded(D)
    kv = 2 * 2 * Tk * Dp * kv_itemsize
    qo = 2 * 2 * block_q * Dp * q_itemsize + 2 * block_q * 128 * 4
    scores = 2 * block_q * block_k * 4
    tables = _rope_tables_vmem_bytes(block_q, Tk, Dp) if rope else 0
    return kv + qo + scores + tables


def flash_bwd_vmem_bytes(block_q: int, block_k: int, Tk: int, D: int, g: int,
                         q_itemsize: int, kv_itemsize: int, *, rope: bool = False) -> int:
    """Estimated per-program VMEM working set of the single-pass flash backward
    (pallasex `_flash_bwd_fused_kernel`, `_flash_rope_bwd_fused_kernel`), to be
    held against the limit its call asks Mosaic for: whole-length K, V, dK and
    dV, two buffers each, and the two whole-length f32 accumulators; the q
    group's q, do and dq blocks with their lse and delta columns, two buffers
    each; four f32 (block_k, block_q) tiles; with rope the tables as in the
    forward. Held against the v5e's compiler as the forward's was: at a group
    of 1 it says "fits" up to the last length that compiles under 64 MiB and
    not beyond (bf16 19,456, with rope at heads of 128 14,336; f32 12,288); at
    a group of 4 the compiler takes up to 21,504 and the estimate declines
    early. The forward's limit is the tighter one unless the group is large."""
    Dp = _lane_padded(D)
    kv = (2 * 4 * kv_itemsize + 2 * 4) * Tk * Dp
    rows = g * block_q
    qdo = 2 * 3 * rows * Dp * q_itemsize + 2 * 2 * rows * 128 * 4
    tiles = 4 * block_q * block_k * 4
    tables = _rope_tables_vmem_bytes(block_q, Tk, Dp) if rope else 0
    return kv + qdo + tiles + tables


def flash_block_cap(widest_itemsize: int, block_q: int, block_k: int,
                    T: int, Tk: int) -> tuple[int, int]:
    """Flash-attention block sizes are swept for bf16; 4-byte operands
    double the VMEM working set and blow the scoped limit — cap both blocks
    at 256 there (gcd keeps divisibility). The decision half of pallasex's
    `_cap_blocks_for_dtype`."""
    if widest_itemsize >= 4:
        block_q = math.gcd(min(block_q, 256), T)
        block_k = math.gcd(min(block_k, 256), Tk)
    return block_q, block_k


# ---------------------------------------------------------------------------
# live-range analysis
# ---------------------------------------------------------------------------


def proxy_nbytes(p) -> int:
    if not isinstance(p, TensorProxy):
        return 0
    return p.numel * p.dtype.bytes


class PeakReport:
    """Result of a live-range sweep over one trace (or region)."""

    __slots__ = ("peak_bytes", "peak_index", "args_bytes", "output_bytes",
                 "n_proxies", "live_at_peak", "timeline")

    def __init__(self, peak_bytes, peak_index, args_bytes, output_bytes,
                 n_proxies, live_at_peak, timeline=None):
        self.peak_bytes = peak_bytes
        self.peak_index = peak_index
        self.args_bytes = args_bytes
        self.output_bytes = output_bytes
        self.n_proxies = n_proxies
        self.live_at_peak = live_at_peak
        # {bsym_index: live bytes while executing it}; filled when the
        # sweep is asked for it (with_timeline=True)
        self.timeline = timeline

    def as_dict(self) -> dict:
        return {"peak_bytes": self.peak_bytes, "peak_index": self.peak_index,
                "args_bytes": self.args_bytes, "output_bytes": self.output_bytes,
                "n_proxies": self.n_proxies,
                "live_at_peak": list(self.live_at_peak)}

    def __repr__(self) -> str:
        return (f"PeakReport(peak={self.peak_bytes / 2**20:.2f} MiB "
                f"at bsym {self.peak_index}, args={self.args_bytes / 2**20:.2f} MiB)")


# view-shaped ops whose outputs alias their first tensor arg's buffer: a
# view costs nothing but keeps the source buffer alive (the semantics of
# the seed estimator utils/memory.py, which now delegates here)
_VIEW_IDS = frozenset({PrimIDs.RESHAPE, PrimIDs.TRANSPOSE, PrimIDs.SQUEEZE,
                       PrimIDs.BROADCAST_IN_DIM})


def live_ranges(bsyms, args=()) -> dict[str, tuple[int, int, int]]:
    """buffer name -> (def_index, last_use_index, nbytes) over a bsym list.

    Args define at -1. DEL ends a range at the DEL's index; otherwise a
    range ends at the last consuming bsym (RETURN counts as a use — outputs
    stay live to the end). View outputs (reshape/transpose/squeeze/
    broadcast) are 0-byte aliases: their reads extend the SOURCE buffer's
    range instead of allocating, so a view-heavy trace is not over-priced.
    """
    ranges: dict[str, tuple[int, int, int]] = {}
    alias_of: dict[str, str] = {}  # view name -> buffer (root) name

    def root(n: str) -> str:
        return alias_of.get(n, n)

    for p in args:
        if isinstance(p, TensorProxy):
            ranges[p.name] = (-1, -1, proxy_nbytes(p))

    def touch(p, i):
        r = root(p.name)
        if r in ranges:
            d, _, nb = ranges[r]
            ranges[r] = (d, i, nb)
        else:  # consumed but never defined here (lenient: region views)
            ranges[r] = (-1, i, proxy_nbytes(p))

    for i, bsym in enumerate(bsyms):
        if bsym.sym.id == PrimIDs.DEL:
            for p in bsym.flat_proxy_args():
                # only a DEL of the buffer itself frees it; deleting a view
                # name must not free a root that later reads still alias
                if p.name in ranges and p.name not in alias_of:
                    d, _, nb = ranges[p.name]
                    ranges[p.name] = (d, i, nb)
            continue
        for p in bsym.flat_proxy_args():
            if isinstance(p, TensorProxy):
                touch(p, i)
        is_view = bsym.sym.id in _VIEW_IDS
        src = None
        if is_view:
            src = next((p for p in bsym.flat_proxy_args()
                        if isinstance(p, TensorProxy)), None)
        for o in bsym.flat_proxy_outs():
            if not isinstance(o, TensorProxy):
                continue
            if is_view and src is not None:
                alias_of[o.name] = root(src.name)
            elif root(o.name) not in ranges:
                ranges[o.name] = (i, i, proxy_nbytes(o))
    return ranges


def peak_bytes(trace_or_bsyms, args=None, *, count_args: bool = True,
               with_timeline: bool = False) -> PeakReport:
    """Sweep live ranges -> peak simultaneously-live bytes.

    Accepts a TraceCtx or a raw bsym list (+ explicit args). Intermediates
    live over [def, last_use (or DEL)]. Args live for the WHOLE trace
    unless explicitly DEL'd — XLA holds non-donated input buffers for the
    entire execution, so freeing them at last use would under-report.
    ``count_args=False`` prices only the intermediates (callers that
    account resident state separately, e.g. ``estimate_step_peak``).
    """
    if isinstance(trace_or_bsyms, TraceCtx):
        bsyms = trace_or_bsyms.bound_symbols
        args = trace_or_bsyms.args if args is None else args
    else:
        bsyms = list(trace_or_bsyms)
        args = args or ()
    ranges = live_ranges(bsyms, args)
    n = len(bsyms)
    deleted: set = set()
    for bsym in bsyms:
        if bsym.sym.id == PrimIDs.DEL:
            deleted.update(p.name for p in bsym.flat_proxy_args())

    def _end(name, d, last):
        if d == -1 and name not in deleted:
            return n - 1  # un-DEL'd args are held to the end
        return last if last >= 0 else n - 1

    delta = [0] * (n + 2)  # position p covers the state while executing bsym p
    args_bytes = 0
    for name, (d, last, nb) in ranges.items():
        if nb == 0:
            continue
        if d == -1:
            args_bytes += nb
            if not count_args:
                continue
        delta[max(d, 0)] += nb
        delta[_end(name, d, last) + 1] -= nb
    peak = 0
    peak_idx = 0
    cur = 0
    timeline: Optional[dict] = {} if with_timeline else None
    for i in range(n + 1):
        cur += delta[i]
        if timeline is not None and i < n:
            timeline[i] = cur
        if cur > peak:
            peak, peak_idx = cur, i
    live_at_peak = sorted(
        name for name, (d, last, nb) in ranges.items()
        if nb and (count_args or d >= 0)
        and max(d, 0) <= peak_idx <= _end(name, d, last))
    out_bytes = 0
    for bsym in reversed(bsyms):
        if bsym.sym.id == PrimIDs.RETURN:
            out_bytes = sum(proxy_nbytes(p) for p in bsym.flat_proxy_args()
                            if isinstance(p, TensorProxy))
            break
    return PeakReport(peak, min(peak_idx, max(n - 1, 0)), args_bytes, out_bytes,
                      len(ranges), live_at_peak[:16], timeline)


def region_peaks(trace: TraceCtx) -> list[dict]:
    """Live-range peak per executor fusion region of a claimed trace:
    [{"index", "region", "executor", "interface_bytes", "peak_bytes"}]."""
    out = []
    for i, bsym in enumerate(trace.bound_symbols):
        if not (bsym.subsymbols and bsym.sym.executor is not None):
            continue
        iface = sum(proxy_nbytes(p) for p in bsym.flat_proxy_args())
        iface += sum(proxy_nbytes(p) for p in bsym.flat_proxy_outs())
        rep = peak_bytes(list(bsym.subsymbols),
                         [p for p in bsym.flat_proxy_args() if isinstance(p, TensorProxy)])
        out.append({
            "index": i,
            "region": bsym.sym.name,
            "executor": getattr(bsym.sym.executor, "name", str(bsym.sym.executor)),
            "interface_bytes": iface,
            "peak_bytes": rep.peak_bytes,
        })
    return out


def estimate_step_peak(step) -> Optional[dict]:
    """Peak-HBM estimate of a built TrainStep: resident state (params,
    optimizer state, batch — priced once, from the live arrays) + the
    larger of the forward/backward INTERMEDIATE live-range peaks
    (``count_args=False``: the traces' args are those same param/batch
    buffers and must not be double-counted; saved-for-backward residuals
    are intermediates of the forward sweep that produces them).

    Returns None when the step has not been built yet (no traces).
    """
    cs = getattr(step, "compile_stats", None)
    if cs is None or not getattr(cs, "last_traces", None):
        return None
    import numpy as _np

    def _arr_bytes(tree) -> int:
        import jax

        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "nbytes"):
                total += int(leaf.nbytes)
            elif hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                total += int(_np.prod(leaf.shape or (1,))) * _np.dtype(leaf.dtype).itemsize
        return total

    tparams, frozen, _ = step._split_arrays()
    state_bytes = _arr_bytes(tparams) + _arr_bytes(frozen) + _arr_bytes(step.opt_state)
    batch_bytes = _arr_bytes(getattr(step, "last_batch", ()))
    fwd_peak = bwd_peak = 0
    fwd_trc = cs.last_traces[-1]
    fwd_peak = peak_bytes(fwd_trc, count_args=False).peak_bytes
    bwd_traces = getattr(cs, "last_backward_traces", None)
    if bwd_traces:
        bwd_peak = peak_bytes(bwd_traces[-1], count_args=False).peak_bytes
    total = state_bytes + batch_bytes + max(fwd_peak, bwd_peak)
    return {
        "state_bytes": state_bytes,
        "batch_bytes": batch_bytes,
        "fwd_peak_bytes": fwd_peak,
        "bwd_peak_bytes": bwd_peak,
        "peak_bytes": total,
        "peak_gb": round(total / 2**30, 4),
    }
