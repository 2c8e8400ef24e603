"""pallasex: hand-written Pallas(Mosaic) TPU kernels for the hot ops.

The TPU analog of the reference's kernel executors — sdpaex/cudnnex/fa3ex
flash attention (thunder/executors/sdpaex.py:1, cudnn_sdpa.py:1, fa3ex.py:1),
apex/triton fused cross-entropy (apex_entropyex_impl.py:1,
triton_crossentropy_impl.py:1) and fused RMSNorm
(apex_fused_rms_norm_impl.py:1). Kernels follow the Pallas TPU playbook:
(8,128)+ tiles, f32 accumulation in VMEM scratch, online softmax for flash
attention.

The executor claims the composite ltorch symbols whole (`sdpa`,
`cross_entropy`, `rms_norm`) via checkers; autodiff uses the executor-claimed
grad path (flash fwd saves (o, lse); flash bwd recomputes blockwise) — the
reference's executor-claimed-grads mechanism (thunder/transforms/autodiff.py:28-40)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..core import dtypes
from ..core.proxies import TensorProxy
from ..core.symbol import OpTags, Symbol
from ..extend import OperatorExecutor, register_executor
from ..observability import events as _obs

ex = OperatorExecutor("pallas")
register_executor(ex)

# swept on v5e (llama-350m, B=4, T=2048, D=64, fwd+bwd step): 512/1024 gave
# 39.4% MFU vs 23.8% at 128/128 — large q blocks amortize the k/v loop,
# k-major blocks keep the MXU fed during the online-softmax accumulation
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
# single-pass fused backward blocks (swept on v5e across llama-350m/llama-1b/
# nanogpt shapes: 512/512 wins everywhere — 4.11/2.75/2.80 ms fwd+bwd vs
# 4.52/3.24/3.42 for a dq pass and a dkv pass; 1024-row q blocks blow the
# 16 MB VMEM limit)
_FUSED_BLOCK_Q = 512
_FUSED_BLOCK_K = 512
# scoped VMEM the single-pass backward asks Mosaic for: it keeps whole-length
# K/V blocks and two (Tk, D) f32 accumulators resident, which at a q group of
# 4 (llama-350m width, T=2048) is 16.23 MiB — over the compiler's 16 MiB
# default. Half of a v5e core's 128 MiB.
_FUSED_BWD_VMEM_LIMIT = 64 * 2**20


def _cap_blocks_for_dtype(q, block_q: int, block_k: int, T: int, Tk: int, *extra):
    """Block sizes are swept for bf16; 4-byte operands (f32 paths: a
    no-autocast train step, or mixed-precision rewrites that leave SOME of
    q/k/v/do f32) double the VMEM working set and blow the 16M scoped limit —
    cap both blocks at 256 there (gcd keeps divisibility). The decision
    lives in the unified budget API (analysis/memory.py flash_block_cap)."""
    from ..analysis import budget as _budget

    widest = max(jnp.dtype(t.dtype).itemsize for t in (q,) + tuple(extra))
    return _budget.flash_block_cap(widest, block_q, block_k, T, Tk)


NEG_INF = -1e30
LOG2E = 1.4426950408889634  # 1/ln 2: base-2 softmax folds this into the scale
LN2 = 0.6931471805599453


def _decline(kernel: str, reason: str) -> bool:
    """A checker's "no" to a shape it would otherwise claim on this device,
    counted (``pallas.decline.<kernel>.<reason>``): the op then runs its
    pure-jax decomposition, and the bus says so."""
    _obs.inc(f"pallas.decline.{kernel}.{reason}")
    return False


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _claims_on_platform() -> bool:
    """Who claims: the one rule of every checker whose kernel pays only on the
    chip (paged, grouped MLP, ring flash, int8 and fp8 linear) — the platform.
    Off the chip their ops run the pure-jax decomposition; the tests patch
    this to run the kernels interpreted (tests/conftest.py: pallas_claims).
    To A/B a kernel against its decomposition on the chip, leave `pallasex.ex`
    out of `tt.jit(fn, executors=[...])`."""
    return _on_tpu()


def _interpret() -> bool:
    return not _on_tpu()


# ===========================================================================
# Flash attention — forward
# ===========================================================================


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int, causal: bool,
                      scale: float, q_offset_blocks: int):
    # q_ref: (block_q, D); k_ref/v_ref: (T, D); o_ref: (block_q, D); lse_ref: (block_q, 1)
    block_q, D = q_ref.shape
    T = k_ref.shape[0]
    qi = pl.program_id(2)

    # inputs stay low-precision so the dots ride the MXU's native bf16 path
    # (fp32 operands run the MXU at a fraction of peak); accumulation is
    # always f32 via preferred_element_type, scores/softmax stay f32
    q = q_ref[:]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    # base-2 softmax: fold log2(e) into the dot scale so the per-element
    # softmax uses the VPU's native exp2 with no premultiply pass — the
    # running max/sum track log2 units; lse converts back to natural log once
    scale2 = scale * LOG2E

    def body(j, carry):
        o_acc, m, l = carry
        k_blk = k_ref[pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale2  # (bq, bk)
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp2(s - m_new[:, None])
        corr = jnp.exp2(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        o_new = o_acc * corr[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    n_k = T // block_k
    if causal:
        # skip fully-masked k blocks: only blocks intersecting the causal
        # triangle ([0, (qi+1)*block_q)) contribute
        n_k = jnp.minimum(n_k, ((qi + 1) * block_q + block_k - 1) // block_k)
    o0 = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, n_k, body, (o0, m0, l0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[:] = (o / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[:] = ((m + jnp.log2(l_safe)) * LN2)[:, None]


def flash_attention_forward(q, k, v, *, causal: bool = True, scale=None,
                            block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K):
    """q,k,v: (B, H, T, D) -> (o, lse). Head dims below the 128-lane tile
    (64 for llama-class models) are handled by Mosaic's implicit minor-dim
    padding in VMEM — no HBM-level zero-pad copies or doubled k/v traffic."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    B, H, T, D = q.shape
    Tk = k.shape[2]
    Hkv = k.shape[1]
    g = H // Hkv  # GQA group: kv head = q head // g (1 for MHA)
    block_q = min(block_q, T)
    block_k = min(block_k, Tk)
    block_q, block_k = _cap_blocks_for_dtype(q, block_q, block_k, T, Tk, k, v)
    grid = (B, H, T // block_q)

    o, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, block_k=block_k, causal=causal, scale=scale,
                          q_offset_blocks=0),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, Tk, D), lambda b, h, i: (b, h // g, 0, 0)),
            pl.BlockSpec((None, None, Tk, D), lambda b, h, i: (b, h // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)
    return o, lse[..., 0]


# ===========================================================================
# Flash attention — backward (recompute blockwise, one pass: dq, dk and dv)
# ===========================================================================


def _fused_bwd_tile(q, do, lse2, delta, k_blk, v_blk, sl, k_pos_t, q_pos_t,
                    causal, scale, dk_scr, dv_scr, dq_acc):
    """One (i, j) tile of the single-pass backward, shared by the plain and
    rope fused kernels: computes s/p ONCE, accumulates dk/dv into the VMEM
    scratch slice and returns the updated dq accumulator. Transposed orientation (rows = k positions)."""
    s_t = jax.lax.dot_general(k_blk, q, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32) * (scale * LOG2E)
    if causal:
        s_t = jnp.where(k_pos_t <= q_pos_t, s_t, NEG_INF)
    p_t = jnp.exp2(s_t - lse2[None, :])
    dv_c = jax.lax.dot_general(p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dp_t = jax.lax.dot_general(v_blk, do, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    ds_t = (p_t * (dp_t - delta[None, :]) * scale).astype(q.dtype)
    dk_c = jax.lax.dot_general(ds_t, q, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dk_scr[sl, :] += dk_c
    dv_scr[sl, :] += dv_c
    return dq_acc + jax.lax.dot_general(ds_t, k_blk, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                            block_k: int, causal: bool, scale: float,
                            g: int, n_i: int):
    """Single-pass backward: grid (B, Hkv, T//block_q)
    with k/v full-T resident; each program computes s/p ONCE per (i, j) tile
    and emits BOTH its dq tile (written per program) and the dk/dv
    contributions (f32 VMEM scratch accumulated across the i axis, written at
    the last i) — vs the two-pass design this halves the backward exp and
    QK^T work (5 dots + 1 exp per tile instead of 7 + 2)."""
    Tk, D = k_ref.shape
    block_q = q_ref.shape[1]
    ii = pl.program_id(2)

    @pl.when(ii == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    n_j = Tk // block_k
    if causal:
        n_j = jnp.minimum(n_j, ((ii + 1) * block_q + block_k - 1) // block_k)

    for h in range(g):  # static unroll over the q-head group (1 for MHA)
        q = q_ref[h]
        do = do_ref[h]
        lse2 = lse_ref[h][:, 0] * LOG2E
        delta = delta_ref[h][:, 0]
        q_pos_t = ii * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)

        def body(j, dq_acc):
            sl = pl.ds(j * block_k, block_k)
            k_pos_t = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
            return _fused_bwd_tile(q, do, lse2, delta, k_ref[sl, :], v_ref[sl, :],
                                   sl, k_pos_t, q_pos_t, causal, scale,
                                   dk_scr, dv_scr, dq_acc)

        dq = jax.lax.fori_loop(0, n_j, body, jnp.zeros((block_q, D), jnp.float32))
        dq_ref[h] = dq.astype(dq_ref.dtype)

    @pl.when(ii == n_i - 1)
    def _write():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _fused_bwd_blocks(block_q: int, block_k: int, T: int, Tk: int):
    """The single-pass backward's blocks, for its two calls and their checker
    (gcd keeps divisibility: a non-divisor block would truncate the grid)."""
    return math.gcd(min(block_q, _FUSED_BLOCK_Q), T), math.gcd(min(block_k, _FUSED_BLOCK_K), Tk)


def flash_attention_backward(q, k, v, o, lse, do, *, causal: bool = True, scale=None,
                             block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K):
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if jnp.dtype(do.dtype).itemsize > jnp.dtype(q.dtype).itemsize:
        # fp8/mixed rewrites can hand a f32 cotangent to a bf16 attention:
        # matching q's precision keeps the swept bf16 block sizes (delta is
        # accumulated in f32 regardless)
        do = do.astype(q.dtype)
    B, H, T, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    g = H // Hkv  # GQA: one program does a kv head's whole q group
    block_q, block_k = _cap_blocks_for_dtype(q, min(block_q, T), min(block_k, Tk), T, Tk, k, v, do)
    block_q, block_k = _fused_bwd_blocks(block_q, block_k, T, Tk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # (B,H,T)
    lse4 = lse[..., None]
    delta4 = delta[..., None]
    qg = q.reshape(B, Hkv, g, T, D)
    dog = do.reshape(B, Hkv, g, T, D)
    lseg = lse4.reshape(B, Hkv, g, T, 1)
    deltag = delta4.reshape(B, Hkv, g, T, 1)
    n_i = T // block_q
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel, block_k=block_k,
                          causal=causal, scale=scale, g=g, n_i=n_i),
        grid=(B, Hkv, n_i),
        in_specs=[
            pl.BlockSpec((None, None, g, block_q, D), lambda b, hk, i: (b, hk, 0, i, 0)),
            pl.BlockSpec((None, None, Tk, D), lambda b, hk, i: (b, hk, 0, 0)),
            pl.BlockSpec((None, None, Tk, D), lambda b, hk, i: (b, hk, 0, 0)),
            pl.BlockSpec((None, None, g, block_q, D), lambda b, hk, i: (b, hk, 0, i, 0)),
            pl.BlockSpec((None, None, g, block_q, 1), lambda b, hk, i: (b, hk, 0, i, 0)),
            pl.BlockSpec((None, None, g, block_q, 1), lambda b, hk, i: (b, hk, 0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, g, block_q, D), lambda b, hk, i: (b, hk, 0, i, 0)),
            pl.BlockSpec((None, None, Tk, D), lambda b, hk, i: (b, hk, 0, 0)),
            pl.BlockSpec((None, None, Tk, D), lambda b, hk, i: (b, hk, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, g, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Tk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((Tk, D), jnp.float32),
                        pltpu.VMEM((Tk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_FUSED_BWD_VMEM_LIMIT),
        interpret=_interpret(),
    )(qg, k, v, dog, lseg, deltag)
    return dq.reshape(B, H, T, D), dk, dv


# ===========================================================================
# Fused RoPE + flash attention (rope applied in-kernel; pre-rope q/k are the
# saved-for-backward residuals and the rope VJP rotation happens in-kernel
# on the dq/dk accumulators — the separate rope slice/negate/cat fusions and
# their backward passes disappear from the XLA timeline)
# ===========================================================================


def _rot_matrix(D: int, n_elem: int, dtype):
    """rotate_half over the first `n_elem` of D columns as a constant matmul:
    rotate(x) = x @ R with R[i, j] = -1 at i == j + n_elem/2 (j in the first
    half), +1 at i == j - n_elem/2 (j in the second), zero on every row and
    column past `n_elem`. Lane-slicing halves of a bf16 tile in-kernel lowers
    to catastrophic VREG shuffles on Mosaic; one (N, D) @ (D, D) dot is
    MXU-trivial instead, and is the same dot whatever the rotary width."""
    h = n_elem // 2
    ii = jax.lax.broadcasted_iota(jnp.int32, (D, D), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (D, D), 1)
    part = n_elem < D  # at the full width the two diagonals end where the matrix does
    lower = (ii == jj + h) & (jj < h) if part else ii == jj + h
    r = jnp.where(lower, -1.0, 0.0)
    upper = (ii + h == jj) & (jj < n_elem) if part else ii + h == jj
    return (r + jnp.where(upper, 1.0, 0.0)).astype(dtype)


def _rope_block(x, c, s, n_elem: int):
    """x (N, D) f32 -> rope'd (N, D); cos/sin (N, D) duplicated-half caches
    over the first `n_elem` columns, cos 1 and sin 0 on the columns that pass."""
    rot = jax.lax.dot_general(x, _rot_matrix(x.shape[-1], n_elem, x.dtype),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return x * c + rot * s


def _rope_vjp_block(dxr, c, s, n_elem: int):
    """VJP of _rope_block wrt x: dx = dxr*c + (dxr*s) @ R^T (dx = dxr on the
    columns that pass: c is 1 and s is 0 there)."""
    ds = dxr * s
    rot = jax.lax.dot_general(ds, _rot_matrix(dxr.shape[-1], n_elem, ds.dtype),
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return dxr * c + rot


def _rope_tables(cos, sin, D: int):
    """The kernels' tables, f32 and D wide: a rotary width `n_elem` < D reads
    cos 1 and sin 0 on the columns past it (widened here, once a call: a few
    hundred KB that XLA makes once a program, where a lane concatenate inside
    the kernel would be made once a tile)."""
    cos, sin = cos.astype(jnp.float32), sin.astype(jnp.float32)
    n_elem = cos.shape[-1]
    if n_elem < D:
        cos = jnp.pad(cos, ((0, 0), (0, D - n_elem)), constant_values=1.0)
        sin = jnp.pad(sin, ((0, 0), (0, D - n_elem)))
    return cos, sin, n_elem


def _flash_rope_fwd_kernel(q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref,
                           o_ref, lse_ref, *, block_k: int, causal: bool, scale: float,
                           n_elem: int):
    block_q, D = q_ref.shape
    T = k_ref.shape[0]
    qi = pl.program_id(2)

    q = _rope_block(q_ref[:].astype(jnp.float32), cq_ref[:], sq_ref[:], n_elem).astype(q_ref.dtype)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, carry):
        o_acc, m, l = carry
        k_blk = _rope_block(k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32),
                            ck_ref[pl.ds(j * block_k, block_k), :],
                            sk_ref[pl.ds(j * block_k, block_k), :], n_elem).astype(k_ref.dtype)
        v_blk = v_ref[pl.ds(j * block_k, block_k), :]
        ss = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * (scale * LOG2E)
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            ss = jnp.where(k_pos <= q_pos, ss, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(ss, axis=1))
        pp = jnp.exp2(ss - m_new[:, None])
        corr = jnp.exp2(m - m_new)
        l_new = l * corr + jnp.sum(pp, axis=1)
        o_new = o_acc * corr[:, None] + jax.lax.dot_general(
            pp.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    n_k = T // block_k
    if causal:
        n_k = jnp.minimum(n_k, ((qi + 1) * block_q + block_k - 1) // block_k)
    o0 = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, n_k, body, (o0, m0, l0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[:] = (o / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[:] = ((m + jnp.log2(l_safe)) * LN2)[:, None]


def flash_rope_attention_forward(q, k, v, cos, sin, *, causal: bool = True, scale=None,
                                 block_q: int = DEFAULT_BLOCK_Q,
                                 block_k: int = DEFAULT_BLOCK_K):
    """q,k,v PRE-rope (B, H, T, D); cos/sin (T, n_elem) duplicated-half caches
    of an even rotary width n_elem <= D: the first n_elem columns of every
    head are rotated, the rest pass."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    g = H // Hkv  # GQA group (1 for MHA)
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    block_q, block_k = _cap_blocks_for_dtype(q, block_q, block_k, T, T, k, v)
    cos, sin, n_elem = _rope_tables(cos, sin, D)
    o, lse = pl.pallas_call(
        functools.partial(_flash_rope_fwd_kernel, block_k=block_k, causal=causal, scale=scale,
                          n_elem=n_elem),
        grid=(B, H, T // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, h, i: (b, h // g, 0, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, h, i: (b, h // g, 0, 0)),
            pl.BlockSpec((block_q, D), lambda b, h, i: (i, 0)),
            pl.BlockSpec((block_q, D), lambda b, h, i: (i, 0)),
            pl.BlockSpec((T, D), lambda b, h, i: (0, 0)),
            pl.BlockSpec((T, D), lambda b, h, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v, cos, sin, cos, sin)
    return o, lse[..., 0]


def _flash_rope_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                                 cq_ref, sq_ref, ck_ref, sk_ref,
                                 dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                                 block_k: int, causal: bool, scale: float,
                                 g: int, n_i: int, n_elem: int):
    """Single-pass rope backward (see _flash_bwd_fused_kernel): rope applied
    in-kernel on q/k loads, rope VJP on the dq carry at write and on the dk
    scratch at the final i."""
    Tk, D = k_ref.shape
    block_q = q_ref.shape[1]
    ii = pl.program_id(2)

    @pl.when(ii == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    n_j = Tk // block_k
    if causal:
        n_j = jnp.minimum(n_j, ((ii + 1) * block_q + block_k - 1) // block_k)

    for h in range(g):  # static unroll over the q-head group (1 for MHA)
        q = _rope_block(q_ref[h].astype(jnp.float32), cq_ref[:], sq_ref[:],
                        n_elem).astype(q_ref.dtype)
        do = do_ref[h]
        lse2 = lse_ref[h][:, 0] * LOG2E
        delta = delta_ref[h][:, 0]
        q_pos_t = ii * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)

        def body(j, dq_acc):
            sl = pl.ds(j * block_k, block_k)
            k_blk = _rope_block(k_ref[sl, :].astype(jnp.float32),
                                ck_ref[sl, :], sk_ref[sl, :], n_elem).astype(k_ref.dtype)
            k_pos_t = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
            return _fused_bwd_tile(q, do, lse2, delta, k_blk, v_ref[sl, :],
                                   sl, k_pos_t, q_pos_t, causal, scale,
                                   dk_scr, dv_scr, dq_acc)

        dq = jax.lax.fori_loop(0, n_j, body, jnp.zeros((block_q, D), jnp.float32))
        dq_ref[h] = _rope_vjp_block(dq, cq_ref[:], sq_ref[:], n_elem).astype(dq_ref.dtype)

    @pl.when(ii == n_i - 1)
    def _write():
        dk_ref[:] = _rope_vjp_block(dk_scr[:], ck_ref[:], sk_ref[:],
                                    n_elem).astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def flash_rope_attention_backward(q, k, v, o, lse, cos, sin, do, *, causal: bool = True,
                                  scale=None, block_q: int = DEFAULT_BLOCK_Q,
                                  block_k: int = DEFAULT_BLOCK_K):
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if jnp.dtype(do.dtype).itemsize > jnp.dtype(q.dtype).itemsize:
        do = do.astype(q.dtype)  # see flash_attention_backward
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    block_q, block_k = _cap_blocks_for_dtype(q, min(block_q, T), min(block_k, T), T, T, k, v, do)
    block_q, block_k = _fused_bwd_blocks(block_q, block_k, T, T)
    cos, sin, n_elem = _rope_tables(cos, sin, D)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse4 = lse[..., None]
    delta4 = delta[..., None]
    qg = q.reshape(B, Hkv, g, T, D)
    dog = do.reshape(B, Hkv, g, T, D)
    lseg = lse4.reshape(B, Hkv, g, T, 1)
    deltag = delta4.reshape(B, Hkv, g, T, 1)
    n_i = T // block_q
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_rope_bwd_fused_kernel, block_k=block_k,
                          causal=causal, scale=scale, g=g, n_i=n_i, n_elem=n_elem),
        grid=(B, Hkv, n_i),
        in_specs=[
            pl.BlockSpec((None, None, g, block_q, D), lambda b, hk, i: (b, hk, 0, i, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, hk, i: (b, hk, 0, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, hk, i: (b, hk, 0, 0)),
            pl.BlockSpec((None, None, g, block_q, D), lambda b, hk, i: (b, hk, 0, i, 0)),
            pl.BlockSpec((None, None, g, block_q, 1), lambda b, hk, i: (b, hk, 0, i, 0)),
            pl.BlockSpec((None, None, g, block_q, 1), lambda b, hk, i: (b, hk, 0, i, 0)),
            pl.BlockSpec((block_q, D), lambda b, hk, i: (i, 0)),
            pl.BlockSpec((block_q, D), lambda b, hk, i: (i, 0)),
            pl.BlockSpec((T, D), lambda b, hk, i: (0, 0)),
            pl.BlockSpec((T, D), lambda b, hk, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, g, block_q, D), lambda b, hk, i: (b, hk, 0, i, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, hk, i: (b, hk, 0, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, hk, i: (b, hk, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, g, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, T, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hkv, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((T, D), jnp.float32),
                        pltpu.VMEM((T, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_FUSED_BWD_VMEM_LIMIT),
        interpret=_interpret(),
    )(qg, k, v, dog, lseg, deltag, cos, sin, cos, sin)
    return dq.reshape(B, H, T, D), dk, dv


def rope_sdpa_supported(q, k, v, cos, sin, is_causal=True, scale=None) -> bool:
    """Claim fused rope+attention when the plain flash checker would claim
    the sdpa AND the tables are (T, n_elem) for an even rotary width
    n_elem <= head dim (read off the tables, nothing else says it): the
    kernels rotate the first n_elem columns of every head and pass the rest.
    A width they do not take is declined by name (`pallas.decline.rope_sdpa.
    width`, or `.tables` where cos and sin are not two (T, n) tables)."""
    if getattr(q, "ndim", 0) != 4:
        return False
    D = q.shape[-1]
    T = q.shape[-2]
    if not flash_attention_supported(q, k, v, None, 0.0, is_causal, scale):
        return False
    shape = tuple(getattr(cos, "shape", ()))
    if shape != tuple(getattr(sin, "shape", ())) or len(shape) != 2 or shape[0] != T:
        return _decline("rope_sdpa", "tables")
    n_elem = shape[1]
    if n_elem <= 0 or n_elem % 2 or n_elem > D:
        return _decline("rope_sdpa", "width")
    return _flash_fits_vmem("rope_sdpa", q, k, rope=True)


def _rope_sdpa_impl(q, k, v, cos, sin, is_causal=True, scale=None):
    o, _ = flash_rope_attention_forward(q, k, v, cos, sin, causal=is_causal, scale=scale)
    return o


def _jit_claimed(impl, static_argnames, normalize):
    """Shared jit wrapper for claimed ops dispatched standalone (outside a
    fusion region they would otherwise re-lower the pallas_call on every
    invocation). `normalize` maps the call args to hashable statics; any
    tracer-in-static slips through to the unjitted impl."""
    jitted = jax.jit(impl, static_argnames=static_argnames)

    def claimed(*args, **kwargs):
        try:
            a, kw = normalize(*args, **kwargs)
            return jitted(*a, **kw)
        except (TypeError, jax.errors.TracerArrayConversionError,
                jax.errors.ConcretizationTypeError):
            return impl(*args, **kwargs)

    return claimed


_rope_sdpa_claimed = _jit_claimed(
    _rope_sdpa_impl, ("is_causal", "scale"),
    lambda q, k, v, cos, sin, is_causal=True, scale=None: (
        (q, k, v, cos, sin),
        {"is_causal": bool(is_causal), "scale": None if scale is None else float(scale)}))


def _register_rope_sdpa():
    from ..ops.ltorch import rope_sdpa as _rope_sdpa_sym

    ex.register_implementation(_rope_sdpa_sym.id, _rope_sdpa_claimed,
                               checker=rope_sdpa_supported)

    fwd_sym = ex.register_operator(
        "rope_flash_fwd",
        meta=lambda q, k, v, cos, sin, causal, scale: (
            TensorProxy(shape=q.shape, dtype=q.dtype, device=q.device),
            TensorProxy(shape=q.shape[:-1], dtype=dtypes.float32, device=q.device),
        ),
        fn=lambda q, k, v, cos, sin, causal, scale: flash_rope_attention_forward(
            q, k, v, cos, sin, causal=causal, scale=scale),
    )
    bwd_sym = ex.register_operator(
        "rope_flash_bwd",
        meta=lambda q, k, v, o, lse, cos, sin, causal, scale, do: (
            TensorProxy(shape=q.shape, dtype=q.dtype, device=q.device),
            TensorProxy(shape=k.shape, dtype=k.dtype, device=k.device),
            TensorProxy(shape=v.shape, dtype=v.dtype, device=v.device),
        ),
        fn=lambda q, k, v, o, lse, cos, sin, causal, scale, do: flash_rope_attention_backward(
            q, k, v, o, lse, cos, sin, do, causal=causal, scale=scale),
    )

    from ..transforms.autodiff import VJPResult, register_augmented_forward, register_backward

    @register_augmented_forward(_rope_sdpa_sym.id)
    def _rope_sdpa_aug(q, k, v, cos, sin, is_causal=True, scale=None):
        if not rope_sdpa_supported(q, k, v, cos, sin, is_causal, scale):
            return NotImplemented  # decompose: composite rope + sdpa rules apply
        o, lse = fwd_sym(q, k, v, cos, sin, bool(is_causal), scale)
        return VJPResult(o, (q, k, v, o, lse, cos, sin, bool(is_causal), scale))

    @register_backward(_rope_sdpa_sym.id)
    def _rope_sdpa_bwd(q, k, v, o, lse, cos, sin, causal, scale, g):
        dq, dk, dv = bwd_sym(q, k, v, o, lse, cos, sin, causal, scale, g)
        return dq, dk, dv, None, None, None, None


def flash_attention_supported(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False, scale=None, enable_gqa=False) -> bool:
    """Checker: pallas flash attention claims sdpa when shapes fit the tiling."""
    if attn_mask is not None or (dropout_p and dropout_p > 0.0):
        return False
    if getattr(q, "ndim", 0) != 4 or getattr(k, "ndim", 0) != 4 or getattr(v, "ndim", 0) != 4:
        return False
    # Claim whenever the tiling fits and the sequence is long enough to
    # amortize the kernel launch: with bf16 MXU dots and swept block sizes
    # the pallas kernels beat XLA's composite attention from T=1024 up
    # (measured v5e: nanogpt-124m B=8 T=1024 +20% step throughput; the
    # composite additionally OOMs at llama-350m B=4 T=2048 fwd+bwd).
    shapes_ok = (
        q.shape[-1] <= 512  # any head dim (Mosaic pads the minor dim in VMEM)
        and q.shape[-2] >= 1024
        and q.shape[-2] % DEFAULT_BLOCK_Q == 0
        and k.shape[-2] % DEFAULT_BLOCK_K == 0
        and q.shape[-2] == k.shape[-2]
        # GQA/MQA: the k/v BlockSpecs index kv head = q head // group, and
        # the backward does a kv head's whole q group in one program
        and q.shape[0] == k.shape[0] == v.shape[0]
        and k.shape[1] == v.shape[1]
        and q.shape[1] % k.shape[1] == 0
        and q.shape[-1] == k.shape[-1] == v.shape[-1]
        and k.shape[-2] == v.shape[-2]
    )
    return bool(shapes_ok) and _flash_fits_vmem("flash_attention", q, k, rope=False)


def _flash_fits_vmem(kernel: str, q, k, *, rope: bool) -> bool:
    """Both kernels keep whole-length K and V in VMEM: the forward must fit the
    compiler's default limit and the backward the one its call asks for, or
    the checker declines and XLA's composite runs (a sequence too long for
    them would otherwise be claimed and then refused by the compiler)."""
    from ..analysis import budget as _budget

    T, D, Tk, g = q.shape[-2], q.shape[-1], k.shape[-2], q.shape[1] // k.shape[1]
    q_item = jnp.dtype(str(q.dtype).rpartition(".")[2]).itemsize
    kv_item = jnp.dtype(str(k.dtype).rpartition(".")[2]).itemsize
    block_q, block_k = _budget.flash_block_cap(
        max(q_item, kv_item), min(DEFAULT_BLOCK_Q, T), min(DEFAULT_BLOCK_K, Tk), T, Tk)
    fwd = _budget.flash_fwd_vmem_bytes(block_q, block_k, Tk, D, q_item, kv_item, rope=rope)
    bwd = _budget.flash_bwd_vmem_bytes(*_fused_bwd_blocks(block_q, block_k, T, Tk),
                                       Tk, D, g, q_item, kv_item, rope=rope)
    if _budget.within_vmem(fwd) and _budget.within_vmem(bwd, _FUSED_BWD_VMEM_LIMIT):
        return True
    return _decline(kernel, "vmem")


# symbol registration: claims ltorch.sdpa whole ------------------------------


def _sdpa_flash_impl(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False, scale=None, enable_gqa=False):
    o, _ = flash_attention_forward(q, k, v, causal=is_causal, scale=scale)
    return o


_sdpa_claimed = _jit_claimed(
    _sdpa_flash_impl, ("dropout_p", "is_causal", "scale"),
    lambda q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False, scale=None,
    enable_gqa=False: (
        (q, k, v, attn_mask, float(dropout_p), bool(is_causal),
         None if scale is None else float(scale)), {}))


ex.register_implementation(
    "torch.nn.functional.scaled_dot_product_attention",
    _sdpa_claimed,
    checker=flash_attention_supported,
)


def _register_sdpa_grad_rule():
    """Executor-claimed grad: flash fwd saves (o, lse, q, k, v); flash bwd
    recomputes probabilities blockwise. Falls through to the composite
    decomposition when the kernel can't claim the shapes."""
    from ..transforms.autodiff import VJPResult, register_augmented_forward, register_backward

    def fwd_meta(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False, scale=None, enable_gqa=False):
        o = TensorProxy(shape=q.shape, dtype=q.dtype, device=q.device)
        lse = TensorProxy(shape=q.shape[:-1], dtype=dtypes.float32, device=q.device)
        return o, lse

    def fwd_impl(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False, scale=None, enable_gqa=False):
        return flash_attention_forward(q, k, v, causal=is_causal, scale=scale)

    flash_fwd_sym = Symbol("flash_attention_fwd", fwd_meta, id="pallas.flash_attention_fwd",
                           is_prim=True, module="pallas", executor=ex)
    ex.opmap[flash_fwd_sym.id] = fwd_impl

    def bwd_meta(q, k, v, o, lse, causal, scale, do):
        return (TensorProxy(shape=q.shape, dtype=q.dtype, device=q.device),
                TensorProxy(shape=k.shape, dtype=k.dtype, device=k.device),
                TensorProxy(shape=v.shape, dtype=v.dtype, device=v.device))

    def bwd_impl(q, k, v, o, lse, causal, scale, do):
        return flash_attention_backward(q, k, v, o, lse, do, causal=causal, scale=scale)

    flash_bwd_sym = Symbol("flash_attention_bwd", bwd_meta, id="pallas.flash_attention_bwd",
                           is_prim=True, module="pallas", executor=ex)
    ex.opmap[flash_bwd_sym.id] = bwd_impl

    @register_augmented_forward("torch.nn.functional.scaled_dot_product_attention")
    def _sdpa_aug(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False, scale=None, enable_gqa=False):
        if not flash_attention_supported(q, k, v, attn_mask, dropout_p, is_causal, scale):
            return NotImplemented
        o, lse = flash_fwd_sym(q, k, v, attn_mask, dropout_p, is_causal, scale)
        return VJPResult(o, (q, k, v, o, lse, bool(is_causal), scale))

    @register_backward("torch.nn.functional.scaled_dot_product_attention")
    def _sdpa_bwd(q, k, v, o, lse, causal, scale, do):
        return flash_bwd_sym(q, k, v, o, lse, causal, scale, do)


_register_sdpa_grad_rule()


# ===========================================================================
# Fused cross-entropy (mean reduction over valid targets)
# ===========================================================================


def _xent_kernel(logits_ref, tgt_ref, loss_ref, lse_ref):
    # logits (block_n, V), tgt (block_n, 1) int32
    logits = logits_ref[:].astype(jnp.float32)
    n, V = logits.shape
    m = jnp.max(logits, axis=1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=1))
    tgt = tgt_ref[:][:, 0]
    onehot = jax.lax.broadcasted_iota(jnp.int32, (n, V), 1) == tgt[:, None]
    picked = jnp.sum(jnp.where(onehot, logits, 0.0), axis=1)
    loss_ref[:] = (lse - picked)[:, None]
    lse_ref[:] = lse[:, None]


def fused_cross_entropy_forward(logits, targets, block_n: int = 8):
    N, V = logits.shape
    block_n = min(block_n, N)
    tgt2 = targets.astype(jnp.int32)[:, None]
    loss, lse = pl.pallas_call(
        _xent_kernel,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, V), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(logits, tgt2)
    return loss[:, 0], lse[:, 0]


def _xent_supported(logits, target, weight=None, ignore_index=-100, reduction="mean", label_smoothing=0.0):
    return (
        weight is None and label_smoothing == 0.0 and reduction == "mean"
        and getattr(logits, "ndim", 0) == 2
        and logits.shape[0] % 8 == 0 and logits.shape[1] % 128 == 0
    )


def _xent_impl(logits, target, weight=None, ignore_index=-100, reduction="mean", label_smoothing=0.0):
    loss, _ = fused_cross_entropy_forward(logits, target)
    valid = (target != ignore_index)
    loss = jnp.where(valid, loss, 0.0)
    return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)


_xent_claimed = _jit_claimed(
    _xent_impl, ("ignore_index", "reduction", "label_smoothing"),
    lambda logits, target, weight=None, ignore_index=-100, reduction="mean",
    label_smoothing=0.0: (
        (logits, target, weight, int(ignore_index), str(reduction),
         float(label_smoothing)), {}))


ex.register_implementation(
    "torch.nn.functional.cross_entropy",
    _xent_claimed,
    checker=_xent_supported,
)


# ===========================================================================
# Fused RMSNorm
# ===========================================================================


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=1, keepdims=True)
    w = w_ref[:].astype(jnp.float32)  # (1, D) broadcasts over rows
    o_ref[:] = ((x * jax.lax.rsqrt(ms + eps)) * w).astype(o_ref.dtype)


def fused_rms_norm(x2d, w, eps: float = 1e-6, block_n: int | None = None):
    from ..analysis import budget as _budget

    N, D = x2d.shape
    if block_n is None:  # 256 rows, fewer where a row is too wide for that many in VMEM
        block_n = _budget.rms_norm_block_rows(D, x2d.dtype.itemsize)
    block_n = min(block_n, N)
    return pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        # every row, also where the block does not divide them (a chunk's 512 rows and a dozen
        # decode rows: `N // block_n` blocks left the last rows unwritten); a row is its own
        # reduction, so what the last block reads past the end touches no row that is kept
        grid=(pl.cdiv(N, block_n),),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x2d.dtype),
        interpret=_interpret(),
    )(x2d, w[None, :])


def _rms_supported(a, normalized_shape, weight=None, eps=1e-6):
    return (
        weight is not None and len(normalized_shape) == 1
        and getattr(a, "ndim", 0) >= 2 and a.shape[-1] % 128 == 0
    )


def _rms_impl(a, normalized_shape, weight=None, eps=1e-6):
    shape = a.shape
    x2d = a.reshape((-1, shape[-1]))
    out = fused_rms_norm(x2d, weight, eps)
    return out.reshape(shape)


_rms_claimed = _jit_claimed(
    _rms_impl, ("normalized_shape", "eps"),
    lambda a, normalized_shape, weight=None, eps=1e-6: (
        (a, tuple(int(d) for d in normalized_shape), weight, float(eps)), {}))


ex.register_implementation(
    "torch.nn.functional.rms_norm",
    _rms_claimed,
    checker=_rms_supported,
)


_register_rope_sdpa()


# ===========================================================================
# Fused int8 dequant-matmul (weight-only quantized linear)
# ===========================================================================
#
# XLA hoists a separate dequant out of loops/scans, materializing the full
# bf16 weight and defeating weight-only quantization's HBM saving (measured:
# the "int8" XLA path streams bf16 weights after the first step). This
# kernel keeps weights int8-resident in HBM: each program streams an int8
# (block_n, K) weight block into VMEM, dequantizes slice-wise, and feeds the
# MXU — the quantized analog of the reference's bnb linear executor.


def _int8_linear_kernel(x_ref, w_ref, s_ref, o_ref, *, block_k: int):
    M, K = x_ref.shape
    block_n = w_ref.shape[0]

    def body(j, acc):
        xs = x_ref[:, pl.ds(j * block_k, block_k)]
        ws = w_ref[:, pl.ds(j * block_k, block_k)].astype(xs.dtype)
        return acc + jax.lax.dot_general(xs, ws, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, K // block_k, body,
                            jnp.zeros((M, block_n), jnp.float32))
    o_ref[:] = (acc * s_ref[:][:, 0][None, :]).astype(o_ref.dtype)


def int8_linear(x, qweight, scale, *, block_n: int = 256, block_k: int = 512):
    """x (..., K) @ dequant(qweight (N, K), scale (N,)).T -> (..., N)."""
    shape = x.shape
    K = shape[-1]
    N = qweight.shape[0]
    x2d = x.reshape((-1, K))
    M = x2d.shape[0]
    block_n = math.gcd(block_n, N)
    block_k = math.gcd(block_k, K)
    out = pl.pallas_call(
        functools.partial(_int8_linear_kernel, block_k=block_k),
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((M, K), lambda n: (0, 0)),
            pl.BlockSpec((block_n, K), lambda n: (n, 0)),
            # scale rides as (N, 1): 1-D f32 operands hit XLA/Mosaic layout
            # tiling mismatches ({0:T(1024)} vs the block's {0:T(256)})
            pl.BlockSpec((block_n, 1), lambda n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((M, block_n), lambda n: (0, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=_interpret(),
    )(x2d, qweight, scale.astype(jnp.float32)[:, None])
    return out.reshape(shape[:-1] + (N,))


def _int8_linear_supported(x, qweight, scale, bias=None):
    if getattr(qweight, "ndim", 0) != 2 or getattr(x, "ndim", 0) < 2:
        return False
    N, K = qweight.shape
    M = 1
    for d in x.shape[:-1]:
        M *= int(d)
    # the kernel is a TPU HBM-residency play; on CPU the interpret-mode
    # pallas path is a per-call interpreter, far slower than XLA's
    # dequant-matmul — serving benchmarks must measure the XLA path there
    if not _claims_on_platform():
        return False
    # whole-M block (no M grid): claim the serving/decode regime; huge-M
    # prefill/training shapes stay on the XLA path (compute-bound there)
    return (
        # exact dtype name (proxy dtypes print as "dtypes.int8"): uint8 must
        # NOT claim the kernel — it would be reinterpreted as signed
        str(getattr(qweight, "dtype", "")).rpartition(".")[2] == "int8"
        and x.shape[-1] == K
        and K % 128 == 0 and K <= 8192
        and N % 128 == 0
        and M <= 512
    )


def _int8_linear_impl(x, qweight, scale, bias=None):
    out = int8_linear(x, qweight, scale)
    if bias is not None:
        out = out + bias
    return out


ex.register_implementation("quant.linear_int8", _int8_linear_impl,
                           checker=_int8_linear_supported)


# ===========================================================================
# Fused fp8 delayed-scaling matmul (quantize + amax + matmul, one VMEM pass)
# ===========================================================================
#
# The unfused delayed-scaling linear runs as FOUR device programs per call:
# quantize(x), quantize(w), the fp8 dot, and a separate abs-max reduction
# over each operand for the history roll — each streaming the operand
# through HBM again. The profiler tags the quantize/amax passes memory-bound
# (the fp8 road once measured 0.83x bf16 at 7B-shape width, i.e. the
# scaling overhead ATE the matmul win). This kernel folds all of it into the
# matmul's VMEM pass: each (block_m, block_k) x block and (block_n, block_k)
# w block is cast to f32 once, clipped/scaled to e4m3, max-reduced into the
# running amax, and fed to the MXU as bf16 (every e4m3 value is exactly
# representable in bf16, so the dot is exact in f32 accumulation). The
# quantized blocks are optionally written out as the saved-for-backward
# residuals — the same bytes the unfused path materializes anyway.


def _fp8_matmul_kernel(x_ref, w_ref, sx_ref, sw_ref, *refs,
                       n_k: int, fmt_max: float, save_q: bool):
    if save_q:
        o_ref, xq_ref, wq_ref, ax_ref, aw_ref, acc_ref = refs
    else:
        o_ref, ax_ref, aw_ref, acc_ref = refs
        xq_ref = wq_ref = None
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    sx = sx_ref[0, 0]
    sw = sw_ref[0, 0]
    xq = jnp.clip(x * sx, -fmt_max, fmt_max).astype(jnp.float8_e4m3fn)
    wq = jnp.clip(w * sw, -fmt_max, fmt_max).astype(jnp.float8_e4m3fn)
    if save_q:
        # unconditional store: an x block is revisited once per j (w block
        # once per i) and rewriting the same value sidesteps any
        # leave-and-return output-revisit semantics
        xq_ref[:] = xq
        wq_ref[:] = wq

    # amax of the UNQUANTIZED operands (feeds the delayed-scaling history).
    # The (1, 1) output block is grid-resident (constant index map): init on
    # the first program, then max-accumulate — revisits re-apply the same
    # max, which is idempotent, so no j==0/i==0 gating is needed.
    @pl.when((i == 0) & (j == 0) & (k == 0))
    def _init_amax():
        # explicit f32 literals: under jax_enable_x64 a bare 0.0 stores f64
        ax_ref[0, 0] = jnp.float32(0.0)
        aw_ref[0, 0] = jnp.float32(0.0)

    ax_ref[0, 0] = jnp.maximum(ax_ref[0, 0], jnp.max(jnp.abs(x)))
    aw_ref[0, 0] = jnp.maximum(aw_ref[0, 0], jnp.max(jnp.abs(w)))

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        xq.astype(jnp.bfloat16), wq.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _write_out():
        o_ref[:] = (acc_ref[...] / (sx * sw)).astype(o_ref.dtype)


def fp8_linear_fused(x2d, w, sx, sw, *, fmt_max: float = 448.0,
                     save_quantized: bool = False,
                     block_m: int = 256, block_n: int = 256, block_k: int = 512):
    """Delayed-scaling fp8 linear: ``dequant(q(x2d) @ q(w).T)`` with the
    operand amaxes reduced in the same pass.

    Returns ``(y, amax_x, amax_w)`` — or ``(y, xq, wq, amax_x, amax_w)``
    with ``save_quantized`` (the e4m3 residuals for the backward). ``sx`` /
    ``sw`` are the precomputed delayed scales (scalars)."""
    M, K = x2d.shape
    N = w.shape[0]
    bm = math.gcd(block_m, M)
    bn = math.gcd(block_n, N)
    bk = math.gcd(block_k, K)
    n_k = K // bk
    # scales in and amaxes out are scalars: SMEM (Mosaic has no scalar store
    # to VMEM)
    scalar_spec = pl.BlockSpec((1, 1), lambda i, j, k: (0, 0),
                               memory_space=pltpu.SMEM)
    out_specs = [pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))]
    out_shape = [jax.ShapeDtypeStruct((M, N), x2d.dtype)]
    if save_quantized:
        out_specs += [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                      pl.BlockSpec((bn, bk), lambda i, j, k: (j, k))]
        out_shape += [jax.ShapeDtypeStruct((M, K), jnp.float8_e4m3fn),
                      jax.ShapeDtypeStruct((N, K), jnp.float8_e4m3fn)]
    out_specs += [scalar_spec, scalar_spec]
    out_shape += [jax.ShapeDtypeStruct((1, 1), jnp.float32),
                  jax.ShapeDtypeStruct((1, 1), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_fp8_matmul_kernel, n_k=n_k, fmt_max=fmt_max,
                          save_q=save_quantized),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            scalar_spec,
            scalar_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=_interpret(),
    )(x2d, w,
      jnp.asarray(sx, jnp.float32).reshape(1, 1),
      jnp.asarray(sw, jnp.float32).reshape(1, 1))
    if save_quantized:
        y, xq, wq, ax, aw = outs
        return y, xq, wq, ax[0, 0], aw[0, 0]
    y, ax, aw = outs
    return y, ax[0, 0], aw[0, 0]


def fp8_linear_fused_supported(x2d, w) -> bool:
    """Dispatch gate for the fp8 training executor: the chip
    (`_claims_on_platform`), tile-aligned shapes. The CPU/jnp unfused
    reference stays the fallback everywhere else."""
    if not _claims_on_platform():
        return False
    if getattr(x2d, "ndim", 0) != 2 or getattr(w, "ndim", 0) != 2:
        return False
    M, K = x2d.shape
    N = w.shape[0]
    return K % 128 == 0 and N % 128 == 0 and M % 8 == 0


# ===========================================================================
# Fused NF4 dequant-matmul (4-bit weight-only linear, opt-in serving kernel)
# ===========================================================================
#
# Weights stay PACKED (0.5 byte/element) in HBM; the kernel unpacks nibbles,
# looks the 16-entry NF4 codebook up via a select tree (Mosaic has no
# small-table gather), applies per-64-block absmax via a 0/1 expander dot,
# and feeds the MXU. Measured at a decode GEMM (M=8, K=4096, N=11008):
# ~0.95x the bf16-weight matmul speed at 4x smaller weight footprint — the
# bitsandbytes trade (footprint over speed), TPU-native. Opt-in via
# nf4_linear + pack_nf4_kernel_layout; the canonical QuantizeNF4Transform
# path keeps its XLA dequant (which XLA may hoist/materialize).
#
# Kernel packing layout: within each block_k slice of a row, byte j holds
# the codes of columns j (hi nibble) and j + block_k/2 (lo nibble) — dequant
# is then a contiguous concat, avoiding Mosaic-unsupported lane interleaves.

NF4_KERNEL_BLOCK_K = 512


def nf4_kernel_block_k(K: int, block_size: int = 64):
    """Largest K-slice width the kernel layout supports for this K: a
    divisor of K, multiple of 2*block_size (nibble halves stay block-aligned)
    and of 256 (the (K/2) lane offsets stay 128-aligned), capped at 512.
    None when no such width exists (e.g. K=2816 -> 256; K=1000 -> None)."""
    for bk in (512, 384, 256, 128):
        if bk <= K and K % bk == 0 and bk % (2 * block_size) == 0 and (bk // 2) % 128 == 0:
            return bk
    return None


def pack_nf4_kernel_layout(packed, absmax, shape, block_size: int = 64):
    """Canonical NF4 (flat hi/lo interleave) -> kernel layout
    ((N, K/2) uint8 halves-per-slice + (N, K/block_size) absmax)."""
    N, K = shape
    bk = nf4_kernel_block_k(K, block_size)
    if bk is None:
        raise ValueError(f"no kernel block width for K={K} (see nf4_kernel_block_k)")
    hi = (packed >> 4) & 0xF
    lo = packed & 0xF
    codes = jnp.stack([hi, lo], axis=1).reshape(N, K)
    parts = []
    for j0 in range(0, K, bk):
        sl = codes[:, j0:j0 + bk]
        parts.append((sl[:, : bk // 2] << 4) | sl[:, bk // 2:])
    return jnp.concatenate(parts, axis=1).astype(jnp.uint8), absmax.reshape(N, K // block_size)


def _nf4_codebook_floats():
    # python-float codebook, resolved OUTSIDE kernel tracing (pallas kernels
    # can neither capture array constants nor concretize values mid-trace)
    from ..transforms.quantization import NF4_CODE

    return [float(v) for v in NF4_CODE]


def _nf4_lookup(codes, vals):
    """16-way select tree over the NF4 codebook (Mosaic has no small-table
    gather)."""
    out = jnp.full(codes.shape, vals[0], jnp.float32)
    for idx in range(1, 16):
        out = jnp.where(codes == idx, vals[idx], out)
    return out


def _nf4_linear_kernel(x_ref, p_ref, a_ref, o_ref, *, block_k: int, block_size: int,
                       codebook: tuple):
    M, K = x_ref.shape
    bn = p_ref.shape[0]
    acc = jnp.zeros((M, bn), jnp.float32)
    for j in range(K // block_k):  # static unroll: lane offsets stay provable
        xs = x_ref[:, j * block_k:(j + 1) * block_k]
        byts = p_ref[:, j * (block_k // 2):(j + 1) * (block_k // 2)]
        b32 = byts.astype(jnp.int32)  # minor-dim ops need 32-bit types
        hi = (b32 >> 4) & 0xF
        lo = b32 & 0xF
        w = jnp.concatenate([_nf4_lookup(hi, codebook), _nf4_lookup(lo, codebook)], axis=-1)
        nb = block_k // block_size
        am = a_ref[:, j * nb:(j + 1) * nb]
        # repeat-along-lanes via a 0/1 expander dot (jnp.repeat's reshape is
        # an unsupported Mosaic shape cast)
        row = jax.lax.broadcasted_iota(jnp.int32, (nb, block_k), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (nb, block_k), 1) // block_size
        expander = (row == col).astype(jnp.float32)
        am_full = jax.lax.dot_general(am, expander, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        ws = (w * am_full).astype(xs.dtype)
        acc = acc + jax.lax.dot_general(xs, ws, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
    o_ref[:] = acc.astype(o_ref.dtype)


def nf4_linear(x, packed_kl, absmax_kl, *, block_n: int = 256, block_size: int = 64):
    """x (..., K) against kernel-layout NF4 weights (see
    pack_nf4_kernel_layout) -> (..., N)."""
    shape = x.shape
    K = shape[-1]
    N = packed_kl.shape[0]
    x2d = x.reshape((-1, K))
    M = x2d.shape[0]
    block_n = math.gcd(block_n, N)
    block_k = nf4_kernel_block_k(K, block_size)
    out = pl.pallas_call(
        functools.partial(_nf4_linear_kernel, block_k=block_k, block_size=block_size,
                          codebook=tuple(_nf4_codebook_floats())),
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((M, K), lambda n: (0, 0)),
            pl.BlockSpec((block_n, K // 2), lambda n: (n, 0)),
            pl.BlockSpec((block_n, K // block_size), lambda n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((M, block_n), lambda n: (0, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=_interpret(),
    )(x2d, packed_kl, absmax_kl.astype(jnp.float32))
    return out.reshape(shape[:-1] + (N,))


def _nf4_kl_supported(x, packed_kl, absmax_kl, out_features, in_features,
                      block_size=64, bias=None):
    try:
        N, K, bs = int(out_features), int(in_features), int(block_size)
    except Exception:
        return False
    M = 1
    for d in getattr(x, "shape", ())[:-1]:
        M *= int(d)
    return (
        getattr(x, "ndim", 0) >= 2 and x.shape[-1] == K
        and bs == 64 and nf4_kernel_block_k(K, bs) is not None
        and N % 128 == 0
        and M <= 512
    )


def _nf4_kl_impl(x, packed_kl, absmax_kl, out_features, in_features,
                 block_size=64, bias=None):
    out = nf4_linear(x, packed_kl, absmax_kl, block_size=int(block_size))
    if bias is not None:
        out = out + bias
    return out


ex.register_implementation("quant.linear_nf4_kl", _nf4_kl_impl,
                           checker=_nf4_kl_supported)


# ===========================================================================
# Paged attention — decode (serving engine, thunder_tpu/serving/)
# ===========================================================================
#
# Continuous-batching decode attends ONE new token per sequence against a
# block-paged KV pool (vLLM/PagedAttention, SOSP '23): k/v live in a fixed
# head-major (n_pages, Hkv, page_size, D) pool per layer, so a page is one
# contiguous (Hkv, page_size, D) block, and each sequence owns a row of page
# ids. The decode kernel runs ONE grid program a sequence. The pools stay in
# HBM; the program walks the sequence's own live pages (from the window's
# first page where there is a window), `pages_per_step` whole pages a loop
# step, copying them itself into a double-buffered VMEM scratch (the next
# block in flight while this one is multiplied) and carrying the flash
# kernel's online softmax (base-2 exp, f32 accumulation) across the steps in
# VMEM scratch. A table entry past the sequence or below the window is never
# read, and a slot with nothing in it costs one page. The
# ltorch.paged_attention decomposition (ops/ltorch.py) is the pure-jax gather
# reference path for CPU/interpret mode and for shapes the kernel declines.
#
# Absurd page_size x D configs must fall back, not fail-to-compile: the block
# of pages is sized against the VMEM budget and the claim declined when not
# even one page a step fits (ADVICE r5: estimate + automatic fallback instead
# of an env escape hatch). The estimate, the block size and the fit decision
# live in the unified budget API (analysis/memory.py).

# Two generalisations ride on both paged kernels (a plain GPT uses neither and
# runs the kernels as they were):
#
# * values of another width than the keys: the V pool is (P, Hkv, page_size,
#   Dv) and the output Dv wide (differential attention reads a pair's two value
#   heads side by side, twice as wide as QK);
# * a WINDOW: queries see key positions > q_pos - window only. The page axis
#   then spans the pages a window can intersect and no more, counted from the
#   per-sequence first page `lo` (a third scalar-prefetch operand); table
#   entries below it are never read (the engine has freed those pages).


def _paged_softmax_update(q, k, v, live, acc, m_prev, l_prev, scale):
    """One block of keys of the online softmax (base-2, f32 accumulation):
    the new (acc, m, l) from the old; m and l are columns."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * (scale * LOG2E)
    s = jnp.where(live, s, NEG_INF)
    m_prev = m_prev[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    pexp = jnp.exp2(s - m_new[:, None])
    corr = jnp.exp2(m_prev - m_new)
    acc = acc * corr[:, None] + jax.lax.dot_general(
        pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc, m_new[:, None], (l_prev[:, 0] * corr + jnp.sum(pexp, axis=1))[:, None]


def _paged_softmax_step(q, k, v, live, acc_scr, m_scr, l_scr, scale):
    """One page of the online softmax into scratch."""
    acc_scr[:], m_scr[:], l_scr[:] = _paged_softmax_update(
        q, k, v, live, acc_scr[:], m_scr[:], l_scr[:], scale)


def _paged_init(acc_scr, m_scr, l_scr):
    acc_scr[:] = jnp.zeros_like(acc_scr)
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)


def _paged_write(o_ref, acc_scr, l_scr):
    l = l_scr[:][:, 0]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[:] = (acc_scr[:] / l_safe[:, None]).astype(o_ref.dtype)


def _paged_attn_kernel(*refs, scale: float, window, pages_per_step: int, head_block: int):
    # grid (B,), in order: one program does every KV head of one sequence.
    # q_ref (Hkv, g, D), o_ref (Hkv, g, Dv); k_hbm / v_hbm are the whole pools,
    # left in HBM. k_buf (2, pps, Hkv, page_size, D) and v_buf (.., Dv) take
    # `pps` whole pages a step; sems (2, 2) is [k or v, buffer]; slot_ref holds
    # the buffer a program's first step is in, which the program before it
    # began to fill during its own last step. Heads go through the MXU
    # `head_block` at a time, their query groups stacked into the rows of one
    # matmul against the step's keys of all those heads, with the products of a
    # row and another head's keys masked like dead positions: a group of 4
    # rows alone leaves the tiles mostly empty. (Multiplying a step's live
    # pages only, two or four at a time, was slower at every length: v5e, PR 28.)
    # q_scr, acc_scr, m_scr and l_scr hold a head block a leading index.
    if window is None:
        pt_ref, sl_ref, q_ref, k_hbm, v_hbm, o_ref, *scratch = refs
    else:
        pt_ref, sl_ref, lo_ref, q_ref, k_hbm, v_hbm, o_ref, *scratch = refs
    k_buf, v_buf, sems, slot_ref, q_scr, acc_scr, m_scr, l_scr = scratch
    b = pl.program_id(0)
    Hkv, g, D = q_ref.shape
    ps, Dv = k_buf.shape[3], v_buf.shape[4]
    pps, hb = pages_per_step, head_block
    rows, cols = hb * g, pps * hb * ps

    def span(seq):
        """Pages [first, end) hold what sequence ``seq``'s query sees."""
        first = 0 if window is None else lo_ref[seq]
        return first, (sl_ref[seq] + ps - 1) // ps

    def copies(seq, page, slot, j):
        pid = pt_ref[seq, page]
        return (pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[slot, j], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[slot, j], sems.at[1, slot]))

    def fetch(seq, step, slot):
        first, end = span(seq)
        for j in range(pps):
            page = first + step * pps + j

            @pl.when(page < end)
            def _start():
                for c in copies(seq, page, slot, j):
                    c.start()

            # past the sequence nothing is copied and the table is not read:
            # the keys there are masked whatever they are, and a probability
            # of zero still needs values that are numbers
            @pl.when(page >= end)
            def _blank():
                v_buf[slot, j] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)

    first, end = span(b)
    seq_len = sl_ref[b]
    n_steps = jnp.maximum((end - first + pps - 1) // pps, 1)

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        fetch(0, 0, 0)

    slot0 = slot_ref[0]
    for h in range(Hkv):
        q_scr[h // hb, (h % hb) * g:(h % hb + 1) * g, :] = q_ref[h]
    _paged_init(acc_scr, m_scr, l_scr)
    q_blocks = [q_scr[i] for i in range(Hkv // hb)]
    # column c of a step's scores is position c % ps of its page c // (hb * ps),
    # under head (c // ps) % hb of the block; row r is of head r // g
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    offset = (col // (hb * ps)) * ps + col % ps
    if hb > 1:
        own_head = (col // ps) % hb == jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) // g

    def step_body(i, carry):
        slot = (slot0 + i) % 2
        page0 = first + i * pps

        @pl.when(i + 1 < n_steps)
        def _next_step():
            fetch(b, i + 1, 1 - slot)

        @pl.when((i + 1 == n_steps) & (b + 1 < pl.num_programs(0)))
        def _next_sequence():
            fetch(b + 1, 0, 1 - slot)
            slot_ref[0] = 1 - slot

        for j in range(pps):
            @pl.when(page0 + j < end)
            def _wait():
                for c in copies(b, page0 + j, slot, j):
                    c.wait()

        k_pos = page0 * ps + offset
        live = k_pos < seq_len
        if window is not None:
            live = live & (k_pos >= seq_len - window)
        if hb > 1:
            live = live & own_head
        for hi, q in enumerate(q_blocks):
            at = (slot, slice(None), slice(hi * hb, (hi + 1) * hb))
            acc_scr[hi], m_scr[hi], l_scr[hi] = _paged_softmax_update(
                q, k_buf[at].reshape(cols, D), v_buf[at].reshape(cols, Dv), live,
                acc_scr[hi], m_scr[hi], l_scr[hi], scale)
        return carry

    jax.lax.fori_loop(0, n_steps, step_body, 0)

    for h in range(Hkv):
        at = (h // hb, slice((h % hb) * g, (h % hb + 1) * g), slice(None))
        l = l_scr[at]
        o_ref[h] = (acc_scr[at] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _paged_decode_blocks(q_heads: int, D: int, q_itemsize: int, k_pages, v_pages):
    """(pages a loop step, KV heads a matmul) of the decode kernel for these
    pools: what fits the budget, from the shapes alone. 0 pages: nothing fits."""
    from ..analysis import budget as _budget

    Hkv, ps, Dv = k_pages.shape[1], k_pages.shape[2], v_pages.shape[3]
    kv_item = jnp.dtype(str(k_pages.dtype).rpartition(".")[2]).itemsize
    pps = _budget.paged_pages_per_step(ps, D, q_heads // Hkv, kv_item, q_itemsize,
                                       Dv=Dv, n_kv_heads=Hkv)
    return pps, _budget.paged_head_block(Hkv, q_heads // Hkv)


_PAGED_REFUSALS = {
    "lanes": "the kernel copies whole pages out of HBM itself, which the compiler takes only of "
             "rows that fill the 128 lanes (cache narrow heads several a row: "
             "serving/runner.py heads_a_row)",
    "vmem": "not one page of every KV head fits the VMEM budget twice over "
            "(analysis.budget.paged_vmem_limit)",
}


def _paged_refusal(D: int, v_pages, pages_per_step: int, compiled: bool):
    """Why a paged kernel cannot take these pools, or None: ``"lanes"`` when
    it is to be compiled and a pool's rows do not fill the 128 lanes (the
    kernel copies whole pages out of HBM itself, and Mosaic pads a narrower
    pool's rows in HBM and then refuses the slice of one page; the interpreter
    takes any width), ``"vmem"`` when not one page a loop step fits the budget
    (``pages_per_step`` 0, from the kernel's blocks)."""
    if compiled and (D % 128 or v_pages.shape[3] % 128):
        return "lanes"
    return None if pages_per_step else "vmem"


def _paged_decode_refusal(q_heads: int, D: int, q_itemsize: int, k_pages, v_pages,
                          compiled: bool):
    return _paged_refusal(D, v_pages, _paged_decode_blocks(q_heads, D, q_itemsize, k_pages, v_pages)[0],
                          compiled)


def paged_attention_decode(q, k_pages, v_pages, page_table, seq_lens, scale=None, window=None,
                           *, interpret: bool | None = None):
    """q (B, H, D) against a paged pool — keys (P, Hkv, page_size, D), values
    (P, Hkv, page_size, Dv) — through page_table (B, n_pages_max) int32 /
    seq_lens (B,) int32 -> (B, H, Dv).

    seq_lens counts valid tokens INCLUDING the current one (whose k/v must
    already be written to its page). With ``window`` only key positions
    >= seq_len - window are read. interpret=True runs the kernel in pallas
    interpret mode (the CPU equivalence tests)."""
    B, H, D = q.shape
    Hkv, ps, Dv = k_pages.shape[1], k_pages.shape[2], v_pages.shape[3]
    g = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    interpret = _interpret() if interpret is None else interpret
    if not interpret:  # what the checker declines, a direct call is refused by name
        refusal = _paged_decode_refusal(H, D, q.dtype.itemsize, k_pages, v_pages, True)
        if refusal:
            raise ValueError(f"paged_attention_decode cannot take keys {tuple(k_pages.shape)} and "
                             f"values {tuple(v_pages.shape)}: {_PAGED_REFUSALS[refusal]}")
    pps, hb = _paged_decode_blocks(H, D, q.dtype.itemsize, k_pages, v_pages)
    pps = max(pps, 1)  # the interpreter has no VMEM to run out of
    seq_lens = seq_lens.astype(jnp.int32)
    prefetch = [page_table.astype(jnp.int32), seq_lens]
    if window is not None:
        prefetch.append(jnp.maximum(seq_lens - window, 0) // ps)

    def rows(width):
        return pl.BlockSpec((None, Hkv, g, width), lambda b, *_: (b, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B,),
        in_specs=[rows(D), pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=rows(Dv),
        scratch_shapes=[pltpu.VMEM((2, pps, Hkv, ps, D), k_pages.dtype),
                        pltpu.VMEM((2, pps, Hkv, ps, Dv), v_pages.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((Hkv // hb, hb * g, D), q.dtype),
                        pltpu.VMEM((Hkv // hb, hb * g, Dv), jnp.float32),
                        pltpu.VMEM((Hkv // hb, hb * g, 1), jnp.float32),
                        pltpu.VMEM((Hkv // hb, hb * g, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, scale=scale, window=window,
                          pages_per_step=pps, head_block=hb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, Dv), q.dtype),
        interpret=interpret,
    )(*prefetch, q.reshape(B, Hkv, g, D), k_pages, v_pages)
    return out.reshape(B, H, Dv)


def _paged_shapes_ok(q_heads: int, D: int, k_pages, v_pages, page_table, B: int) -> bool:
    """What both paged checkers ask of the pools and the table."""
    if getattr(k_pages, "ndim", 0) != 4 or getattr(v_pages, "ndim", 0) != 4:
        return False
    P, Hkv, ps, Dk = k_pages.shape
    return (D == Dk and D <= 512 and v_pages.shape[3] <= 512
            and tuple(v_pages.shape[:3]) == (P, Hkv, ps)
            and q_heads % Hkv == 0
            and ps % 8 == 0  # sublane tile
            and getattr(page_table, "ndim", 0) == 2 and page_table.shape[0] == B)


def paged_attention_supported(q, k_pages, v_pages, page_table, seq_lens, scale=None,
                              window=None) -> bool:
    """Checker: the paged decode kernel claims thunder.paged_attention on
    the chip (`_claims_on_platform`); shapes must fit the page tiling, the
    pools' rows fill the lanes and at least one whole page a loop step fits
    the VMEM budget — otherwise the pure-jax gather decomposition runs."""
    if not _claims_on_platform():
        return False
    if getattr(q, "ndim", 0) != 3:
        return False
    B, H, D = q.shape
    if not (_paged_shapes_ok(H, D, k_pages, v_pages, page_table, B)
            and getattr(seq_lens, "ndim", 0) == 1 and seq_lens.shape[0] == B):
        return False
    q_item = jnp.dtype(str(q.dtype).rpartition(".")[2]).itemsize
    refusal = _paged_decode_refusal(H, D, q_item, k_pages, v_pages, _on_tpu())
    return _decline("paged_attention", refusal) if refusal else True


def _paged_attention_impl(q, k_pages, v_pages, page_table, seq_lens, scale=None, window=None):
    return paged_attention_decode(q, k_pages, v_pages, page_table, seq_lens, scale, window)


ex.register_implementation("thunder.paged_attention", _paged_attention_impl,
                           checker=paged_attention_supported)


# ---------------------------------------------------------------------------
# Paged attention — multi-query (chunked prefill + speculative verify)
# ---------------------------------------------------------------------------
#
# The fleet-serving programs attend MORE than one new token per sequence
# against the same paged pool: a chunked-prefill chunk (B=1, T=chunk tokens)
# and the speculative-decoding verify step (T=k+1 proposals per packed
# sequence), both with PER-QUERY causal coverage k_pos <= q_pos[b, t]. The
# kernel is the decode kernel's pattern with a tile of queries in the place
# of one: the grid is (B, groups of KV heads, tiles of the T queries), the
# pools stay in HBM, and a program walks the pages ITS tile's queries can see
# (from the window's first page where there is a window, to the page of the
# tile's last position and no further), `pages_per_step` whole pages a loop
# step through a double-buffered VMEM scratch that its own copies fill (the
# next step's, or the next program's first, in flight). A step's scores are
# one matmul of a KV head's query rows (g x tile) against all of the step's
# keys, so the maximum, the accumulator's rescale and the mask are paid once a
# step. The tile, the heads a program and the pages a step follow from the
# shapes and the VMEM budget (analysis/memory.py: paged_chunk_blocks); the
# per-query positions ride as a (rows, 1) VMEM column for the masking, and
# each tile's coverage bound (and first window page) as scalar-prefetch
# operands beside the table. Shared (copy-on-write) page tables are
# transparent: a physical page shared by N sequences simply appears in N table
# rows. Table entries past a tile's last visible page are never read.


def _paged_chunk_kernel(*refs, scale: float, window, pages_per_step: int):
    # grid (B, Hkv // hg, T // Tq), in order. q_ref (hg, G, R, D) and o_ref
    # (hg, G, R, Dv) hold `rows` = G * R = g * Tq query rows a KV head, row r
    # query r % Tq of the tile; qp_ref (rows, 1) their absolute positions (a
    # vector cannot index the SMEM prefetch operands); end_ref and lo_ref hold
    # a tile's coverage bound (its last position + 1) and first window page at
    # [sequence * tiles + tile]. k_hbm / v_hbm are the whole pools, left in
    # HBM; k_buf (2, pps, hg, page_size, D) and v_buf (.., Dv) take the
    # program's heads of `pps` pages a step; sems (2, 2) is [k or v, buffer];
    # slot_ref holds the buffer a program's first step is in, which the
    # program before it began to fill during its own last step.
    if window is None:
        pt_ref, end_ref, q_ref, qp_ref, k_hbm, v_hbm, o_ref, *scratch = refs
    else:
        pt_ref, end_ref, lo_ref, q_ref, qp_ref, k_hbm, v_hbm, o_ref, *scratch = refs
    k_buf, v_buf, sems, slot_ref, acc_scr, m_scr, l_scr = scratch
    b, hi, qi = (pl.program_id(a) for a in range(3))
    n_b, n_h, n_q = (pl.num_programs(a) for a in range(3))
    hg, ps, D = k_buf.shape[2:]
    Dv = v_buf.shape[4]
    pps = pages_per_step
    rows, cols = acc_scr.shape[1], pps * ps

    def span(seq, tile):
        """Pages [first, end) hold what the queries of ``tile`` of ``seq`` see."""
        at = seq * n_q + tile
        return (0 if window is None else lo_ref[at]), (end_ref[at] + ps - 1) // ps

    def copy_pages(seq, heads, tile, step, slot, wait: bool):
        """Start, or wait for, the copies of the pages of loop step ``step``
        of that program into buffer ``slot``. Past the tile's last page
        nothing is copied and the table is not read: the keys there are
        masked by position whatever they are."""
        first, end = span(seq, tile)
        page0 = first + step * pps

        def one(j, carry):
            at = (pt_ref[seq, page0 + j], pl.ds(heads * hg, hg))
            for c in (pltpu.make_async_copy(k_hbm.at[at], k_buf.at[slot, j], sems.at[0, slot]),
                      pltpu.make_async_copy(v_hbm.at[at], v_buf.at[slot, j], sems.at[1, slot])):
                c.wait() if wait else c.start()
            return carry

        jax.lax.fori_loop(0, jnp.clip(end - page0, 0, pps), one, 0)

    first, end = span(b, qi)
    n_steps = jnp.maximum((end - first + pps - 1) // pps, 1)
    # the program after this one: the next tile, else the next heads, else the next sequence
    last_tile, last_heads = qi + 1 == n_q, hi + 1 == n_h
    nxt = (jnp.where(last_tile & last_heads, b + 1, b),
           jnp.where(last_tile, jnp.where(last_heads, 0, hi + 1), hi),
           jnp.where(last_tile, 0, qi + 1))

    @pl.when((b == 0) & (hi == 0) & (qi == 0))
    def _first():
        # a probability of zero still needs values that are numbers: where no
        # live page was ever copied the buffers hold zeros, not what VMEM held
        v_buf[:] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        copy_pages(0, 0, 0, 0, 0, wait=False)

    slot0 = slot_ref[0]
    _paged_init(acc_scr, m_scr, l_scr)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)

    def step_body(i, carry):
        slot = (slot0 + i) % 2
        page0 = first + i * pps

        @pl.when(i + 1 < n_steps)
        def _next_step():
            copy_pages(b, hi, qi, i + 1, 1 - slot, wait=False)

        @pl.when((i + 1 == n_steps) & (nxt[0] < n_b))
        def _next_program():
            copy_pages(*nxt, 0, 1 - slot, wait=False)
            slot_ref[0] = 1 - slot

        copy_pages(b, hi, qi, i, slot, wait=True)

        # column c of a step's scores is position page0 * ps + c: its pages
        # follow one another. (Steps that every query of the tile sees whole
        # need no mask; leaving it out of them bought nothing: v5e, PR 32.)
        rel = qp_ref[:] - page0 * ps
        live = col <= rel
        if window is not None:
            live = live & (col > rel - window)
        for h in range(hg):
            acc_scr[h], m_scr[h], l_scr[h] = _paged_softmax_update(
                q_ref[h].reshape(rows, D), k_buf[slot, :, h].reshape(cols, D),
                v_buf[slot, :, h].reshape(cols, Dv), live, acc_scr[h], m_scr[h], l_scr[h], scale)
        return carry

    jax.lax.fori_loop(0, n_steps, step_body, 0)

    for h in range(hg):
        l = l_scr[h]
        out = acc_scr[h] / jnp.where(l == 0.0, 1.0, l)
        o_ref[h] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def _paged_chunk_blocks(q_heads: int, T: int, D: int, q_itemsize: int, k_pages, v_pages):
    """(query tile, KV heads a program, pages a loop step) of the chunk kernel
    for these queries and pools: what fits the budget, from the shapes alone.
    0 pages: nothing fits."""
    from ..analysis import budget as _budget

    Hkv, ps, Dv = k_pages.shape[1], k_pages.shape[2], v_pages.shape[3]
    kv_item = jnp.dtype(str(k_pages.dtype).rpartition(".")[2]).itemsize
    return _budget.paged_chunk_blocks(ps, D, q_heads // Hkv, T, kv_item, q_itemsize,
                                      Dv=Dv, n_kv_heads=Hkv)


def paged_chunk_decode(q, k_pages, v_pages, page_table, q_pos, scale=None, window=None,
                       *, interpret: bool | None = None):
    """q (B, H, T, D) against a paged pool — keys (P, Hkv, page_size, D),
    values (P, Hkv, page_size, Dv) — through page_table (B, n_pages_max) with
    per-query positions q_pos (B, T) int32 -> (B, H, T, Dv). Each query
    attends key positions <= its own, and with ``window`` > its own - window."""
    B, H, T, D = q.shape
    Hkv, ps, Dv = k_pages.shape[1], k_pages.shape[2], v_pages.shape[3]
    g = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    interpret = _interpret() if interpret is None else interpret
    Tq, hg, pps = _paged_chunk_blocks(H, T, D, q.dtype.itemsize, k_pages, v_pages)
    refusal = None if interpret else _paged_refusal(D, v_pages, pps, True)
    if refusal:  # what the checker declines, a direct call is refused by name
        raise ValueError(f"paged_chunk_decode cannot take {T} queries a sequence, keys "
                         f"{tuple(k_pages.shape)} and values {tuple(v_pages.shape)}: "
                         f"{_PAGED_REFUSALS[refusal]}")
    if not pps:  # the interpreter has no VMEM to run out of
        Tq, hg, pps = T, Hkv, 1
    n_q = T // Tq
    # a KV head's rows of one tile: its g query heads' Tq queries, (g, Tq) of
    # (g, T) where the queries are tiled and the whole (1, g * T) where not
    # (a T that is no multiple of the sublane tile then needs no relayout)
    G, R = (1, g * T) if n_q == 1 else (g, T)
    q_pos = q_pos.astype(jnp.int32)
    tiles = q_pos.reshape(B, n_q, Tq)
    # a tile's coverage bound, inside the table, and the first page of its window
    prefetch = [page_table.astype(jnp.int32),
                jnp.minimum(jnp.max(tiles, axis=2) + 1, page_table.shape[1] * ps).reshape(-1)]
    if window is not None:
        prefetch.append((jnp.maximum(jnp.min(tiles, axis=2) - window + 1, 0) // ps).reshape(-1))
    # row r of a tile's q block is query r % Tq of the tile
    qp_rows = jnp.broadcast_to(tiles[:, :, None, :], (B, n_q, g, Tq)).reshape(B, n_q * g * Tq, 1)

    def rows(width):
        return pl.BlockSpec((None, hg, G, R // n_q, width), lambda b, h, t, *_: (b, h, 0, t, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, Hkv // hg, n_q),
        in_specs=[rows(D), pl.BlockSpec((None, g * Tq, 1), lambda b, h, t, *_: (b, t, 0)),
                  pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=rows(Dv),
        scratch_shapes=[pltpu.VMEM((2, pps, hg, ps, D), k_pages.dtype),
                        pltpu.VMEM((2, pps, hg, ps, Dv), v_pages.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((hg, g * Tq, Dv), jnp.float32),
                        pltpu.VMEM((hg, g * Tq, 1), jnp.float32),
                        pltpu.VMEM((hg, g * Tq, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_chunk_kernel, scale=scale, window=window, pages_per_step=pps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, R, Dv), q.dtype),
        interpret=interpret,
    )(*prefetch, q.reshape(B, Hkv, G, R, D), qp_rows, k_pages, v_pages)
    return out.reshape(B, H, T, Dv)


def paged_chunk_attention_supported(q, k_pages, v_pages, page_table, q_pos,
                                    scale=None, window=None) -> bool:
    """Checker for thunder.paged_chunk_attention: same claim policy as the
    decode kernel (the chip, page tiling, pools whose rows fill the lanes, at
    least one page a loop step beside a tile of the queries inside the VMEM
    budget)."""
    if not _claims_on_platform():
        return False
    if getattr(q, "ndim", 0) != 4:
        return False
    B, H, T, D = q.shape
    if not (_paged_shapes_ok(H, D, k_pages, v_pages, page_table, B)
            and getattr(q_pos, "ndim", 0) == 2 and tuple(q_pos.shape) == (B, T)):
        return False
    q_item = jnp.dtype(str(q.dtype).rpartition(".")[2]).itemsize
    refusal = _paged_refusal(D, v_pages, _paged_chunk_blocks(H, T, D, q_item, k_pages, v_pages)[2], _on_tpu())
    return _decline("paged_chunk_attention", refusal) if refusal else True


def _paged_chunk_attention_impl(q, k_pages, v_pages, page_table, q_pos, scale=None, window=None):
    return paged_chunk_decode(q, k_pages, v_pages, page_table, q_pos, scale, window)


ex.register_implementation("thunder.paged_chunk_attention", _paged_chunk_attention_impl,
                           checker=paged_chunk_attention_supported)


# ---------------------------------------------------------------------------
# Paged write — a program's new key and value rows into both pools of a layer
# ---------------------------------------------------------------------------
#
# A decode step writes one row a sequence and KV head, a pass over blocks or a
# speculative verify step a few consecutive ones; XLA lowers that to a scatter
# whose cost follows the rows, one update a (token, head) row. The compiler
# refuses a copy of a single row of a packed type, so this kernel copies the
# ALIGNED TILES a sequence's rows fall in (``paged_write_tile_rows``: 16 rows of
# bf16, every KV head at once) out of HBM, patches the rows in VMEM and copies
# the tiles back: all reads in flight, then every patch, then all writes. K1
# consecutive rows of one sequence lie in at most two tiles, which may be on
# two pages. A row on the null page (an idle slot, the inactive block of a
# pass, a speculative row past the table) is skipped: the null page is masked
# everywhere, so what it holds is never read, and no two sequences then share
# a tile (a live sequence never writes a shared page: copy-on-write,
# serving/kv_pages.py). The pools are aliased to the outputs and stay in HBM.


def _paged_write_kernel(pg_ref, sl_ref, kr_ref, vr_ref, k_in, v_in, k_out, v_out,
                        k_buf, v_buf, sems, *, rows: int):
    # grid (B // seqs,): a program does `seqs` sequences of `rows` rows. pg_ref
    # and sl_ref (B * rows,) hold the page and slot of row r of sequence b at
    # b * rows + r; kr_ref (seqs, rows * Hkv, D) and vr_ref (.., Dv) the new
    # rows, row r of head h at r * Hkv + h. k_in / v_in and k_out / v_out are
    # the same pools (aliased), in HBM. k_buf (seqs, 2, Hkv, tile rows, D) and
    # v_buf (.., Dv) hold a sequence's two tiles; sems (2,) is [reads, writes].
    seqs, _, Hkv, tr = k_buf.shape[:4]
    first_seq = pl.program_id(0) * seqs

    def tiles(s):
        """Sequence s's rows: the index of its first into the tables, whether
        one is live, the tiles (page, first slot) of its first and its last
        live row, and whether those are two."""
        base = (first_seq + s) * rows
        page_a = slot_a = page_z = slot_z = jnp.int32(0)
        found = jnp.bool_(False)
        for r in range(rows):
            page, slot = pg_ref[base + r], sl_ref[base + r]
            live = page != 0
            first = live & jnp.logical_not(found)
            page_a, slot_a = jnp.where(first, page, page_a), jnp.where(first, slot, slot_a)
            page_z, slot_z = jnp.where(live, page, page_z), jnp.where(live, slot, slot_z)
            found = found | live
        a, z = (page_a, slot_a // tr * tr), (page_z, slot_z // tr * tr)
        return base, found, (a, z), (a[0] != z[0]) | (a[1] != z[1])

    def each(fn):
        """fn(s, t, page, first slot, base) for every tile t a sequence s writes."""
        def body(s, carry):
            base, found, ends, two = tiles(s)
            for t, (page, t0), cond in ((0, ends[0], found), (1, ends[1], found & two)):
                pl.when(cond)(functools.partial(fn, s, t, page, t0, base))
            return carry

        jax.lax.fori_loop(0, seqs, body, 0)

    def copies(s, t, page, t0, write: bool):
        at = (page, slice(None), pl.ds(pl.multiple_of(t0, tr), tr), slice(None))
        if write:
            return [pltpu.make_async_copy(buf.at[s, t], pool.at[at], sems.at[1])
                    for buf, pool in ((k_buf, k_out), (v_buf, v_out))]
        return [pltpu.make_async_copy(pool.at[at], buf.at[s, t], sems.at[0])
                for buf, pool in ((k_buf, k_in), (v_buf, v_in))]

    def start(write):
        def run(s, t, page, t0, base):
            for c in copies(s, t, page, t0, write):
                c.start()
        return run

    def wait(write):
        def run(s, t, page, t0, base):
            for c in copies(s, t, page, t0, write):
                c.wait()
        return run

    def patch(s, t, page, t0, base):
        # row r goes to sublane slot - t0 of this tile where it is on its page
        # and in its range: a dead row's page is 0, never a tile's
        where = [(pg_ref[base + r] == page) & (sl_ref[base + r] >= t0) & (sl_ref[base + r] < t0 + tr)
                 for r in range(rows)]
        offset = [sl_ref[base + r] - t0 for r in range(rows)]
        for buf, new in ((k_buf, kr_ref), (v_buf, vr_ref)):
            at_row = jax.lax.broadcasted_iota(jnp.int32, buf.shape[3:], 0)
            for h in range(Hkv):
                tile = buf[s, t, h]
                for r in range(rows):
                    row = jnp.broadcast_to(new[s, pl.ds(r * Hkv + h, 1), :], tile.shape)
                    tile = jnp.where(where[r] & (at_row == offset[r]), row, tile)
                buf[s, t, h] = tile

    each(start(False))
    each(wait(False))
    each(patch)
    each(start(True))
    each(wait(True))


_PAGED_WRITE_REFUSALS = {
    "lanes": _PAGED_REFUSALS["lanes"],
    "rows": "a sequence's rows span more than two tiles of the pool (more rows than one tile holds)",
    "vmem": "not one sequence's two tiles and rows fit the VMEM budget (analysis.budget.paged_vmem_limit)",
}


def _paged_write_blocks(B: int, K1: int, Hkv: int, D: int, Dv: int, itemsize: int):
    """(rows of a tile, sequences a program) of the page writer: 0 sequences
    when not one fits the budget."""
    from ..analysis import budget as _budget

    return (_budget.paged_write_tile_rows(itemsize),
            _budget.paged_write_seqs_per_step(B, K1, Hkv, D, Dv, itemsize))


def _paged_write_refusal(K1: int, D: int, Dv: int, tile_rows: int, seqs: int, compiled: bool):
    """Why the page writer cannot take these rows and pools, or None: ``"lanes"``
    when it is to be compiled and a pool's rows do not fill the 128 lanes (as
    the paged attention kernels), ``"rows"`` when a sequence's rows may span more
    than two tiles, ``"vmem"`` when not one sequence a program fits."""
    if compiled and (D % 128 or Dv % 128):
        return "lanes"
    if K1 > tile_rows:
        return "rows"
    return None if seqs else "vmem"


def paged_write_rows(k_pool, v_pool, page_of, slot_in_page, k_rows, v_rows, *,
                     interpret: bool | None = None):
    """k_rows (B, K1, Hkv, D) and v_rows (B, K1, Hkv, Dv) into the pools (P,
    Hkv, page_size, D / Dv) at page_of / slot_in_page (B, K1) int32 ->
    (k_pool, v_pool), written in place (the pools are aliased to the outputs).
    A sequence's live rows are consecutive positions; rows on page 0 are not
    written."""
    B, K1, Hkv, D = k_rows.shape
    Dv = v_rows.shape[3]
    interpret = _interpret() if interpret is None else interpret
    tr, seqs = _paged_write_blocks(B, K1, Hkv, D, Dv, k_pool.dtype.itemsize)
    refusal = _paged_write_refusal(K1, D, Dv, tr, seqs, not interpret)
    if refusal and not (interpret and refusal == "vmem"):  # the interpreter has no VMEM
        raise ValueError(f"paged_write_rows cannot take {K1} rows a sequence into pools "
                         f"{tuple(k_pool.shape)} and {tuple(v_pool.shape)}: {_PAGED_WRITE_REFUSALS[refusal]}")
    seqs = seqs or B

    def new(width):
        return pl.BlockSpec((seqs, K1 * Hkv, width), lambda i, *_: (i, 0, 0))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // seqs,),
        in_specs=[new(D), new(Dv), hbm, hbm],
        out_specs=[hbm, hbm],
        scratch_shapes=[pltpu.VMEM((seqs, 2, Hkv, tr, D), k_pool.dtype),
                        pltpu.VMEM((seqs, 2, Hkv, tr, Dv), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return tuple(pl.pallas_call(
        functools.partial(_paged_write_kernel, rows=K1),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(page_of.reshape(B * K1).astype(jnp.int32), slot_in_page.reshape(B * K1).astype(jnp.int32),
      k_rows.reshape(B, K1 * Hkv, D), v_rows.reshape(B, K1 * Hkv, Dv), k_pool, v_pool))


def paged_write_supported(k_pool, v_pool, page_of, slot_in_page, k_rows, v_rows) -> bool:
    """Checker for thunder.paged_write: claims on the chip (`_claims_on_platform`)
    where pools and rows agree (one dtype, whole tiles a page), the pools' rows
    fill the lanes, a sequence's rows fit two tiles and one sequence a program
    fits the VMEM budget; otherwise the row scatter of the decomposition runs."""
    if not _claims_on_platform():
        return False
    ndims = ((k_pool, 4), (v_pool, 4), (page_of, 2), (slot_in_page, 2), (k_rows, 4), (v_rows, 4))
    if any(getattr(t, "ndim", 0) != n for t, n in ndims):
        return False
    P, Hkv, ps, D = k_pool.shape
    B, K1 = page_of.shape
    Dv = v_pool.shape[3]
    if not (tuple(v_pool.shape[:3]) == (P, Hkv, ps) and tuple(slot_in_page.shape) == (B, K1)
            and tuple(k_rows.shape) == (B, K1, Hkv, D) and tuple(v_rows.shape) == (B, K1, Hkv, Dv)
            and len({str(t.dtype) for t in (k_pool, v_pool, k_rows, v_rows)}) == 1):
        return False
    tr, seqs = _paged_write_blocks(B, K1, Hkv, D, Dv,
                                   jnp.dtype(str(k_pool.dtype).rpartition(".")[2]).itemsize)
    if ps % tr:  # a page of whole tiles
        return False
    refusal = _paged_write_refusal(K1, D, Dv, tr, seqs, _on_tpu())
    return _decline("paged_write", refusal) if refusal else True


def _paged_write_impl(k_pool, v_pool, page_of, slot_in_page, k_rows, v_rows):
    return paged_write_rows(k_pool, v_pool, page_of, slot_in_page, k_rows, v_rows)


ex.register_implementation("thunder.paged_write", _paged_write_impl, checker=paged_write_supported)


# ---------------------------------------------------------------------------
# Paged attention — decode over ONE latent pool (multi-head latent attention)
# ---------------------------------------------------------------------------
#
# The absorbed form of latent attention (ops/ltorch.py paged_latent_attention)
# has no per-head keys or values: a cached row IS every head's key, and its
# first `v_width` columns every head's value. The kernel is the decode kernel
# above with one pool and one head of keys: one grid program a sequence walks
# its live pages `pages_per_step` a loop step through a double-buffered VMEM
# scratch (the next block, or the next sequence's first, in flight), all the
# query heads are the rows of one matmul against the step's rows, and the
# values are a slice of the same VMEM tile: a row crosses HBM once.


def _latent_attn_kernel(pt_ref, sl_ref, q_ref, c_hbm, o_ref, c_buf, sems, slot_ref,
                        acc_scr, m_scr, l_scr, *, scale: float, pages_per_step: int, v_width: int):
    # grid (B,), in order. q_ref (H, W), o_ref (H, v_width); c_hbm the whole
    # pool (P, page_size, W), left in HBM; c_buf (2, pps, page_size, W); sems
    # (2,) one a buffer; slot_ref the buffer this program's first step is in.
    b = pl.program_id(0)
    pps = pages_per_step
    ps, W = c_buf.shape[2], c_buf.shape[3]
    H = q_ref.shape[0]
    cols = pps * ps

    def end_of(seq):
        return (sl_ref[seq] + ps - 1) // ps

    def copy(seq, page, slot, j):
        return pltpu.make_async_copy(c_hbm.at[pt_ref[seq, page]], c_buf.at[slot, j], sems.at[slot])

    def fetch(seq, step, slot):
        end = end_of(seq)
        for j in range(pps):
            page = step * pps + j

            @pl.when(page < end)
            def _start():
                copy(seq, page, slot, j).start()

            # past the sequence nothing is copied: the rows there are masked,
            # and a probability of zero still needs values that are numbers
            @pl.when(page >= end)
            def _blank():
                c_buf[slot, j] = jnp.zeros((ps, W), c_buf.dtype)

    end = end_of(b)
    seq_len = sl_ref[b]
    n_steps = jnp.maximum((end + pps - 1) // pps, 1)

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        fetch(0, 0, 0)

    slot0 = slot_ref[0]
    _paged_init(acc_scr, m_scr, l_scr)
    q = q_ref[:]
    offset = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 1)

    def step_body(i, carry):
        slot = (slot0 + i) % 2
        page0 = i * pps

        @pl.when(i + 1 < n_steps)
        def _next_step():
            fetch(b, i + 1, 1 - slot)

        @pl.when((i + 1 == n_steps) & (b + 1 < pl.num_programs(0)))
        def _next_sequence():
            fetch(b + 1, 0, 1 - slot)
            slot_ref[0] = 1 - slot

        for j in range(pps):
            @pl.when(page0 + j < end)
            def _wait():
                copy(b, page0 + j, slot, j).wait()

        rows = c_buf[slot].reshape(cols, W)
        live = page0 * ps + offset < seq_len
        _paged_softmax_step(q, rows, rows[:, :v_width], live, acc_scr, m_scr, l_scr, scale)
        return carry

    jax.lax.fori_loop(0, n_steps, step_body, 0)
    _paged_write(o_ref, acc_scr, l_scr)


def _latent_pages_per_step(H: int, q_itemsize: int, pool, v_width: int) -> int:
    from ..analysis import budget as _budget

    ps, W = pool.shape[1], pool.shape[2]
    item = jnp.dtype(str(pool.dtype).rpartition(".")[2]).itemsize
    return _budget.latent_pages_per_step(ps, W, v_width, H, item, q_itemsize)


def paged_latent_decode(q, pool, page_table, seq_lens, scale, v_width: int,
                        *, interpret: bool | None = None):
    """q (B, H, W) against a paged latent pool (P, page_size, W) through
    page_table (B, n_pages_max) int32 / seq_lens (B,) int32 (valid rows,
    the current token's included) -> (B, H, v_width): every head scores each
    row over its W columns and takes its first ``v_width`` as the value."""
    B, H, W = q.shape
    ps = pool.shape[1]
    interpret = _interpret() if interpret is None else interpret
    pps = _latent_pages_per_step(H, q.dtype.itemsize, pool, v_width)
    if not interpret and (W % 128 or v_width % 128 or not pps):
        raise ValueError(f"paged_latent_decode cannot take rows {tuple(pool.shape)} with values "
                         f"{v_width} wide: " + _PAGED_REFUSALS["lanes" if W % 128 or v_width % 128 else "vmem"])
    pps = max(pps, 1)  # the interpreter has no VMEM to run out of
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, H, W), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, H, v_width), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, pps, ps, W), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((H, v_width), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_latent_attn_kernel, scale=scale, pages_per_step=pps, v_width=v_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, v_width), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32), q, pool)


def paged_latent_attention_supported(q, pool, page_table, q_pos, scale, v_width) -> bool:
    """Checker: the latent decode kernel claims thunder.paged_latent_attention
    on the chip for ONE query a sequence (the decode step; a chunk's and the
    verify step's queries run the gather decomposition), rows that fill the
    128 lanes, values a whole number of lane groups, and at least one page a
    loop step inside the VMEM budget."""
    if not _claims_on_platform():
        return False
    if getattr(q, "ndim", 0) != 4 or getattr(pool, "ndim", 0) != 3 or q.shape[2] != 1:
        return False
    B, H, _, W = q.shape
    if not (pool.shape[2] == W and pool.shape[1] % 8 == 0 and 0 < v_width <= W <= 1024
            and getattr(page_table, "ndim", 0) == 2 and page_table.shape[0] == B
            and tuple(q_pos.shape) == (B, 1)):
        return False
    if _on_tpu() and (W % 128 or v_width % 128):
        return _decline("paged_latent_attention", "lanes")
    q_item = jnp.dtype(str(q.dtype).rpartition(".")[2]).itemsize
    if not _latent_pages_per_step(H, q_item, pool, v_width):
        return _decline("paged_latent_attention", "vmem")
    return True


def _paged_latent_attention_impl(q, pool, page_table, q_pos, scale, v_width):
    B, H, _, W = q.shape
    out = paged_latent_decode(q.reshape(B, H, W), pool, page_table, q_pos[:, 0] + 1, scale, v_width)
    return out.reshape(B, H, 1, v_width)


ex.register_implementation("thunder.paged_latent_attention", _paged_latent_attention_impl,
                           checker=paged_latent_attention_supported)


# ===========================================================================
# Grouped-expert MLP (MoE capacity-routed dispatch)
# ===========================================================================
#
# Tokens are packed into per-expert capacity bins (E, cap, D) by the routing
# scatter; the grid runs (expert, bin-block) so each expert's MXU matmuls
# touch ONLY its own bin — the dense one-hot einsum road multiplies every
# token through every expert (O(E*cap*D*H) regardless of routing). Bin rows
# at/after group_sizes[e] are zero-filled padding: wholly-padding blocks are
# skipped (zero write, no MXU work), partially-padding blocks compute them
# anyway — SwiGLU(0) = 0 exactly, so both roads agree bitwise on padding.

_GROUPED_BLOCK_C = 128


def _grouped_mlp_kernel(gs_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, *, block_c: int):
    # grid (E, cap // block_c); x_ref (block_c, D) — one bin block of expert
    # e; wg/wu (D, H), wd (H, D) — expert e's panels; gs_ref (E,) prefetched
    e = pl.program_id(0)
    c = pl.program_id(1)
    live = c * block_c < gs_ref[e]

    @pl.when(jnp.logical_not(live))
    def _pad():
        o_ref[:] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _compute():
        x = x_ref[:]
        wd = wd_ref[:]
        # fused SwiGLU in one VMEM pass: f32 accumulation for the dots,
        # silu on the VPU, single down-projection write
        g = jax.lax.dot_general(x, wg_ref[:], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, wu_ref[:], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        h = (g * (1.0 / (1.0 + jnp.exp(-g)))) * u
        o_ref[:] = jax.lax.dot_general(h.astype(wd.dtype), wd,
                                       (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32).astype(o_ref.dtype)


def grouped_mlp_fused(bins, w_gate, w_up, w_down, group_sizes, *,
                      block_c: int | None = None, interpret: bool | None = None):
    """bins (E, cap, D) x per-expert panels (E, D, H)/(E, H, D) with
    group_sizes (E,) int32 -> (E, cap, D). Rows past group_sizes[e] must be
    zero-filled (the dispatch scatter's contract); whole padding blocks skip
    the MXU entirely."""
    E, cap, D = bins.shape
    H = w_gate.shape[-1]
    if block_c is None:
        block_c = math.gcd(cap, _GROUPED_BLOCK_C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E, cap // block_c),
        in_specs=[
            pl.BlockSpec((None, block_c, D), lambda e, c, gs: (e, c, 0)),
            pl.BlockSpec((None, D, H), lambda e, c, gs: (e, 0, 0)),
            pl.BlockSpec((None, D, H), lambda e, c, gs: (e, 0, 0)),
            pl.BlockSpec((None, H, D), lambda e, c, gs: (e, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_c, D), lambda e, c, gs: (e, c, 0)),
    )
    return pl.pallas_call(
        functools.partial(_grouped_mlp_kernel, block_c=block_c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, cap, D), bins.dtype),
        interpret=_interpret() if interpret is None else interpret,
    )(group_sizes.astype(jnp.int32), bins, w_gate, w_up, w_down)


def grouped_mlp_supported(bins, w_gate, w_up, w_down, group_sizes) -> bool:
    """Checker: the grouped kernel claims thunder.grouped_mlp on the chip
    (`_claims_on_platform`); the per-program working set — one expert's three
    weight panels plus a bin block and its f32 SwiGLU intermediates — must
    fit the VMEM budget, otherwise the batched-matmul decomposition runs (the
    ADVICE fallback pattern, unified via analysis/memory.py)."""
    if not _claims_on_platform():
        return False
    if getattr(bins, "ndim", 0) != 3 or getattr(w_gate, "ndim", 0) != 3:
        return False
    E, cap, D = bins.shape
    H = w_gate.shape[-1]
    shapes_ok = (
        tuple(w_gate.shape) == (E, D, H)
        and tuple(w_up.shape) == (E, D, H)
        and tuple(w_down.shape) == (E, H, D)
        and getattr(group_sizes, "ndim", 0) == 1 and group_sizes.shape[0] == E
        and cap % 8 == 0  # sublane tile
        and D <= 4096 and H <= 16384
    )
    if not shapes_ok:
        return False
    from ..analysis import budget as _budget

    block_c = math.gcd(cap, _GROUPED_BLOCK_C)
    w_item = jnp.dtype(str(w_gate.dtype).rpartition(".")[2]).itemsize
    x_item = jnp.dtype(str(bins.dtype).rpartition(".")[2]).itemsize
    if not _budget.within_vmem(
            _budget.grouped_mlp_vmem_bytes(block_c, D, H, w_item, x_item)):
        return _decline("grouped_mlp", "vmem")
    return True


_grouped_mlp_claimed = _jit_claimed(
    lambda bins, w_gate, w_up, w_down, group_sizes: grouped_mlp_fused(
        bins, w_gate, w_up, w_down, group_sizes),
    (), lambda *a: (a, {}))


ex.register_implementation("thunder.grouped_mlp", _grouped_mlp_claimed,
                           checker=grouped_mlp_supported)


def _register_grouped_mlp_grad_rule():
    """Executor-claimed grad for thunder.grouped_mlp: the fused kernel runs
    the forward; the backward is the straight SwiGLU chain rule over the
    SAME capacity bins (padding rows are zero, so their contributions to
    every weight grad vanish identically). Falls through to the composite
    decomposition when the kernel can't claim the shapes."""
    from ..transforms.autodiff import VJPResult, register_augmented_forward, register_backward

    def fwd_meta(bins, w_gate, w_up, w_down, group_sizes):
        return TensorProxy(shape=bins.shape, dtype=bins.dtype, device=bins.device)

    fwd_sym = Symbol("grouped_mlp_fwd", fwd_meta, id="pallas.grouped_mlp_fwd",
                     is_prim=True, module="pallas", executor=ex)
    ex.opmap[fwd_sym.id] = lambda bins, w_gate, w_up, w_down, group_sizes: (
        grouped_mlp_fused(bins, w_gate, w_up, w_down, group_sizes))

    def bwd_meta(bins, w_gate, w_up, w_down, group_sizes, do):
        return (TensorProxy(shape=bins.shape, dtype=bins.dtype, device=bins.device),
                TensorProxy(shape=w_gate.shape, dtype=w_gate.dtype, device=w_gate.device),
                TensorProxy(shape=w_up.shape, dtype=w_up.dtype, device=w_up.device),
                TensorProxy(shape=w_down.shape, dtype=w_down.dtype, device=w_down.device))

    def bwd_impl(bins, w_gate, w_up, w_down, group_sizes, do):
        g = jnp.einsum("ecd,edh->ech", bins, w_gate)
        u = jnp.einsum("ecd,edh->ech", bins, w_up)
        sg = jax.nn.sigmoid(g)
        h = g * sg * u
        dh = jnp.einsum("ecd,ehd->ech", do, w_down)
        dwd = jnp.einsum("ech,ecd->ehd", h, do)
        du = dh * (g * sg)
        dg = dh * u * (sg * (1.0 + g * (1.0 - sg)))
        dbins = (jnp.einsum("ech,edh->ecd", dg, w_gate)
                 + jnp.einsum("ech,edh->ecd", du, w_up))
        dwg = jnp.einsum("ecd,ech->edh", bins, dg)
        dwu = jnp.einsum("ecd,ech->edh", bins, du)
        return (dbins.astype(bins.dtype), dwg.astype(w_gate.dtype),
                dwu.astype(w_up.dtype), dwd.astype(w_down.dtype))

    bwd_sym = Symbol("grouped_mlp_bwd", bwd_meta, id="pallas.grouped_mlp_bwd",
                     is_prim=True, module="pallas", executor=ex)
    ex.opmap[bwd_sym.id] = bwd_impl

    @register_augmented_forward("thunder.grouped_mlp")
    def _grouped_mlp_aug(bins, w_gate, w_up, w_down, group_sizes):
        if not grouped_mlp_supported(bins, w_gate, w_up, w_down, group_sizes):
            return NotImplemented  # decompose: batched-matmul grad rules apply
        out = fwd_sym(bins, w_gate, w_up, w_down, group_sizes)
        return VJPResult(out, (bins, w_gate, w_up, w_down, group_sizes))

    @register_backward("thunder.grouped_mlp")
    def _grouped_mlp_bwd(bins, w_gate, w_up, w_down, group_sizes, do):
        dbins, dwg, dwu, dwd = bwd_sym(bins, w_gate, w_up, w_down, group_sizes, do)
        return dbins, dwg, dwu, dwd, None


_register_grouped_mlp_grad_rule()


# ---------------------------------------------------------------------------
# Ragged expert MLP (drop-free dispatch: rows sorted by expert)
# ---------------------------------------------------------------------------
#
# ops/ltorch.py ragged_mlp: the rows of each expert lie together from a
# tile-aligned offset, so a tile of rows is ONE expert's. The grid runs (row
# tile, hidden block), hidden blocks innermost: a program multiplies its tile
# by a (D, block_h) slab of that expert's gate and up panels and a (block_h, D)
# slab of its down panel and adds into an f32 accumulator, so the working set
# is a few tiles however wide the expert (the capacity-bin kernel above keeps
# three whole panels twice). Which expert a tile belongs to rides as a
# scalar-prefetch operand, so the weight index maps resolve it before each
# DMA; the tiles past the last group point at the block that was fetched last
# and compute nothing, so an expert with no rows costs no weight read.


def _ragged_mlp_kernel(te_ref, nl_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref):
    # grid (R // tile, H // block_h). te_ref (tiles,) expert of each row tile,
    # nl_ref (1,) the tiles that hold rows; x_ref (tile, D); wg/wu (D, block_h),
    # wd (block_h, D) of the tile's expert; acc_ref (tile, D) f32
    i, j = pl.program_id(0), pl.program_id(1)
    live = i < nl_ref[0]

    @pl.when(live & (j == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _compute():
        x = x_ref[:]
        wd = wd_ref[:]
        g = jax.lax.dot_general(x, wg_ref[:], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, wu_ref[:], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        h = (g * (1.0 / (1.0 + jnp.exp(-g)))) * u
        acc_ref[:] += jax.lax.dot_general(h.astype(wd.dtype), wd, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _write():
        o_ref[:] = jnp.where(live, acc_ref[:], 0.0).astype(o_ref.dtype)


def _ragged_block_h(tile: int, D: int, H: int, w_dtype, x_dtype) -> int:
    from ..analysis import budget as _budget

    w_item = jnp.dtype(str(w_dtype).rpartition(".")[2]).itemsize
    x_item = jnp.dtype(str(x_dtype).rpartition(".")[2]).itemsize
    return _budget.ragged_mlp_block_h(tile, D, H, w_item, x_item)


def ragged_mlp_fused(rows, w_gate, w_up, w_down, group_sizes, tile: int, *,
                     block_h: int | None = None, interpret: bool | None = None):
    """rows (R, D), sorted by expert in tile-aligned ragged groups, through
    their experts' panels (E, D, H)/(E, H, D) -> (R, D); rows outside every
    group come back zero (ops/ltorch.py ragged_mlp has the layout)."""
    from ..analysis import budget as _budget

    R, D = rows.shape
    E, _, H = w_gate.shape
    if block_h is None:
        block_h = _ragged_block_h(tile, D, H, w_gate.dtype, rows.dtype) or math.gcd(H, 128)
    n_tiles, nj = R // tile, H // block_h
    tiles_of = (group_sizes.astype(jnp.int32) + (tile - 1)) // tile
    ends = jnp.cumsum(tiles_of)
    n_live = ends[-1:]
    tile_expert = jnp.minimum(jnp.searchsorted(ends, jnp.arange(n_tiles, dtype=jnp.int32),
                                               side="right"), E - 1).astype(jnp.int32)

    def row_tile(i, nl):     # a tile past the last group: the block that is there
        return jnp.maximum(jnp.minimum(i, nl[0] - 1), 0)

    def hidden(i, j, nl):
        return jnp.where(i < nl[0], j, nj - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, nj),
        in_specs=[
            pl.BlockSpec((tile, D), lambda i, j, te, nl: (row_tile(i, nl), 0)),
            pl.BlockSpec((None, D, block_h), lambda i, j, te, nl: (te[row_tile(i, nl)], 0, hidden(i, j, nl))),
            pl.BlockSpec((None, D, block_h), lambda i, j, te, nl: (te[row_tile(i, nl)], 0, hidden(i, j, nl))),
            pl.BlockSpec((None, block_h, D), lambda i, j, te, nl: (te[row_tile(i, nl)], hidden(i, j, nl), 0)),
        ],
        out_specs=pl.BlockSpec((tile, D), lambda i, j, te, nl: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tile, D), jnp.float32)],
    )
    return pl.pallas_call(
        _ragged_mlp_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_budget.RAGGED_MLP_VMEM_LIMIT),
        interpret=_interpret() if interpret is None else interpret,
    )(tile_expert, n_live, rows, w_gate, w_up, w_down)


def ragged_mlp_supported(rows, w_gate, w_up, w_down, group_sizes, tile) -> bool:
    """Checker: the ragged kernel claims thunder.ragged_mlp on the chip
    (`_claims_on_platform`) where rows and panels belong together, the tile is
    a whole number of sublane tiles and D and H of lane groups, and a weight
    tile over some block of the hidden dimension fits the kernel's VMEM limit
    (analysis/memory.py ragged_mlp_block_h); otherwise the decomposition runs."""
    if not _claims_on_platform():
        return False
    if getattr(rows, "ndim", 0) != 2 or getattr(w_gate, "ndim", 0) != 3:
        return False
    R, D = rows.shape
    E, _, H = w_gate.shape
    if not (tuple(w_gate.shape) == (E, D, H) and tuple(w_up.shape) == (E, D, H)
            and tuple(w_down.shape) == (E, H, D)
            and getattr(group_sizes, "ndim", 0) == 1 and group_sizes.shape[0] == E
            and tile % 8 == 0 and R % tile == 0):
        return False
    if _on_tpu() and (D % 128 or H % 128 or tile % 16):
        return _decline("ragged_mlp", "lanes")
    if not _ragged_block_h(tile, D, H, w_gate.dtype, rows.dtype):
        return _decline("ragged_mlp", "vmem")
    return True


_ragged_mlp_claimed = _jit_claimed(
    lambda rows, w_gate, w_up, w_down, group_sizes, tile: ragged_mlp_fused(
        rows, w_gate, w_up, w_down, group_sizes, tile),
    ("tile",), lambda rows, w_gate, w_up, w_down, group_sizes, tile: (
        (rows, w_gate, w_up, w_down, group_sizes), {"tile": tile}))


ex.register_implementation("thunder.ragged_mlp", _ragged_mlp_claimed,
                           checker=ragged_mlp_supported)


# ===========================================================================
# Streaming ring-flash attention (context parallelism)
# ===========================================================================
#
# One ring step = one pallas_call: the ppermute'd K/V shard (T_blk rows, the
# per-device block — not the global sequence) is consumed by the flash
# online-softmax body with the (o, m, l) accumulators carried in HBM between
# steps, so the VMEM working set is O(block) however long the global context
# grows. GQA is native — the k/v BlockSpecs index kv head = q head // group,
# never materializing replicated heads. The causal mask uses GLOBAL
# positions (q_off/k_off ride as scalar prefetch): each device's q shard
# starts at my*T, each arriving k shard at src*T.
#
# Step-order contract: the jax-level ring MUST process src == my first (the
# diagonal block). Its first k sub-block gives every causal row at least one
# valid key, making the carried running max finite before any later
# fully-masked tile — NEG_INF is a finite sentinel, so a fully-masked tile
# against a still-NEG_INF max would contribute exp2(0) garbage.


def _ring_flash_step_kernel(off_ref, q_ref, k_ref, v_ref, oi_ref, mi_ref, li_ref,
                            oo_ref, mo_ref, lo_ref, *, block_k: int, causal: bool,
                            scale: float):
    # grid (B, H, T // block_q); q_ref (block_q, D); k_ref/v_ref (T_blk, D)
    # — this ring step's shard; (oi, mi, li) carried accumulators in, the
    # updated (oo, mo, lo) out. m/l ride in log2 units (flash convention).
    block_q, D = q_ref.shape
    Tk = k_ref.shape[0]
    qi = pl.program_id(2)
    q = q_ref[:]
    q_off = off_ref[0]
    k_off = off_ref[1]
    scale2 = scale * LOG2E
    q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, carry):
        o_acc, m, l = carry
        k_blk = k_ref[pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale2
        if causal:
            k_pos = k_off + j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp2(s - m_new[:, None])
        corr = jnp.exp2(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        o_new = o_acc * corr[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    n_k = Tk // block_k
    if causal:
        # global causal skip: k sub-blocks starting past this q block's last
        # position contribute nothing (a whole future shard skips entirely)
        lim = (q_off - k_off) + (qi + 1) * block_q
        n_k = jnp.clip((lim + block_k - 1) // block_k, 0, n_k)
    o, m, l = jax.lax.fori_loop(
        0, n_k, body, (oi_ref[:], mi_ref[:][:, 0], li_ref[:][:, 0]))
    oo_ref[:] = o
    mo_ref[:] = m[:, None]
    lo_ref[:] = l[:, None]


def ring_flash_step(q, kb, vb, o, m, l, q_off, k_off, *, causal: bool,
                    scale: float, block_q: int, block_k: int,
                    interpret: bool | None = None):
    """One ring step: fold the arriving K/V shard kb/vb (B, Hkv, T_blk, D)
    into the carried accumulators o (B, H, T, D) f32 / m, l (B, H, T, 1)
    f32 for local queries q (B, H, T, D). q_off/k_off are the shards'
    global sequence offsets (traced: my*T and src*T)."""
    B, H, T, D = q.shape
    Tk = kb.shape[2]
    g = H // kb.shape[1]
    offs = jnp.stack([jnp.asarray(q_off, jnp.int32), jnp.asarray(k_off, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, T // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, D), lambda b, h, i, off: (b, h, i, 0)),
            pl.BlockSpec((None, None, Tk, D), lambda b, h, i, off: (b, h // g, 0, 0)),
            pl.BlockSpec((None, None, Tk, D), lambda b, h, i, off: (b, h // g, 0, 0)),
            pl.BlockSpec((None, None, block_q, D), lambda b, h, i, off: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_q, 1), lambda b, h, i, off: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_q, 1), lambda b, h, i, off: (b, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, D), lambda b, h, i, off: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_q, 1), lambda b, h, i, off: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_q, 1), lambda b, h, i, off: (b, h, i, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_ring_flash_step_kernel, block_k=block_k,
                          causal=causal, scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        interpret=_interpret() if interpret is None else interpret,
    )(offs, q, kb, vb, o, m, l)


def _ring_flash_bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, dq_ref, *, block_k: int, causal: bool,
                              scale: float):
    # the flash dq recompute (p from the saved lse, blockwise) with GLOBAL causal
    # positions; lse is the GLOBAL log-sum-exp (all ring steps), so p for
    # this shard's keys is exact and dq contributions just add across steps
    block_q, D = q_ref.shape
    Tk = k_ref.shape[0]
    qi = pl.program_id(2)
    q = q_ref[:]
    do = do_ref[:]
    lse2 = lse_ref[:][:, 0] * LOG2E
    delta = delta_ref[:][:, 0]
    q_off = off_ref[0]
    k_off = off_ref[1]
    q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, dq_acc):
        k_blk = k_ref[pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * (scale * LOG2E)
        if causal:
            k_pos = k_off + j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp2(s - lse2[:, None])
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq_acc + jax.lax.dot_general(ds.astype(k_blk.dtype), k_blk,
                                            (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

    n_k = Tk // block_k
    if causal:
        lim = (q_off - k_off) + (qi + 1) * block_q
        n_k = jnp.clip((lim + block_k - 1) // block_k, 0, n_k)
    dq = jax.lax.fori_loop(0, n_k, body, jnp.zeros((block_q, D), jnp.float32))
    dq_ref[:] = dq


def _dkv_tile(k_blk, v_blk, q, do, lse2, delta, k_pos_t, q_pos_t, causal,
              scale, dk_acc, dv_acc):
    """One (k-block x q-tile) contribution to dk/dv, transposed orientation
    (rows = k positions) in log2 units."""
    s_t = jax.lax.dot_general(k_blk, q, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32) * (scale * LOG2E)  # (bk, bq)
    if causal:
        s_t = jnp.where(k_pos_t <= q_pos_t, s_t, NEG_INF)
    p_t = jnp.exp2(s_t - lse2[None, :])
    dv_acc = dv_acc + jax.lax.dot_general(p_t.astype(do.dtype), do,
                                          (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
    dp_t = jax.lax.dot_general(v_blk, do, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (bk, bq)
    ds_t = (p_t * (dp_t - delta[None, :]) * scale).astype(q.dtype)
    dk_acc = dk_acc + jax.lax.dot_general(ds_t, q, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
    return dk_acc, dv_acc


def _ring_flash_bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                               delta_ref, dk_ref, dv_ref, *, block_q: int,
                               causal: bool, scale: float):
    # transposed orientation (rows = k positions) per-q-head partials,
    # group-summed outside (the flash GQA backward convention here); global
    # positions via the off prefetch
    block_k, D = k_ref.shape
    Tq = q_ref.shape[0]
    ki = pl.program_id(2)
    k_blk = k_ref[:]
    v_blk = v_ref[:]
    q_off = off_ref[0]
    k_off = off_ref[1]
    k_pos_t = k_off + ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)

    def body(i, carry):
        q = q_ref[pl.ds(i * block_q, block_q), :]
        do = do_ref[pl.ds(i * block_q, block_q), :]
        lse2 = lse_ref[pl.ds(i * block_q, block_q), :][:, 0] * LOG2E
        delta = delta_ref[pl.ds(i * block_q, block_q), :][:, 0]
        q_pos_t = q_off + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        return _dkv_tile(k_blk, v_blk, q, do, lse2, delta, k_pos_t, q_pos_t,
                         causal, scale, *carry)

    z = jnp.zeros((block_k, D), jnp.float32)
    n_i = Tq // block_q
    if causal:
        # first q tile whose last position reaches this k block
        i0 = jnp.clip((k_off + ki * block_k - q_off) // block_q, 0, n_i)
    else:
        i0 = 0
    dk, dv = jax.lax.fori_loop(i0, n_i, body, (z, z))
    dk_ref[:] = dk
    dv_ref[:] = dv


def ring_flash_bwd_step(q, kb, vb, do, lse, delta, q_off, k_off, *, causal: bool,
                        scale: float, block_q: int, block_k: int,
                        interpret: bool | None = None):
    """One backward ring step: local queries against the arriving shard.
    Returns (dq_contrib (B, H, T, D) f32, dk_contrib/dv_contrib
    (B, Hkv, T_blk, D) f32) — the kv grads are per-q-head partials
    group-summed here before the accumulators ride the ring onward."""
    B, H, T, D = q.shape
    Hkv, Tk = kb.shape[1], kb.shape[2]
    g = H // Hkv
    itp = _interpret() if interpret is None else interpret
    offs = jnp.stack([jnp.asarray(q_off, jnp.int32), jnp.asarray(k_off, jnp.int32)])
    dq = pl.pallas_call(
        functools.partial(_ring_flash_bwd_dq_kernel, block_k=block_k,
                          causal=causal, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, T // block_q),
            in_specs=[
                pl.BlockSpec((None, None, block_q, D), lambda b, h, i, off: (b, h, i, 0)),
                pl.BlockSpec((None, None, Tk, D), lambda b, h, i, off: (b, h // g, 0, 0)),
                pl.BlockSpec((None, None, Tk, D), lambda b, h, i, off: (b, h // g, 0, 0)),
                pl.BlockSpec((None, None, block_q, D), lambda b, h, i, off: (b, h, i, 0)),
                pl.BlockSpec((None, None, block_q, 1), lambda b, h, i, off: (b, h, i, 0)),
                pl.BlockSpec((None, None, block_q, 1), lambda b, h, i, off: (b, h, i, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, block_q, D),
                                   lambda b, h, i, off: (b, h, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), jnp.float32),
        interpret=itp,
    )(offs, q, kb, vb, do, lse, delta)

    dk_p, dv_p = pl.pallas_call(
        functools.partial(_ring_flash_bwd_dkv_kernel, block_q=block_q,
                          causal=causal, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, Tk // block_k),
            in_specs=[
                pl.BlockSpec((None, None, T, D), lambda b, h, j, off: (b, h, 0, 0)),
                pl.BlockSpec((None, None, block_k, D), lambda b, h, j, off: (b, h // g, j, 0)),
                pl.BlockSpec((None, None, block_k, D), lambda b, h, j, off: (b, h // g, j, 0)),
                pl.BlockSpec((None, None, T, D), lambda b, h, j, off: (b, h, 0, 0)),
                pl.BlockSpec((None, None, T, 1), lambda b, h, j, off: (b, h, 0, 0)),
                pl.BlockSpec((None, None, T, 1), lambda b, h, j, off: (b, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, block_k, D), lambda b, h, j, off: (b, h, j, 0)),
                pl.BlockSpec((None, None, block_k, D), lambda b, h, j, off: (b, h, j, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Tk, D), jnp.float32),
        ],
        interpret=itp,
    )(offs, q, kb, vb, do, lse, delta)
    dk = dk_p.reshape(B, Hkv, g, Tk, D).sum(axis=2)
    dv = dv_p.reshape(B, Hkv, g, Tk, D).sum(axis=2)
    return dq, dk, dv


def ring_flash_supported(q, k, v) -> bool:
    """Checker for the streaming ring path inside dist.ring_attention: the
    chip (`_claims_on_platform`), equal-size shards on the flash tiling, and
    one step's working set — q block + this shard's K/V + the f32 carries —
    within the VMEM budget via the unified analysis/memory.py estimate;
    otherwise the pure-jax GQA-native reference ring runs."""
    if not _claims_on_platform():
        return False
    if getattr(q, "ndim", 0) != 4 or getattr(k, "ndim", 0) != 4:
        return False
    B, H, T, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    block_q = min(DEFAULT_BLOCK_Q, T)
    block_k = min(DEFAULT_BLOCK_K, Tk)
    block_q, block_k = _cap_blocks_for_dtype(q, block_q, block_k, T, Tk, k, v)
    shapes_ok = (
        D <= 512
        and tuple(v.shape) == tuple(k.shape)
        and k.shape[0] == B
        and T == Tk  # equal shards: every device holds T/n rows
        and H % Hkv == 0
        and T % block_q == 0 and Tk % block_k == 0
        and T % 8 == 0
    )
    if not shapes_ok:
        return False
    from ..analysis import budget as _budget

    q_item = jnp.dtype(str(q.dtype).rpartition(".")[2]).itemsize
    kv_item = jnp.dtype(str(k.dtype).rpartition(".")[2]).itemsize
    if not _budget.within_vmem(
            _budget.ring_flash_vmem_bytes(block_q, Tk, D, q_item, kv_item)):
        return _decline("ring_flash", "vmem")
    return True


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, block_q, block_k,
                         interpret):
    B, H, T, D = q.shape
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    o0 = jnp.zeros((B, H, T, D), jnp.float32)
    m0 = jnp.full((B, H, T, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, T, 1), jnp.float32)

    def step(carry, i):
        o, m, l, kb, vb = carry
        src = jax.lax.rem(my - i + n, n)  # device that produced this shard
        o, m, l = ring_flash_step(q, kb, vb, o, m, l, my * T, src * T,
                                  causal=causal, scale=scale, block_q=block_q,
                                  block_k=block_k, interpret=interpret)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (o, m, l, kb, vb), None

    # i=0 is src == my: the diagonal step that seeds finite running maxima
    # (see the step-order contract above); after n permutes k/v are home
    # again, which is what lets the backward reuse the SAME residency
    (o, m, l, _, _), _ = jax.lax.scan(step, (o0, m0, l0, k, v), jnp.arange(n))
    l1 = l[..., 0]
    l_safe = jnp.where(l1 == 0.0, 1.0, l1)
    out = (o / l_safe[..., None]).astype(q.dtype)
    lse = (m[..., 0] + jnp.log2(l_safe)) * LN2  # (B, H, T), natural log
    return out, lse


def _ring_flash_bwd_impl(q, k, v, out, lse, do, axis_name, causal, scale,
                         block_q, block_k, interpret):
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)  # (B, H, T, 1)
    lse1 = lse[..., None].astype(jnp.float32)
    dq0 = jnp.zeros((B, H, T, D), jnp.float32)
    dkv0 = jnp.zeros((B, Hkv, T, D), jnp.float32)

    def step(carry, i):
        dq, kb, vb, dkb, dvb = carry
        src = jax.lax.rem(my - i + n, n)
        dq_c, dk_c, dv_c = ring_flash_bwd_step(
            q, kb, vb, do, lse1, delta, my * T, src * T, causal=causal,
            scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
        dq = dq + dq_c
        # the kv-grad accumulators travel WITH their shard: after n permutes
        # both are home with every device's contribution folded in
        dkb = dkb + dk_c
        dvb = dvb + dv_c
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        dkb = jax.lax.ppermute(dkb, axis_name, perm)
        dvb = jax.lax.ppermute(dvb, axis_name, perm)
        return (dq, kb, vb, dkb, dvb), None

    (dq, _, _, dk, dv), _ = jax.lax.scan(
        step, (dq0, k, v, dkv0, dkv0), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.lru_cache(maxsize=None)
def _ring_flash_vjp(axis_name, causal, scale, block_q, block_k, interpret):
    @jax.custom_vjp
    def f(q, k, v):
        out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                      block_q, block_k, interpret)
        return out

    def fwd(q, k, v):
        out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                        block_q, block_k, interpret)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        return _ring_flash_bwd_impl(q, k, v, out, lse, do, axis_name, causal,
                                    scale, block_q, block_k, interpret)

    f.defvjp(fwd, bwd)
    return f


def ring_flash_attention(q, k, v, *, axis_name: str, causal: bool = True,
                         scale=None, interpret: bool | None = None):
    """Streaming ring attention over the named mesh axis: q (B, H, T, D)
    local shard, k/v (B, Hkv, T, D) — GQA-native. Differentiable (custom
    VJP rides the flash backward recompute around the same ring), so
    jax.vjp — and thus the executor's JAX_VJP_FALLBACK — works through it."""
    B, H, T, D = q.shape
    Tk = k.shape[2]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(DEFAULT_BLOCK_Q, T)
    block_k = min(DEFAULT_BLOCK_K, Tk)
    block_q, block_k = _cap_blocks_for_dtype(q, block_q, block_k, T, Tk, k, v)
    itp = _interpret() if interpret is None else bool(interpret)
    return _ring_flash_vjp(str(axis_name), bool(causal), scale,
                           int(block_q), int(block_k), itp)(q, k, v)
