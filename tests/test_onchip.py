"""On-chip (real TPU) tests — run with TT_ONCHIP=1 on a machine with a chip:

    TT_ONCHIP=1 python -m pytest tests/test_onchip.py -q

Validates what the CPU suite cannot: every Pallas kernel family lowers
through Mosaic (non-interpret) and agrees with a pure-jax reference at
published widths, and the flash-attention fwd AND bwd kernels are claimed
inside TrainStep's program on hardware (VERDICT round-1 weak #4). Without
TT_ONCHIP=1 the module is skipped; with it and no TPU it fails."""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ONCHIP = os.environ.get("TT_ONCHIP") == "1"
if _ONCHIP and jax.devices()[0].platform != "tpu":
    raise RuntimeError(
        f"TT_ONCHIP=1 asks for the on-chip tests but jax found platform "
        f"{jax.devices()[0].platform!r}")

pytestmark = pytest.mark.skipif(not _ONCHIP, reason="on-chip tests need TT_ONCHIP=1 and a TPU")


def _attention_ref(q, k, v, mask):
    """float32 softmax attention, q (..., Tq, D), k/v (..., Tk, D), boolean
    mask (..., Tq, Tk) of the keys each query may see."""
    q, k, v = (jnp.asarray(t, jnp.float32) for t in (q, k, v))
    s = jnp.einsum("...qd,...kd->...qk", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v)


def _paged_case(rng, B, H, Hkv, D, ps, npm, Dv=None):
    """A head-major pool, ragged page tables and the dense (B, Hkv, S, D)
    keys and (B, Hkv, S, Dv) values they address."""
    P = 1 + B * npm
    k_pages = jnp.asarray(rng.randn(P, Hkv, ps, D), jnp.bfloat16)
    v_pages = jnp.asarray(rng.randn(P, Hkv, ps, Dv or D), jnp.bfloat16)
    pt = 1 + rng.permutation(B * npm).reshape(B, npm).astype(np.int32)
    dense = lambda pages: jnp.asarray(  # noqa: E731
        np.asarray(pages, np.float32)[pt].transpose(0, 2, 1, 3, 4).reshape(
            B, Hkv, npm * ps, pages.shape[3]))
    return k_pages, v_pages, jnp.asarray(pt), dense(k_pages), dense(v_pages)


def _as_served(q, k_pages, v_pages):
    """Heads narrower than the 128 lanes as the engine caches them
    (serving/runner.py: heads_a_row): ``r`` neighbouring KV heads side by side
    a pool row, each query over the lanes of its own key head and zeros over
    the others'. q is (B, H, D), or (B, H, T, D) with T queries a sequence.
    Returns q, the pools and what takes a query head's own value lanes out of
    the output; at a head of 128 all four are what they were."""
    from thunder_tpu.serving.runner import heads_a_row

    (B, H, *mid, D), (P, Hkv, ps, _) = q.shape, k_pages.shape
    r, g = heads_a_row(Hkv, D), H // Hkv
    one = (None,) * len(mid)

    def pack(pages):
        rows = pages.reshape(P, Hkv // r, r, ps, -1).transpose(0, 1, 3, 2, 4)
        return rows.reshape(P, Hkv // r, ps, -1)

    def own(out):
        out = out.reshape(B, Hkv // r, r, g, *mid, r, -1)
        return jnp.stack([out[:, :, j, ..., j, :] for j in range(r)], 2).reshape(B, H, *mid, -1)

    spread = (q.reshape(B, Hkv // r, r, g, *mid, 1, D)
              * jnp.eye(r, dtype=q.dtype)[(slice(None), None) + one + (slice(None), None)])
    return spread.reshape(B, H, *mid, r * D), pack(k_pages), pack(v_pages), own


@pytest.mark.parametrize("window", [None, 512], ids=["plain", "window"])
@pytest.mark.parametrize("H,Hkv,D,Dv,ps", [(16, 16, 64, 64, 64), (16, 16, 64, 64, 16),
                                           (32, 8, 128, 128, 16), (32, 8, 64, 64, 64),
                                           (16, 16, 128, 128, 64), (16, 16, 128, 128, 16),
                                           (32, 8, 128, 128, 64), (40, 10, 128, 256, 64)])
def test_paged_decode_kernel_on_chip(H, Hkv, D, Dv, ps, window):
    """One program a sequence over its live pages: lengths from an idle slot
    (1, on the null page) to the whole table, the table wider than most of
    them, and every page past a sequence or below its window full of
    infinities. Heads of 64 reach the kernel two a row, as the engine caches
    them."""
    from thunder_tpu.executors import pallasex

    rng = np.random.RandomState(0)
    B, npm = 8, 2048 // ps
    k_pages, v_pages, pt, k, v = _paged_case(rng, B, H, Hkv, D, ps, npm, Dv)
    lens = np.concatenate([[1, npm * ps, 9 * ps, 8 * ps + 1], rng.randint(1, npm * ps // 3, (B - 4,))])
    pt = np.array(pt)
    pt[0, 0] = 0  # an idle slot reads the null page's first position and nothing else
    k, v = (dense.at[0, :, 0].set(pages[0, :, 0].astype(jnp.float32))
            for dense, pages in ((k, k_pages), (v, v_pages)))
    dead = np.ones(k_pages.shape[0], bool)
    dead[0] = False
    for b, n in enumerate(lens):
        dead[pt[b, (max(n - window, 0) // ps if window else 0):-(-n // ps)]] = False
    k_pages, v_pages = k_pages.at[dead].set(jnp.inf), v_pages.at[dead].set(jnp.inf)
    seq_lens = jnp.asarray(lens, jnp.int32)
    q = jnp.asarray(rng.randn(B, H, D), jnp.bfloat16)
    *served, own = _as_served(q, k_pages, v_pages)
    served += [jnp.asarray(pt), seq_lens, 1 / math.sqrt(D), window]
    assert pallasex.paged_attention_supported(*served)
    out = own(pallasex.paged_attention_decode(*served))
    g = H // Hkv
    pos = jnp.arange(npm * ps)[None, :]
    mask = pos < seq_lens[:, None]
    if window:
        mask &= pos >= seq_lens[:, None] - window
    ref = _attention_ref(q[:, :, None, :], jnp.repeat(k, g, 1), jnp.repeat(v, g, 1), mask[:, None, None, :])
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref)[:, :, 0],
                               atol=2e-2, rtol=2e-2)


def test_paged_decode_takes_no_pool_narrower_than_the_lanes():
    """Heads the engine cannot pack (three of 64) leave a 64-wide pool: the
    checker declines it, so the gather decomposition runs, and a direct call
    is refused by name and not by the compiler."""
    from thunder_tpu.executors import pallasex

    rng = np.random.RandomState(0)
    k_pages, v_pages, pt, _, _ = _paged_case(rng, 2, 6, 3, 64, 64, 4)
    q, lens = jnp.asarray(rng.randn(2, 6, 64), jnp.bfloat16), jnp.asarray([5, 200], jnp.int32)
    assert _as_served(q, k_pages, v_pages)[1].shape == k_pages.shape
    assert not pallasex.paged_attention_supported(q, k_pages, v_pages, pt, lens)
    with pytest.raises(ValueError, match="128 lanes"):
        pallasex.paged_attention_decode(q, k_pages, v_pages, pt, lens)


_RAGGED = tuple(int(n) for n in np.random.RandomState(1).randint(150, 900, 48))
CHUNK_CASES = {  # H, Hkv, D, Dv, ps, table width, T, each sequence's first position, window
    "longprompt": (32, 8, 128, 128, 64, 128, 512, (0, 2048, 6656), None),
    "longprompt_final_rung": (32, 8, 128, 128, 64, 128, 128, (2048, 6656), None),
    "reasoning_shared_pool": (40, 10, 128, 256, 64, 48, 512, (0, 1024, 2560), None),
    "reasoning_window": (40, 10, 128, 256, 64, 48, 512, (0, 1024, 2560), 512),
    "verify_b48_t5": (32, 8, 128, 128, 64, 32, 5, _RAGGED, None),
    "head_64_as_served": (16, 16, 64, 64, 64, 32, 512, (0, 512, 1024), None),
    "head_64_as_served_verify": (16, 16, 64, 64, 64, 32, 5, (0, 1024), None),
    "pages_of_16": (32, 8, 128, 128, 16, 128, 128, (512, 1024), None),
}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_paged_chunk_kernel_on_chip(case):
    """Chunked prefill (T chunk tokens a sequence) and speculative verify
    (T = k + 1) against the pool, per-query causal coverage, at the shapes of
    the callers: the long-prompt cell's (chunks at 0, 2,048 and 6,656 of a
    table 128 wide), the reasoning model's shared pool (values twice as wide)
    and window layers, a verify step of 48 sequences, heads of 64 two a row as
    the engine caches them. Every page past a sequence's last position or
    below its first query's window is full of infinities."""
    from thunder_tpu.executors import pallasex

    H, Hkv, D, Dv, ps, npm, T, starts, window = CHUNK_CASES[case]
    rng = np.random.RandomState(0)
    B = len(starts)
    k_pages, v_pages, pt, k, v = _paged_case(rng, B, H, Hkv, D, ps, npm, Dv)
    pt = np.asarray(pt)
    dead = np.ones(k_pages.shape[0], bool)
    for b, start in enumerate(starts):
        dead[pt[b, (max(start - window + 1, 0) // ps if window else 0):-(-(start + T) // ps)]] = False
    k_pages, v_pages = k_pages.at[dead].set(jnp.inf), v_pages.at[dead].set(jnp.inf)
    q_pos = jnp.asarray(np.asarray(starts)[:, None] + np.arange(T)[None, :], jnp.int32)
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    *served, own = _as_served(q, k_pages, v_pages)
    served += [jnp.asarray(pt), q_pos, 1 / math.sqrt(D), window]
    assert pallasex.paged_chunk_attention_supported(*served)
    out = own(pallasex.paged_chunk_decode(*served))
    g = H // Hkv
    pos = jnp.arange(npm * ps)[None, None, :]
    mask = pos <= q_pos[:, :, None]
    if window:
        mask &= pos > q_pos[:, :, None] - window
    ref = _attention_ref(q, jnp.repeat(k, g, 1), jnp.repeat(v, g, 1), mask[:, None])
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_paged_chunk_takes_no_pool_narrower_than_the_lanes():
    """As the decode kernel: a 64-wide pool is declined, and a direct call
    refused by name and not by the compiler."""
    from thunder_tpu.executors import pallasex

    rng = np.random.RandomState(0)
    k_pages, v_pages, pt, _, _ = _paged_case(rng, 2, 6, 3, 64, 64, 4)
    q = jnp.asarray(rng.randn(2, 6, 16, 64), jnp.bfloat16)
    q_pos = jnp.asarray(np.asarray([[5], [200]]) + np.arange(16)[None], jnp.int32)
    assert not pallasex.paged_chunk_attention_supported(q, k_pages, v_pages, pt, q_pos)
    with pytest.raises(ValueError, match="128 lanes"):
        pallasex.paged_chunk_decode(q, k_pages, v_pages, pt, q_pos)


def test_grouped_expert_mlp_kernel_on_chip():
    """Grouped SwiGLU expert MLP over capacity bins (OLMoE expert width 1024
    at d=1024) against the einsum reference; padding blocks come back zero."""
    from thunder_tpu.executors import pallasex

    rng = np.random.RandomState(0)
    E, cap, D, H = 8, 256, 1024, 1024
    sizes = np.asarray([256, 130, 0, 1, 128, 255, 64, 200], np.int32)
    bins = rng.randn(E, cap, D).astype(np.float32) * 0.5
    bins[np.arange(cap)[None, :] >= sizes[:, None]] = 0.0  # the dispatch contract
    bins = jnp.asarray(bins, jnp.bfloat16)
    wg, wu = (jnp.asarray(rng.randn(E, D, H) / math.sqrt(D), jnp.bfloat16) for _ in range(2))
    wd = jnp.asarray(rng.randn(E, H, D) / math.sqrt(H), jnp.bfloat16)
    assert pallasex.grouped_mlp_supported(bins, wg, wu, wd, jnp.asarray(sizes))
    out = pallasex.grouped_mlp_fused(bins, wg, wu, wd, jnp.asarray(sizes))
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    gate = jnp.einsum("ecd,edh->ech", f32(bins), f32(wg))
    up = jnp.einsum("ecd,edh->ech", f32(bins), f32(wu))
    ref = jnp.einsum("ech,ehd->ecd", jax.nn.silu(gate) * up, f32(wd))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("tokens_in", [128, 512], ids=["decode-128-slots", "chunk-512"])
def test_ragged_expert_mlp_kernel_on_chip(tokens_in):
    """The ragged expert kernel at the widths and row counts of the cell
    mistral-small-4-119b-ep4-l6.serve-rollouts (32 held experts of 4096 x 2048,
    `tokens_in` tokens of 4 choices each), uneven groups with empty experts
    among them, against each row through its own expert in float32."""
    from thunder_tpu.executors import pallasex
    from thunder_tpu.models.moe import ragged_tile

    rng = np.random.RandomState(0)
    E, D, H = 32, 4096, 2048
    tile = ragged_tile(tokens_in * 4, 128)
    R = -(-tokens_in * 4 // tile) * tile + E * tile
    sizes = rng.multinomial(tokens_in, rng.dirichlet(np.full(E, 0.5))).astype(np.int32)
    sizes[:3] = 0
    padded = -(-sizes // tile) * tile
    starts = np.cumsum(padded) - padded
    rows = np.zeros((R, D), np.float32)
    of = np.full(R, -1)
    for e in range(E):
        rows[starts[e]:starts[e] + sizes[e]] = rng.randn(sizes[e], D) * 0.5
        of[starts[e]:starts[e] + sizes[e]] = e
    rows = jnp.asarray(rows, jnp.bfloat16)
    wg, wu = (jnp.asarray(rng.randn(E, D, H) / math.sqrt(D), jnp.bfloat16) for _ in range(2))
    wd = jnp.asarray(rng.randn(E, H, D) / math.sqrt(H), jnp.bfloat16)
    assert pallasex.ragged_mlp_supported(rows, wg, wu, wd, jnp.asarray(sizes), tile)
    out = np.asarray(pallasex.ragged_mlp_fused(rows, wg, wu, wd, jnp.asarray(sizes), tile), np.float32)
    assert np.abs(out[of < 0]).max() == 0.0           # padding rows and the tiles past the last group
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        for e in np.flatnonzero(sizes)[:6]:
            x = f32(rows[starts[e]:starts[e] + sizes[e]])
            ref = (jax.nn.silu(x @ f32(wg[e])) * (x @ f32(wu[e]))) @ f32(wd[e])
            np.testing.assert_allclose(out[starts[e]:starts[e] + sizes[e]], np.asarray(ref), atol=3e-2, rtol=3e-2)


def test_latent_decode_kernel_on_chip():
    """The decode kernel over one latent pool at the cell's shapes: 128 slots
    (idle ones among them), 32 heads on rows of 320 numbers padded to 384,
    values the first 256 columns, ragged contexts up to 4096."""
    from thunder_tpu.executors import pallasex

    rng = np.random.RandomState(0)
    B, H, W, vw, ps, npm = 128, 32, 384, 256, 64, 64
    lens = np.where(rng.rand(B) < 0.85, rng.randint(2, 4096, B), 1).astype(np.int32)
    P = 1 + int(np.sum(-(-lens // ps)))
    pool = rng.randn(P, ps, W).astype(np.float32)
    pool[..., 320:] = 0.0
    pool = jnp.asarray(pool, jnp.bfloat16)
    table, nxt = np.zeros((B, npm), np.int32), 1
    for b in np.flatnonzero(lens > 1):
        n = -(-int(lens[b]) // ps)
        table[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
    q = jnp.asarray(rng.randn(B, H, W) * 0.5, jnp.bfloat16)
    scale = 0.19
    out = np.asarray(pallasex.paged_latent_decode(q, pool, jnp.asarray(table), jnp.asarray(lens), scale, vw),
                     np.float32)
    dense = np.asarray(pool, np.float32)[table].reshape(B, npm * ps, W)
    mask = (np.arange(npm * ps)[None, :] < lens[:, None])[:, None, :]
    s = np.einsum("bhw,bsw->bhs", np.asarray(q, np.float32), dense) * scale
    p = np.asarray(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1))
    np.testing.assert_allclose(out, np.einsum("bhs,bsv->bhv", p, dense[..., :vw]), atol=2e-2, rtol=2e-2)


def test_ring_flash_kernels_on_chip():
    """The streaming ring-flash forward and backward step kernels, driven
    through ring_flash_attention over a one-chip ring (GQA 16q/4kv, D=64):
    output and all three gradients against plain causal attention."""
    from jax.sharding import PartitionSpec as P

    from thunder_tpu.executors import pallasex
    from thunder_tpu.parallel import make_mesh

    rng = np.random.RandomState(0)
    B, H, Hkv, T, D = 1, 16, 4, 2048, 64
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.bfloat16)
    w = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    assert pallasex.ring_flash_supported(q, k, v)
    mesh = make_mesh({"sp": 1}, devices=jax.devices()[:1])
    spec = P(None, None, "sp")
    ring = jax.shard_map(
        lambda q, k, v: pallasex.ring_flash_attention(q, k, v, axis_name="sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def ref(q, k, v):
        g = H // Hkv
        return _attention_ref(q, jnp.repeat(k, g, 1), jnp.repeat(v, g, 1), causal)

    loss = lambda f: (lambda q, k, v: jnp.sum(jnp.asarray(f(q, k, v), jnp.float32) * w))  # noqa: E731
    out, grads = jax.jit(lambda q, k, v: (ring(q, k, v),
                                          jax.grad(loss(ring), (0, 1, 2))(q, k, v)))(q, k, v)
    ref_grads = jax.jit(jax.grad(loss(ref), (0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref(q, k, v)),
                               atol=2e-2, rtol=2e-2)
    for name, got, want in zip("qkv", grads, ref_grads):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        rel = np.abs(got - want).mean() / np.abs(want).mean()
        assert rel < 2e-2, f"d{name}: mean relative error {rel}"


@pytest.mark.parametrize("save_quantized", [False, True])
def test_fused_fp8_linear_kernel_on_chip(save_quantized):
    """Fused delayed-scaling fp8 linear at llama-350m's MLP shape against the
    unfused quantize / e4m3 dot / amax reference."""
    from thunder_tpu.executors import pallasex
    from thunder_tpu.transforms.fp8_training import E4M3_MAX

    rng = np.random.RandomState(0)
    M, K, N = 8192, 1024, 2816
    x = jnp.asarray(rng.randn(M, K), jnp.bfloat16)
    w = jnp.asarray(rng.randn(N, K) * 0.05, jnp.bfloat16)
    sx, sw = 64.0, 1024.0  # powers of two: scaling and de-scaling are exact
    assert pallasex.fp8_linear_fused_supported(x, w)
    outs = pallasex.fp8_linear_fused(x, w, sx, sw, fmt_max=E4M3_MAX,
                                     save_quantized=save_quantized)
    quant = lambda t, s: jnp.clip(jnp.asarray(t, jnp.float32) * s,  # noqa: E731
                                  -E4M3_MAX, E4M3_MAX).astype(jnp.float8_e4m3fn)
    xq, wq = quant(x, sx), quant(w, sw)
    ref = jnp.matmul(jnp.asarray(xq, jnp.float32), jnp.asarray(wq, jnp.float32).T) / (sx * sw)
    np.testing.assert_allclose(np.asarray(outs[0], np.float32), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)
    assert float(outs[-2]) == float(jnp.max(jnp.abs(x)))
    assert float(outs[-1]) == float(jnp.max(jnp.abs(w)))
    if save_quantized:
        np.testing.assert_array_equal(np.asarray(outs[1]).view(np.uint8),
                                      np.asarray(xq).view(np.uint8))
        np.testing.assert_array_equal(np.asarray(outs[2]).view(np.uint8),
                                      np.asarray(wq).view(np.uint8))


def test_flash_kernels_lower_via_mosaic():
    import jax.numpy as jnp

    from thunder_tpu.executors import pallasex

    assert not pallasex._interpret()  # real lowering, not interpret mode
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, 4096, 64), jnp.bfloat16)
    o, lse = pallasex.flash_attention_forward(q, q, q, causal=True)
    do = jnp.asarray(rng.randn(*o.shape), jnp.bfloat16)
    dq, dk, dv = pallasex.flash_attention_backward(q, q, q, o, lse, do, causal=True)
    assert np.isfinite(np.asarray(o, np.float32)).all()
    assert np.isfinite(np.asarray(dq, np.float32)).all()


@pytest.mark.parametrize("H,Hkv,T,D,n_elem", [(16, 16, 2048, 64, 16), (16, 16, 2048, 64, 64),
                                              (8, 2, 4096, 128, 128), (8, 2, 4096, 128, 32)],
                         ids=["pythia-quarter-of-64", "llama-350m-64", "mistral-g4-128",
                              "g4-quarter-of-128"])
def test_rope_flash_kernels_on_chip(H, Hkv, T, D, n_elem):
    """The rope-fused flash pair compiled by Mosaic at the train cells' heads, at a rotary width
    of the whole head and of a quarter of it: the output and dq, dk, dv against float32 rope
    and softmax attention in plain jax (bf16 operands: a hundredth of the largest entry)."""
    from thunder_tpu.executors import pallasex
    from thunder_tpu.models.litgpt import build_rope_cache

    assert not pallasex._interpret()
    rng = np.random.RandomState(0)
    g = H // Hkv
    q = jnp.asarray(rng.randn(1, H, T, D), jnp.bfloat16)
    k, v = (jnp.asarray(rng.randn(1, Hkv, T, D), jnp.bfloat16) for _ in range(2))
    do = jnp.asarray(rng.randn(1, H, T, D), jnp.bfloat16)
    cos, sin = build_rope_cache(T, n_elem, 10000, jnp.float32)
    assert cos.shape == (T, n_elem)

    def rope(x):
        h = n_elem // 2
        x1, x2, rest = x[..., :h], x[..., h:n_elem], x[..., n_elem:]
        c, s = cos[:, :h], sin[:, :h]
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)
        return out.astype(jnp.bfloat16).astype(jnp.float32)  # rounded once, as the kernels do

    def ref(q, k, v):
        kk, vv = (jnp.repeat(t, g, axis=1) for t in (rope(k), v))
        mask = jnp.tril(jnp.ones((T, T), bool))
        return _attention_ref(rope(q), kk, vv, mask)

    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    want, vjp = jax.vjp(ref, *f32)
    want_g = vjp(do.astype(jnp.float32))
    o, lse = jax.jit(lambda *a: pallasex.flash_rope_attention_forward(*a, causal=True))(q, k, v, cos, sin)
    got_g = jax.jit(lambda *a: pallasex.flash_rope_attention_backward(*a, causal=True))(
        q, k, v, o, lse, cos, sin, do)
    for name, got, ref_ in zip(("o", "dq", "dk", "dv"), (o, *got_g), (want, *want_g)):
        got, ref_ = np.asarray(got, np.float32), np.asarray(ref_)
        assert np.isfinite(got).all(), name
        assert np.abs(got - ref_).max() < 0.01 * np.abs(ref_).max() + 1e-3, (
            name, float(np.abs(got - ref_).max()), float(np.abs(ref_).max()))


def test_flash_bwd_claimed_inside_train_step():
    """The executor-claimed sdpa grad must survive into TrainStep's backward
    trace (flash_attention_bwd symbol present, not the composite decomp)."""
    import jax.numpy as jnp

    import thunder_tpu as tt
    from thunder_tpu import optim
    from thunder_tpu.models.litgpt import Config, GPTForCausalLM
    from thunder_tpu.training import TrainStep
    from thunder_tpu.transforms.autocast import AutocastTransform

    cfg = Config.from_name("tiny-llama2", block_size=4096, n_layer=1,
                           vocab_size=512, padded_vocab_size=512)
    step = TrainStep(tt.jit(GPTForCausalLM(cfg), transforms=[AutocastTransform()]),
                     optim.AdamW(lr=1e-4))
    rng = np.random.RandomState(0)
    idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 4096)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 4096)), jnp.int32)
    loss = step(idx, tgt)
    assert np.isfinite(float(loss))
    # the claimed fwd/bwd traces before fusion collapses them into one
    # XLA region (the pallas calls live inside the fused program)
    fwd_srcs = [t.python() for t in step._vag._cs.last_traces]
    bwd_srcs = [t.python() for t in step._vag._cs.last_backward_traces]
    # tiny-llama2 is GQA with full-head rope: the fused rope+flash symbol
    # claims (rope_flash_*); plain flash_attention_* covers non-rope paths
    assert any("flash_attention_fwd" in s or "rope_flash_fwd" in s for s in fwd_srcs)
    assert any("flash_attention_bwd" in s or "rope_flash_bwd" in s for s in bwd_srcs)


def test_fused_cross_entropy_kernel_on_chip():
    import jax.numpy as jnp

    from thunder_tpu.executors import pallasex

    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(256, 2048), jnp.float32)
    tgt = jnp.asarray(rng.randint(0, 2048, (256,)), jnp.int32)
    loss, lse = pallasex.fused_cross_entropy_forward(logits, tgt)
    ref = -np.asarray(jax.nn.log_softmax(logits, -1))[np.arange(256), np.asarray(tgt)]
    np.testing.assert_allclose(np.asarray(loss), ref, atol=2e-3)


def test_fp8_inference_linear_on_chip():
    """The fp8 weight-only inference linear (transforms/fp8_inference.py, an
    XLA e4m3 dot) against the float32 matmul on this chip generation."""
    from thunder_tpu.transforms.fp8_inference import _fp8_linear_impl, quantize_fp8_weight

    rng = np.random.RandomState(0)
    M, K, N = 4096, 4096, 4096
    x = jnp.asarray(rng.randn(M, K), jnp.bfloat16)
    w = jnp.asarray(rng.randn(N, K), jnp.bfloat16)
    qw, scale = quantize_fp8_weight(w.astype(jnp.float32))
    got = np.asarray(jax.jit(_fp8_linear_impl)(x, qw, scale), np.float32)
    ref = np.asarray(jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32).T))
    rel = np.abs(got - ref).mean() / np.abs(ref).mean()
    assert rel < 0.08, rel


def test_gqa_rope_flash_train_step_on_chip():
    """GQA fused rope+flash on real hardware: a grouped-head llama config
    trains with decreasing loss through TrainStep (the kernels index kv
    blocks by q_head // group; dkv group-sums per-q-head partials)."""
    import thunder_tpu as tt
    from thunder_tpu import optim
    from thunder_tpu.models.litgpt import Config, GPTForCausalLM
    from thunder_tpu.training import TrainStep
    from thunder_tpu.transforms.autocast import AutocastTransform

    import jax.numpy as jnp

    cfg = Config.from_name("llama-350m", n_layer=2, n_query_groups=4,
                           block_size=2048)
    step = TrainStep(tt.jit(GPTForCausalLM(cfg), transforms=[AutocastTransform()]),
                     optim.AdamW(lr=1e-4))
    rng = np.random.RandomState(0)
    idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 2048)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 2048)), jnp.int32)
    losses = [float(step(idx, tgt)) for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    srcs = [t.python() for t in step._vag._cs.last_traces]
    assert any("rope_flash_fwd" in s for s in srcs)


def test_fused_quantized_linears_on_chip():
    """int8 and NF4 dequant-in-kernel matmuls vs their dequant references on
    the real chip (Mosaic lowering differs from interpret mode)."""
    import jax.numpy as jnp

    from thunder_tpu.executors import pallasex as px
    from thunder_tpu.transforms.quantization import dequantize_nf4_kl, quantize_nf4

    rng = np.random.RandomState(0)
    M, K, N = 8, 1024, 512
    x = jnp.asarray(rng.randn(M, K), jnp.bfloat16)

    w8 = jnp.asarray(np.clip(np.round(rng.randn(N, K) * 40), -127, 127), jnp.int8)
    s8 = jnp.asarray(np.abs(rng.randn(N)) * 1e-3 + 1e-4, jnp.float32)
    got8 = np.asarray(px.int8_linear(x, w8, s8), np.float32)
    want8 = np.asarray(x, np.float32) @ (np.asarray(w8, np.float32) * np.asarray(s8)[:, None]).T
    np.testing.assert_allclose(got8, want8, atol=2e-2, rtol=2e-2)

    w = rng.randn(N, K).astype(np.float32) * 0.05
    packed, absmax = quantize_nf4(jnp.asarray(w))
    pkl, akl = px.pack_nf4_kernel_layout(packed, absmax, (N, K))
    got4 = np.asarray(px.nf4_linear(x, pkl, akl), np.float32)
    want4 = np.asarray(x, np.float32) @ np.asarray(
        dequantize_nf4_kl(pkl, akl, (N, K)), np.float32).T
    np.testing.assert_allclose(got4, want4, atol=2e-2, rtol=2e-2)

    # adaptive block width (the llama MLP K)
    K2 = 2816
    assert px.nf4_kernel_block_k(K2) == 256


def test_a_decode_row_reads_the_same_through_the_decode_program_and_beside_a_chunk():
    """A sequence's decode rows go through `decode_cfn` (max_batch rows) when it is served
    alone and through the chunk program (512 + max_batch rows) when a prompt chunk is due in
    the same pass. The benchmark holds four requests to identical tokens alone and batched, so
    a row's logits may not depend on which of the two shapes its matmuls had: bit for bit the
    same, at the long-prompt cell's widths (two layers of them), whatever the chunk's start."""
    from thunder_tpu.models.litgpt import GPT, Config
    from thunder_tpu.serving import ServingEngine

    cfg = Config(name="mistral-7b-widths-l2", block_size=2048, vocab_size=32768, padded_vocab_size=32768,
                 n_layer=2, n_head=32, n_query_groups=8, n_embd=4096, head_size=128,
                 intermediate_size=14336, rope_base=1000000, norm_eps=1e-5)
    gpt = GPT(cfg, dtype=jnp.bfloat16)
    key = jax.random.key(35)
    for i, (name, p) in enumerate(sorted(gpt.named_parameters())):
        if p.data.ndim >= 2:
            p.data = (0.02 * jax.random.normal(jax.random.fold_in(key, i), p.data.shape,
                                               jnp.float32)).astype(jnp.bfloat16)
    eng = ServingEngine(gpt, max_batch=12, page_size=64, max_seq=2048, chunk_tokens=512, min_bucket=512)
    assert eng.runner.mixes
    rng = np.random.RandomState(35)
    lens = [515, 700, 1300, 33, 1600]
    for L in lens:
        eng.submit(rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32), max_new_tokens=40)
    while eng._chunking or eng._pending or sum(s is not None for s in eng._slots) < len(lens):
        eng._step_once()
    for _ in range(3):
        eng._step_once()
    eng._land()
    live = np.flatnonzero(eng._pos > 0)
    assert len(live) == len(lens)

    def i32(a):
        return jnp.asarray(np.array(a), jnp.int32)

    toks, tables, pos = i32(eng._toks[:, None]), (i32(eng._page_tables),), i32(eng._pos)
    alone, state = eng.runner.decode_cfn(eng.params, toks, eng.cache.state, tables, pos)
    alone = np.asarray(alone.astype(jnp.float32))[live]
    assert np.isfinite(alone).all() and alone.std() > 0.1
    T = eng.chunk_tokens
    row = (i32(eng.cache.page_table_row(eng.cache.allocator.alloc(3 * T // 64), eng.n_pages_max)[None]),)
    for start in (0, 2 * T):
        idx = i32(rng.randint(0, cfg.vocab_size, (1, T)))
        _, beside, state = eng.runner.chunk_cfn(eng.params, idx, row, state, i32(start), i32(T - 1),
                                                i32(11), (toks, tables, pos))
        # the decode rows rewrite the k/v they wrote in the step above, at the same positions
        beside = np.asarray(beside.astype(jnp.float32))[live]
        assert np.array_equal(alone, beside), (
            f"chunk at {start}: {int((alone != beside).sum())} of {alone.size} logits differ, "
            f"by {np.abs(alone - beside).max()} at most")
    eng.cache.rebind(state)
    eng.stop()
