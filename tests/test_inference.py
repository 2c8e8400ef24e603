"""KV-cache generation correctness: cached decode must match full recompute
(reference inference path correctness, thunder/benchmarks/benchmark_inference.py)."""
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.inference import GPTInference
from thunder_tpu.models.litgpt import Config, GPT


@pytest.mark.parametrize("name", ["tiny", "tiny-llama2"])
def test_generate_matches_full_recompute(name, rng):
    cfg = Config.from_name(name, block_size=64)
    gpt = GPT(cfg, dtype=jnp.float32)
    engine = GPTInference(gpt, dtype=jnp.float32)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 8)))

    out, metrics = engine.generate(prompt, max_new_tokens=6)
    assert out.shape == (2, 14)

    # reference: recompute the full forward at each step
    tm = tt.jit(gpt)
    seq = prompt
    for _ in range(6):
        logits = tm(seq)
        nxt = jnp.argmax(logits[:, -1], -1).astype(prompt.dtype)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_metrics_populated(rng):
    cfg = Config.from_name("tiny", block_size=64)
    engine = GPTInference(GPT(cfg, dtype=jnp.float32), dtype=jnp.float32)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 8)))
    _, m = engine.generate(prompt, max_new_tokens=4)
    assert m.ttft_s > 0 and m.tbot_s > 0 and m.tokens_per_sec > 0


def test_scan_decode_matches_loop(rng):
    """One-dispatch scan decode (the CUDA-graphs analog) produces the exact
    token sequence of the per-step loop."""
    from thunder_tpu.inference import GPTInference
    from thunder_tpu.models.litgpt import Config, GPT

    cfg = Config.from_name("tiny-llama2")
    gpt = GPT(cfg, dtype=jnp.float32)
    inf = GPTInference(gpt, dtype=jnp.float32)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 12)), jnp.int32)
    out_scan, m_scan = inf.generate(prompt, 8, scan_decode=True)
    out_loop, m_loop = inf.generate(prompt, 8, scan_decode=False)
    np.testing.assert_array_equal(np.asarray(out_scan), np.asarray(out_loop))
    assert out_scan.shape == (2, 20)


def test_scan_decode_batch_change_then_loop(rng):
    """Changing batch size between scan generations must not poison the
    decode cache with scan tracers (regression)."""
    from thunder_tpu.inference import GPTInference
    from thunder_tpu.models.litgpt import Config, GPT

    cfg = Config.from_name("tiny-llama2")
    inf = GPTInference(GPT(cfg, dtype=jnp.float32), dtype=jnp.float32)
    p2 = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 12)), jnp.int32)
    p4 = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 12)), jnp.int32)
    inf.generate(p2, 6, scan_decode=True)
    inf.generate(p4, 6, scan_decode=True)
    out, _ = inf.generate(p4, 6, scan_decode=False)
    assert out.shape == (4, 18)


def test_moe_generate_matches_full_recompute(rng):
    """KV-cached generation over the Mixtral-style MoE decoder (the reference
    inference harness drives MoE CausalLMs, benchmark_inference.py:1-11)."""
    from thunder_tpu.models.moe import MoEConfig, MoEGPT

    cfg = Config.from_name("tiny-llama2", block_size=64)
    moe_cfg = MoEConfig(n_embd=cfg.n_embd, intermediate_size=160,
                        n_expert=4, n_expert_per_token=2)
    gpt = MoEGPT(cfg, moe_cfg, dtype=jnp.float32)
    engine = GPTInference(gpt, dtype=jnp.float32)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 8)))

    out, _ = engine.generate(prompt, max_new_tokens=5)
    assert out.shape == (2, 13)

    tm = tt.jit(gpt)
    seq = prompt
    for _ in range(5):
        logits = tm(seq)
        nxt = jnp.argmax(logits[:, -1], -1).astype(prompt.dtype)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_temperature_sampling_valid_and_seeded(rng):
    """temperature>0 samples from the categorical; tokens stay in-vocab and
    a fixed key makes the run reproducible."""
    cfg = Config.from_name("tiny", block_size=64)
    engine = GPTInference(GPT(cfg, dtype=jnp.float32), dtype=jnp.float32)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 6)))
    out1, _ = engine.generate(prompt, 8, temperature=0.8)
    out2, _ = engine.generate(prompt, 8, temperature=0.8)
    assert out1.shape == (2, 14)
    toks = np.asarray(out1[:, 6:])
    assert ((toks >= 0) & (toks < cfg.vocab_size)).all()
    # same engine, same inputs, same key schedule -> identical draws
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_temperature_zero_equals_greedy(rng):
    cfg = Config.from_name("tiny", block_size=64)
    engine = GPTInference(GPT(cfg, dtype=jnp.float32), dtype=jnp.float32)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 6)))
    out_t0, _ = engine.generate(prompt, 6, temperature=0.0, scan_decode=False)
    out_greedy, _ = engine.generate(prompt, 6, scan_decode=False)
    np.testing.assert_array_equal(np.asarray(out_t0), np.asarray(out_greedy))


@pytest.mark.parametrize("B", [1, 3, 4])
def test_batch_sizes_match_full_recompute(B, rng):
    """Every batch size decodes the exact full-recompute sequence (batch>1
    rode only the benchmarks before round 5)."""
    cfg = Config.from_name("tiny", block_size=64)
    gpt = GPT(cfg, dtype=jnp.float32)
    engine = GPTInference(gpt, dtype=jnp.float32)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 7)))
    out, _ = engine.generate(prompt, 5)
    tm = tt.jit(gpt)
    seq = prompt
    for _ in range(5):
        logits = tm(seq)
        nxt = jnp.argmax(logits[:, -1], -1).astype(prompt.dtype)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_quantized_engine_generate_shapes(rng):
    """int8 weight-only quantization through the serving engine: generation
    runs end-to-end and stays in-vocab (kernel-claimed path on chip; the
    jax fallback path on CPU)."""
    from thunder_tpu.transforms.quantization import QuantizeInt8Transform

    cfg = Config.from_name("tiny-llama2", block_size=64)
    gpt = GPT(cfg, dtype=jnp.float32)
    QuantizeInt8Transform().transform_module(gpt)
    engine = GPTInference(gpt, dtype=jnp.float32)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 6)))
    out, _ = engine.generate(prompt, 4)
    assert out.shape == (2, 10)
    toks = np.asarray(out[:, 6:])
    # random-init logits cover the PADDED vocab; trained models mask the tail
    assert ((toks >= 0) & (toks < cfg.padded_vocab_size)).all()


def test_overlong_generation_raises(rng):
    """prompt_len + max_new_tokens > max_seq must fail up front: letting it
    run would have dynamic_update_slice clamp its writes at the cache edge
    and silently corrupt the KV tail (the old behavior)."""
    cfg = Config.from_name("tiny", block_size=16)
    engine = GPTInference(GPT(cfg, dtype=jnp.float32), dtype=jnp.float32)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 14)))
    with pytest.raises(ValueError, match="max_seq"):
        engine.generate(prompt, 10)
    # the boundary itself is fine: prompt + new == max_seq
    out_scan, _ = engine.generate(prompt, 2, scan_decode=True)
    out_loop, _ = engine.generate(prompt, 2, scan_decode=False)
    assert out_scan.shape == (1, 16)
    np.testing.assert_array_equal(np.asarray(out_scan), np.asarray(out_loop))


def test_gqa_scan_decode_matches_eager(rng):
    """GQA config (n_query_groups != n_head): one-dispatch scan decode and
    the eager per-step loop must produce identical token streams."""
    cfg = Config(name="gqa-test", block_size=64, vocab_size=256,
                 padded_vocab_size=256, n_layer=2, n_head=8, n_query_groups=2,
                 n_embd=64, norm_class_name="RMSNorm", mlp_class_name="LLaMAMLP")
    assert cfg.n_query_groups != cfg.n_head
    engine = GPTInference(GPT(cfg, dtype=jnp.float32), dtype=jnp.float32)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 10)), jnp.int32)
    out_scan, _ = engine.generate(prompt, 8, scan_decode=True)
    out_loop, _ = engine.generate(prompt, 8, scan_decode=False)
    assert out_scan.shape == (2, 18)
    np.testing.assert_array_equal(np.asarray(out_scan), np.asarray(out_loop))


def test_seeded_sampling_reproducible_and_per_seed(rng):
    """seed= keys the sampling stream: same seed -> identical tokens,
    different seeds -> (overwhelmingly) different draws (the old
    PRNGKey(pos) scheme drew the SAME stream for every request)."""
    cfg = Config.from_name("tiny", block_size=64)
    engine = GPTInference(GPT(cfg, dtype=jnp.float32), dtype=jnp.float32)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 6)))
    out_a1, _ = engine.generate(prompt, 12, temperature=1.0, seed=7)
    out_a2, _ = engine.generate(prompt, 12, temperature=1.0, seed=7)
    out_b, _ = engine.generate(prompt, 12, temperature=1.0, seed=8)
    np.testing.assert_array_equal(np.asarray(out_a1), np.asarray(out_a2))
    assert (np.asarray(out_a1) != np.asarray(out_b)).any()


# ---------------------------------------------------------------------------
# paged-vs-dense attention equivalence (serving substrate)
# ---------------------------------------------------------------------------


def _paged_fixture(rng, B=3, H=4, Hkv=2, D=16, ps=8, P=12, npm=4, dtype=jnp.float32):
    """Random pool + ragged page tables, incl. partially-filled last pages."""
    k_pages = jnp.asarray(rng.randn(P, Hkv, ps, D), dtype)
    v_pages = jnp.asarray(rng.randn(P, Hkv, ps, D), dtype)
    seq_lens = np.asarray([5, 17, 24], np.int32)  # partial, partial, full
    pt = np.zeros((B, npm), np.int32)
    pt[0, :1] = [3]
    pt[1, :3] = [1, 4, 7]
    pt[2, :3] = [2, 5, 9]
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    return q, k_pages, v_pages, jnp.asarray(pt), jnp.asarray(seq_lens)


def _dense_from_pages(q, k_pages, v_pages, pt, seq_lens):
    """Gather each sequence's pages densely and run cached_sdpa (the dense
    decode-attention reference) over its exact length."""
    from thunder_tpu.inference import cached_sdpa

    B, H, D = q.shape
    P, Hkv, ps, _ = k_pages.shape
    g = H // Hkv
    dense = tt.jit(lambda q4, k4, v4, pos: cached_sdpa(q4, k4, v4, pos))
    outs = []
    for b in range(int(B)):
        L = int(seq_lens[b])
        npg = -(-L // ps)
        row = np.asarray(pt)[b, :npg]
        # head-major pages (npg, Hkv, ps, D) -> (Hkv, npg*ps, D)
        k = np.asarray(k_pages)[row].transpose(1, 0, 2, 3).reshape(Hkv, npg * ps, D)[:, :L]
        v = np.asarray(v_pages)[row].transpose(1, 0, 2, 3).reshape(Hkv, npg * ps, D)[:, :L]
        k = jnp.asarray(np.repeat(k, g, 0)[None])  # (1, H, L, D)
        v = jnp.asarray(np.repeat(v, g, 0)[None])
        q4 = jnp.asarray(np.asarray(q)[b][None, :, None, :])  # (1, H, 1, D)
        # the query is the LAST cached token: cached_sdpa's mask needs its
        # position, L-1
        o = dense(q4, k, v, jnp.asarray(L - 1, jnp.int32))
        outs.append(np.asarray(o)[0, :, 0, :])
    return np.stack(outs)


def test_paged_attention_reference_matches_dense(rng):
    """ltorch.paged_attention's gather decomposition == dense cached_sdpa
    over ragged page tables with partially-filled last pages."""
    from thunder_tpu.ops import ltorch

    q, kp, vp, pt, sl = _paged_fixture(rng)
    paged = tt.jit(lambda q, kp, vp, pt, sl: ltorch.paged_attention(q, kp, vp, pt, sl))
    out = np.asarray(paged(q, kp, vp, pt, sl))
    ref = _dense_from_pages(q, kp, vp, pt, sl)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_paged_attention_kernel_matches_dense(rng):
    """The pallas paged decode kernel (interpret mode on CPU) == dense
    cached_sdpa within tolerance — incl. GQA grouping and partial pages."""
    from thunder_tpu.executors.pallasex import paged_attention_decode

    q, kp, vp, pt, sl = _paged_fixture(rng)
    out = np.asarray(paged_attention_decode(q, kp, vp, pt, sl, interpret=True))
    ref = _dense_from_pages(q, kp, vp, pt, sl)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_paged_attention_kernel_bf16_tolerance(rng):
    """bf16 pool/query: kernel and reference agree within bf16 tolerance
    (the acceptance bar: paged decode matches dense within bf16 eps)."""
    from thunder_tpu.executors.pallasex import paged_attention_decode

    q, kp, vp, pt, sl = _paged_fixture(rng, dtype=jnp.bfloat16)
    out = np.asarray(paged_attention_decode(q, kp, vp, pt, sl, interpret=True),
                     dtype=np.float32)
    ref = _dense_from_pages(jnp.asarray(q, jnp.float32),
                            jnp.asarray(kp, jnp.float32),
                            jnp.asarray(vp, jnp.float32), pt, sl)
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)


def test_paged_chunk_kernel_matches_reference(rng):
    """The multi-query paged kernel (interpret mode) == the
    ltorch.paged_chunk_attention gather decomposition, GQA with ragged
    per-query positions (chunk rows and speculative-verify rows)."""
    from thunder_tpu.executors.pallasex import paged_chunk_decode
    from thunder_tpu.ops import ltorch

    q1, kp, vp, pt, _ = _paged_fixture(rng)
    B, H, D = q1.shape
    T = 4
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    q_pos = jnp.asarray([[1, 2, 3, 4], [13, 14, 15, 16], [20, 21, 22, 23]], jnp.int32)
    ref = tt.jit(lambda q, kp, vp, pt, qp: ltorch.paged_chunk_attention(q, kp, vp, pt, qp))
    out = np.asarray(paged_chunk_decode(q, kp, vp, pt, q_pos, interpret=True))
    np.testing.assert_allclose(out, np.asarray(ref(q, kp, vp, pt, q_pos)),
                               atol=2e-5, rtol=2e-5)


def test_paged_attention_vmem_fallback_declines(pallas_claims):
    """The ADVICE VMEM-estimation pattern: a page_size x D working set over
    the budget makes the checker decline (the jax gather decomposition runs
    instead of a kernel that would fail to fit VMEM)."""
    from thunder_tpu.executors import pallasex

    class _P:
        def __init__(self, shape, dtype="float32"):
            self.shape = shape
            self.ndim = len(shape)
            self.dtype = dtype

    q = _P((2, 4, 512))
    small = _P((8, 2, 32, 512))
    huge = _P((8, 2, 8192, 512))  # 2 * 2 * 8192*512*4B ≈ 67 MB of k/v blocks
    pt = _P((2, 4), "int32")
    sl = _P((2,), "int32")
    assert pallasex.paged_attention_supported(q, small, small, pt, sl)
    assert not pallasex.paged_attention_supported(q, huge, huge, pt, sl)
