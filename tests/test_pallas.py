"""Pallas kernel coverage (interpret mode on CPU — the same kernels lower via
Mosaic on TPU). Reference analogs: sdpaex/cudnnex flash attention
(thunder/executors/sdpaex.py), triton/apex cross-entropy, fused RMSNorm."""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import thunder_tpu as tt
from thunder_tpu.executors import pallasex
from thunder_tpu.ops import ltorch


def _ref_attn(q, k, v, causal=True, scale=None):
    D = q.shape[-1]
    scale = scale or 1.0 / math.sqrt(D)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        L = q.shape[-2]
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    return jax.nn.softmax(s, -1) @ v


@pytest.mark.parametrize("dtype,atol", [(np.float32, 2e-3), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("D", [64, 128])
def test_flash_forward_matches_reference(rng, D, dtype, atol):
    # bf16 exercises the low-precision MXU path (p cast to the value dtype
    # before the pv dot); f32 inputs make those casts identity no-ops
    B, H, T = 2, 3, 256
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32), dtype) for _ in range(3))
    o, lse = pallasex.flash_attention_forward(q, k, v, causal=True)
    ref = _ref_attn(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(ref), atol=atol)
    assert lse.shape == (B, H, T)


@pytest.mark.parametrize("dtype,atol", [(np.float32, 5e-3), (jnp.bfloat16, 1e-1)])
@pytest.mark.parametrize("D", [64, 128])
def test_flash_backward_matches_jax_vjp(rng, D, dtype, atol):
    B, H, T = 2, 2, 128
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32), dtype) for _ in range(3))
    o, lse = pallasex.flash_attention_forward(q, k, v, causal=True)
    do = jnp.asarray(rng.randn(*o.shape).astype(np.float32), dtype)
    dq, dk, dv = pallasex.flash_attention_backward(q, k, v, o, lse, do, causal=True)
    f32 = jnp.float32
    ref_grads = jax.vjp(lambda q, k, v: _ref_attn(q, k, v),
                        q.astype(f32), k.astype(f32), v.astype(f32))[1](do.astype(f32))
    for got, want, name in zip((dq, dk, dv), ref_grads, "dq dk dv".split()):
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                                   atol=atol, err_msg=name)


def test_flash_noncausal(rng):
    B, H, T, D = 1, 2, 128, 64
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) for _ in range(3))
    o, _ = pallasex.flash_attention_forward(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(_ref_attn(q, k, v, causal=False)), atol=2e-3)


class _Operand:
    """What a checker reads of a proxy."""

    def __init__(self, shape, dtype="bfloat16"):
        self.shape, self.ndim, self.dtype = shape, len(shape), dtype


def test_checker_accepts_gpt2_shapes():
    FakeProxy = _Operand
    q = FakeProxy((2, 12, 4096, 64))
    assert pallasex.flash_attention_supported(q, q, q, None, 0.0, True, None)
    # T=1024 claims too (bf16-dot kernels beat the composite from T>=1024)
    q_1k = FakeProxy((8, 12, 1024, 64))
    assert pallasex.flash_attention_supported(q_1k, q_1k, q_1k, None, 0.0, True, None)
    # short sequences stay on the composite path (XLA wins on-chip, measured)
    q_short = FakeProxy((8, 12, 512, 64))
    assert not pallasex.flash_attention_supported(q_short, q_short, q_short, None, 0.0, True, None)
    # unaligned sequence length stays on the composite path
    q_bad = FakeProxy((8, 12, 4100, 64))
    assert not pallasex.flash_attention_supported(q_bad, q_bad, q_bad, None, 0.0, True, None)
    # GQA/MQA (divisible kv heads) now claims: kv blocks index h // group,
    # dkv group-sums per-q-head partials
    kv = FakeProxy((2, 4, 4096, 64))
    assert pallasex.flash_attention_supported(q, kv, kv, None, 0.0, True, None)
    kv_bad = FakeProxy((2, 5, 4096, 64))  # indivisible head count: composite
    assert not pallasex.flash_attention_supported(q, kv_bad, kv_bad, None, 0.0, True, None)
    # mismatched head dim / kv seq len also fall back
    v_bad = FakeProxy((2, 12, 4096, 128))
    assert not pallasex.flash_attention_supported(q, q, v_bad, None, 0.0, True, None)
    k_short = FakeProxy((2, 12, 512, 64))
    assert not pallasex.flash_attention_supported(q, k_short, k_short, None, 0.0, False, None)


# the cells' and chip_smoke.py's shapes (T 2,048 and 4,096; heads 64 and 128; groups 1 and 4)
# fit; 8,192 fits the plain kernels only (the rope tables stay whole in VMEM beside K and V);
# 16,384 fits neither forward under the compiler's 16 MiB. At heads of 128 the last lengths
# the v5e's compiler takes for the forward's 512 x 512 tile, 13,312 plain and 6,144 with rope,
# fit, and the next ones do not
_FLASH_VMEM_CASES = ([(T, D, g, ("flash_attention", "rope_sdpa"))
                      for T in (2048, 4096) for D in (64, 128) for g in (1, 4)]
                     + [(8192, 128, 4, ("flash_attention",)), (8192, 64, 1, ("flash_attention",)),
                        (16384, 128, 4, ()), (16384, 64, 1, ()), (32768, 128, 1, ()),
                        (6144, 128, 4, ("flash_attention", "rope_sdpa")),
                        (7168, 128, 4, ("flash_attention",)), (13312, 128, 1, ("flash_attention",)),
                        (14336, 128, 1, ())])


def _claimed_and_declines(check):
    """What a checker said, and the `pallas.decline.*` counters it left on the bus."""
    from thunder_tpu import observability

    observability.enable()
    observability.reset()
    try:
        claimed = check()
        return claimed, [k for k in observability.counters() if k.startswith("pallas.decline.")]
    finally:
        observability.disable()


@pytest.mark.parametrize("kernel", ["flash_attention", "rope_sdpa", "rope_sdpa-quarter"])
@pytest.mark.parametrize("T,D,g,fits", _FLASH_VMEM_CASES,
                         ids=[f"T{T}-D{D}-g{g}" for T, D, g, _ in _FLASH_VMEM_CASES])
def test_flash_checkers_claim_what_fits_vmem_and_decline_the_rest(kernel, T, D, g, fits):
    """A sequence whose whole-length K and V (and rope tables) the estimate says do not fit is
    declined with `pallas.decline.<kernel>.vmem` on the bus (the rope checker goes through the
    plain one, whose counter it is when the plain kernel does not fit either) and XLA's
    composite runs; it used to be claimed and then refused by the compiler. Tables of a
    quarter of the head (pythia's `(T, 16)` at heads of 64) claim and decline where the whole
    head's do: the kernels widen them to the head, and the estimate counts them so."""
    kernel, _, quarter = kernel.partition("-")
    q, kv = _Operand((4, 8 * g, T, D)), _Operand((4, 8, T, D))
    table = _Operand((T, D // 4 if quarter else D), "float32")
    if kernel == "flash_attention":
        check = lambda: pallasex.flash_attention_supported(q, kv, kv, None, 0.0, True, None)  # noqa: E731
    else:
        check = lambda: pallasex.rope_sdpa_supported(q, kv, kv, table, table, True, None)  # noqa: E731
    claimed, declines = _claimed_and_declines(check)
    assert claimed == (kernel in fits)
    if claimed:
        assert declines == []
    else:
        by = kernel if "flash_attention" in fits else "flash_attention"
        assert declines == [f"pallas.decline.{by}.vmem"]


def test_the_choice_of_kernel_reads_no_environment():
    """Which kernel runs is the checkers' decision from the platform, the shapes and a VMEM
    estimate: the executor, the fp8 road and the two VMEM budgets read no variable."""
    import inspect

    from thunder_tpu.analysis import memory
    from thunder_tpu.transforms import fp8_training

    sources = {"executors/pallasex.py": inspect.getsource(pallasex),
               "transforms/fp8_training.py": inspect.getsource(fp8_training),
               "analysis/memory.py: vmem_limit": inspect.getsource(memory.vmem_limit),
               "analysis/memory.py: paged_vmem_limit": inspect.getsource(memory.paged_vmem_limit),
               "analysis/memory.py: within_vmem": inspect.getsource(memory.within_vmem)}
    for name, source in sources.items():
        assert "environ" not in source and "getenv" not in source, name


def test_sdpa_symbol_claims_flash_end_to_end(rng):
    """Through tt.jit the pallas executor claims sdpa whole when shapes fit
    (long sequences only — short ones stay on XLA's fused composite)."""
    B, H, T, D = 1, 1, 4096, 64
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D).astype(np.float32)) for _ in range(3))

    calls = {"n": 0}
    orig = pallasex.flash_attention_forward

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    pallasex.flash_attention_forward = spy
    try:
        fn = tt.jit(lambda q, k, v: ltorch.sdpa(q, k, v, is_causal=True))
        out = np.asarray(fn(q, k, v))
    finally:
        pallasex.flash_attention_forward = orig
    assert calls["n"] >= 1
    np.testing.assert_allclose(out, np.asarray(_ref_attn(q, k, v)), atol=2e-3)


def test_fused_cross_entropy_matches(rng):
    N, C = 64, 512
    logits = jnp.asarray(rng.randn(N, C).astype(np.float32))
    tgt = jnp.asarray(rng.randint(0, C, (N,)))
    loss, lse = pallasex.fused_cross_entropy_forward(logits, tgt)
    ref = -np.asarray(jax.nn.log_softmax(logits, -1))[np.arange(N), np.asarray(tgt)]
    np.testing.assert_allclose(np.asarray(loss), ref, atol=2e-4)


@pytest.mark.parametrize("rows", [32, 256, 524, 640], ids=lambda n: f"rows{n}")
def test_fused_rms_norm_matches(rng, rows):
    """Every row is written, also where the block of 256 does not divide them (524: a chunk's
    512 rows and a dozen decode rows in one program; 640: 512 and 128)."""
    x = jnp.asarray(rng.randn(rows, 256).astype(np.float32))
    w = jnp.asarray(rng.randn(256).astype(np.float32))
    out = pallasex.fused_rms_norm(x, w)
    ref = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)


def test_sdpa_gqa_short_seq_falls_back_to_composite(rng):
    """GQA now CAN claim (kv head = q head // group in the BlockSpecs), but
    this T=256 case fails the size gate (T>=1024, block divisibility) like
    any short sequence — the composite path replicates kv heads."""
    B, Hq, Hkv, T, D = 2, 8, 2, 256, 64
    q = jnp.asarray(rng.randn(B, Hq, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, Hkv, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, Hkv, T, D).astype(np.float32))

    calls = {"n": 0}
    orig = pallasex.flash_attention_forward

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    pallasex.flash_attention_forward = spy
    try:
        fn = tt.jit(lambda q, k, v: ltorch.sdpa(q, k, v, is_causal=True, enable_gqa=True))
        out = np.asarray(fn(q, k, v))
    finally:
        pallasex.flash_attention_forward = orig
    assert calls["n"] == 0

    kk = jnp.repeat(k, Hq // Hkv, axis=1)
    vv = jnp.repeat(v, Hq // Hkv, axis=1)
    np.testing.assert_allclose(out, np.asarray(_ref_attn(q, kk, vv)), atol=2e-3)

    # without enable_gqa, mismatched heads is an error (torch semantics)
    with pytest.raises(RuntimeError, match="enable_gqa"):
        tt.jit(lambda q, k, v: ltorch.sdpa(q, k, v))(q, k, v)


@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("D,n_elem", [(64, 64), (64, 16), (128, 32), (128, 128)])
def test_rope_sdpa_fused_matches_decomposition(rng, D, n_elem, g):
    """Fused rope+flash (in-kernel rope + in-kernel rope-VJP rotation) vs the
    decomposed rope->sdpa path, fwd and grads (f32, interpret mode), at a rotary
    width of the whole head and of a quarter of it (the tables' width says which),
    one query head a KV head and four."""
    import math

    import thunder_tpu as tt
    from thunder_tpu.models.litgpt import build_rope_cache

    B, Hkv, T = 1, 2, 1024  # T=1024: the fused kernel actually claims
    q = jnp.asarray(rng.randn(B, Hkv * g, T, D).astype(np.float32))
    k, v = (jnp.asarray(rng.randn(B, Hkv, T, D).astype(np.float32)) for _ in range(2))
    cos, sin = build_rope_cache(T, n_elem, 10000, jnp.float32)
    assert cos.shape == (T, n_elem)

    calls = {"n": 0}
    orig_fwd = pallasex.flash_rope_attention_forward

    def spy(*a, **kw):
        calls["n"] += 1
        return orig_fwd(*a, **kw)

    pallasex.flash_rope_attention_forward = spy

    def loss(q, k, v, c, s):
        o = ltorch.rope_sdpa(q, k, v, c, s, is_causal=True, scale=1.0 / math.sqrt(D))
        return ltorch.sum(o * o)  # a cotangent that differs by column

    orig = pallasex.rope_sdpa_supported
    pallasex.rope_sdpa_supported = lambda *a, **kw: False
    try:
        ref_loss, ref_g = tt.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v, cos, sin)
    finally:
        pallasex.rope_sdpa_supported = orig
    try:
        got_loss, got_g = tt.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v, cos, sin)
    finally:
        pallasex.flash_rope_attention_forward = orig_fwd
    assert calls["n"] >= 1, "fused rope kernel was not exercised"
    np.testing.assert_allclose(float(got_loss), float(ref_loss), rtol=1e-6)
    for i, name in enumerate(["dq", "dk", "dv"]):
        np.testing.assert_allclose(np.asarray(got_g[0][i]), np.asarray(ref_g[0][i]),
                                   atol=2e-4, err_msg=name)
    if n_elem < D:  # the columns past the width pass: their rows of the rope are the identity
        plain = np.asarray(jax.jit(lambda x: pallasex._rope_block(
            x, *pallasex._rope_tables(cos, sin, D)))(q[0, 0]))
        np.testing.assert_array_equal(plain[:, n_elem:], np.asarray(q[0, 0, :, n_elem:]))


def test_the_rotation_matrix_of_the_full_width_is_the_one_it_was():
    """`_rot_matrix(D, D)` is rotate_half over the head, as before the kernels took a width;
    at a narrower width it is rotate_half over the first columns and zero elsewhere."""
    full = np.asarray(pallasex._rot_matrix(8, 8, jnp.float32))
    x = np.arange(1.0, 9.0, dtype=np.float32)
    np.testing.assert_array_equal(x @ full, np.concatenate([-x[4:], x[:4]]))
    part = np.asarray(pallasex._rot_matrix(8, 4, jnp.float32))
    np.testing.assert_array_equal(x @ part, np.array([-3, -4, 1, 2, 0, 0, 0, 0], np.float32))
    assert not part[4:].any() and not part[:, 4:].any()
    np.testing.assert_array_equal(part[:4, :4], np.asarray(pallasex._rot_matrix(4, 4, jnp.float32)))


@pytest.mark.parametrize("width, reason", [(15, "width"), (63, "width"), (66, "width"),
                                           (128, "width"), (0, "width"), (None, "tables")])
def test_rope_checker_declines_a_width_it_does_not_take_by_name(width, reason):
    """An odd rotary width, one wider than the head or none, and tables that are not two
    (T, n) of one shape: declined as `pallas.decline.rope_sdpa.<reason>`, alone on the bus."""
    T, D = 2048, 64
    q = _Operand((4, 8, T, D))
    cos = _Operand((T, 16 if width is None else width), "float32")
    sin = _Operand((T, 32), "float32") if width is None else cos
    claimed, declines = _claimed_and_declines(
        lambda: pallasex.rope_sdpa_supported(q, q, q, cos, sin, True, None))
    assert not claimed
    assert declines == [f"pallas.decline.rope_sdpa.{reason}"]


@pytest.mark.parametrize("dtype,atol", [(np.float32, 2e-3)])
def test_flash_gqa_matches_reference(rng, dtype, atol):
    """GQA flash: kv head = q head // group in the BlockSpecs; dkv backward
    group-sums per-q-head partials (no repeated-KV materialization)."""
    B, Hq, Hkv, T, D = 2, 8, 2, 256, 64
    q = jnp.asarray(rng.randn(B, Hq, T, D).astype(dtype))
    k = jnp.asarray(rng.randn(B, Hkv, T, D).astype(dtype))
    v = jnp.asarray(rng.randn(B, Hkv, T, D).astype(dtype))
    o, lse = pallasex.flash_attention_forward(q, k, v, causal=True)
    kk = jnp.repeat(k, Hq // Hkv, axis=1)
    vv = jnp.repeat(v, Hq // Hkv, axis=1)
    np.testing.assert_allclose(np.asarray(o), np.asarray(_ref_attn(q, kk, vv)), atol=atol)

    do = jnp.asarray(rng.randn(*o.shape).astype(dtype))
    dq, dk, dv = pallasex.flash_attention_backward(q, k, v, o, lse, do, causal=True)
    assert dk.shape == k.shape and dv.shape == v.shape
    ref = jax.vjp(lambda q, k, v: _ref_attn(
        q, jnp.repeat(k, Hq // Hkv, axis=1), jnp.repeat(v, Hq // Hkv, axis=1)),
        q, k, v)[1](do)
    for got, want, name in zip((dq, dk, dv), ref, "dq dk dv".split()):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-3,
                                   err_msg=name)


def _rope_ref(x, cos, sin):
    """rotate_half rope over the first `n_elem` columns, the rest passed."""
    n_elem = cos.shape[-1]
    h = n_elem // 2
    x1, x2, rest = x[..., :h], x[..., h:n_elem], x[..., n_elem:]
    c, s = cos[:, :h], sin[:, :h]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


# (pair, causal, head dim, rotary width, q heads a KV head, block_q, block_k) at T 256: a query
# block of 64 has interior tiles and one diagonal tile of 64 keys, or two of 32
_SPLIT_CASES = [("rope", True, 64, 16, 1, 64, 32), ("rope", False, 128, 128, 4, 64, 64),
                ("plain", True, 128, None, 4, 64, 64), ("plain", False, 64, None, 1, 64, 32)]


@pytest.mark.parametrize("pair,causal,D,n_elem,g,block_q,block_k", _SPLIT_CASES,
                         ids=[f"{p}-{'causal' if c else 'full'}-D{D}-g{g}-{bq}x{bk}"
                              for p, c, D, _, g, bq, bk in _SPLIT_CASES])
def test_flash_pairs_split_key_tiles_match_reference(rng, pair, causal, D, n_elem, g,
                                                     block_q, block_k):
    """Both flash pairs, forward and backward, at blocks small enough that a query block has
    interior key tiles (run by the forward with no mask) and one or two diagonal ones (the
    mask, and only there): against float32 attention in plain jax."""
    from thunder_tpu.models.litgpt import build_rope_cache

    B, Hkv, T = 1, 2 if g == 1 else 1, 256
    q, do = (jnp.asarray(rng.randn(B, Hkv * g, T, D).astype(np.float32)) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(B, Hkv, T, D).astype(np.float32)) for _ in range(2))
    blocks = {"causal": causal, "block_q": block_q, "block_k": block_k}
    if pair == "rope":
        cos, sin = build_rope_cache(T, n_elem, 10000, jnp.float32)
        o, lse = pallasex.flash_rope_attention_forward(q, k, v, cos, sin, **blocks)
        got = pallasex.flash_rope_attention_backward(q, k, v, o, lse, cos, sin, do, **blocks)
        rope, atol = functools.partial(_rope_ref, cos=cos, sin=sin), (2e-4, 2e-4)
    else:
        o, lse = pallasex.flash_attention_forward(q, k, v, **blocks)
        got = pallasex.flash_attention_backward(q, k, v, o, lse, do, **blocks)
        rope, atol = (lambda x: x), (2e-3, 5e-3)

    def ref(q, k, v):
        kk, vv = (jnp.repeat(t, g, axis=1) for t in (rope(k), v))
        return _ref_attn(rope(q), kk, vv, causal=causal)

    def ref_and_grads(q, k, v, do):
        want, vjp = jax.vjp(ref, q, k, v)
        return want, vjp(do)

    want, want_g = jax.jit(ref_and_grads)(q, k, v, do)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=atol[0], err_msg="o")
    for got_, want_, name in zip(got, want_g, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got_), np.asarray(want_), atol=atol[1], err_msg=name)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_tile_counters_at_pythias_shape(causal):
    """At pythia's shape (B 4, H 16, T 2,048, head 64, rotary 16) both forwards run blocks of 512
    queries by 512 keys: under the causal mask a (batch, head) has 6 interior tiles and 4
    diagonal ones and no tile past its last query, without it 16 interior ones; each traced
    call counts them times B * H = 64 (`pallas.flash.tiles_interior` / `.tiles_diagonal`)."""
    from thunder_tpu import observability

    B, H, T, D = 4, 16, 2048, 64
    x = jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16)
    table = jax.ShapeDtypeStruct((T, 16), jnp.float32)
    calls = [(functools.partial(pallasex.flash_rope_attention_forward, causal=causal),
              (x, x, x, table, table)),
             (functools.partial(pallasex.flash_attention_forward, causal=causal), (x, x, x))]
    observability.enable()
    try:
        for call, args in calls:
            observability.reset()
            jax.eval_shape(call, *args)
            counters = observability.counters()
            assert (counters.get("pallas.flash.tiles_interior", 0),
                    counters.get("pallas.flash.tiles_diagonal", 0)) == (
                        (6 * 64, 4 * 64) if causal else (16 * 64, 0))
    finally:
        observability.disable()
