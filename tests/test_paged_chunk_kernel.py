"""The paged chunk kernel (executors/pallasex.py: a grid of (sequence, KV heads, tile of the queries), a
loop over the pages the tile's queries can see, several a step) in interpret mode on the CPU: against
the ``ltorch.paged_chunk_attention`` gather decomposition, a sequence alone and batched, with everything
outside the visible range poisoned; the operand list the benchmark's kernel classes recognise the call
by; the VMEM estimate, the blocks it gives and the checker's declines."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.analysis import budget
from thunder_tpu.executors import pallasex
from thunder_tpu.ops import ltorch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PS, NPM = 8, 20  # a table of 20 pages of 8: five steps of 4 pages


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Steps of 4 pages and tiles of 64 rows a KV head, so that a chunk of 32 queries at these sizes is
    two tiles (16 queries each at g = 4) and its context several steps, the last one partial."""
    monkeypatch.setattr(budget, "PAGED_CHUNK_KEYS_PER_STEP", 4 * PS)
    monkeypatch.setattr(budget, "PAGED_CHUNK_MAX_ROWS", 64)


def _case(rng, starts, T, *, g=4, Hkv=2, D=16, Dv=16, dtype=jnp.float32, ps=PS, npm=NPM):
    """A pool, a table of distinct pages and ``T`` queries a sequence at positions ``start ..``:
    (q, k_pages, v_pages, table, q_pos)."""
    B = len(starts)
    P = 1 + B * npm
    k_pages = jnp.asarray(rng.normal(size=(P, Hkv, ps, D)), dtype)
    v_pages = jnp.asarray(rng.normal(size=(P, Hkv, ps, Dv)), dtype)
    table = jnp.asarray(1 + rng.permutation(B * npm).reshape(B, npm), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Hkv * g, T, D)), dtype)
    q_pos = jnp.asarray(np.asarray(starts)[:, None] + np.arange(T)[None], jnp.int32)
    return q, k_pages, v_pages, table, q_pos


def _decomposition(q, k_pages, v_pages, table, q_pos, window):
    return np.asarray(tt.jit(lambda *a: ltorch.paged_chunk_attention(*a, window=window))(
        q, k_pages, v_pages, table, q_pos), np.float32)


def _kernel(q, k_pages, v_pages, table, q_pos, window):
    return np.asarray(pallasex.paged_chunk_decode(q, k_pages, v_pages, table, q_pos, None, window,
                                                  interpret=True), np.float32)


# a chunk at 0, at a page boundary, one past it, and where the visible pages (10) are no multiple of
# the step; a verify step's or a single query's rows from the first position to the table's last
CHUNK = 32
STARTS = {CHUNK: [0, 32, 33, 44], 5: [0, 7, 8, NPM * PS - 5], 1: [0, 7, 8, NPM * PS - 1]}
HEADS = {"g4": (4, 2, 16), "g1": (1, 4, 16), "g2_v2d_odd_heads": (2, 3, 32), "g4_v2d": (4, 2, 32)}
WINDOWS = {"plain": None, "window_in_a_page": 5, "window_across_pages": 20, "window_past_the_context": 1000}


CASES = ([(h, CHUNK, w) for h in HEADS for w in WINDOWS]
         + [(h, 5, w) for h in ("g4", "g2_v2d_odd_heads") for w in ("plain", "window_in_a_page", "window_across_pages")]
         + [(h, 1, w) for h in ("g4", "g1") for w in ("plain", "window_across_pages")])


@pytest.mark.parametrize("heads,T,window", CASES, ids=[f"{h}-T{T}-{w}" for h, T, w in CASES])
def test_the_kernel_matches_the_gather_decomposition(heads, T, window):
    g, Hkv, Dv = HEADS[heads]
    args = _case(np.random.default_rng(0), STARTS[T], T, g=g, Hkv=Hkv, Dv=Dv)
    q_tile, n_heads, pps = budget.paged_chunk_blocks(PS, 16, g, T, 4, 4, Dv=Dv, n_kv_heads=Hkv)
    assert (q_tile, n_heads, pps) == (min(T, 64 // g), Hkv, 4)
    want, got = _decomposition(*args, WINDOWS[window]), _kernel(*args, WINDOWS[window])
    assert got.shape == (4, Hkv * g, T, Dv)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", ["plain", "window_across_pages"])
@pytest.mark.parametrize("heads", ["g4", "g2_v2d_odd_heads"])
def test_bf16_pools_and_queries_match_the_decomposition(heads, window):
    g, Hkv, Dv = HEADS[heads]
    args = _case(np.random.default_rng(0), STARTS[CHUNK], CHUNK, g=g, Hkv=Hkv, Dv=Dv, dtype=jnp.bfloat16)
    want, got = _decomposition(*args, WINDOWS[window]), _kernel(*args, WINDOWS[window])
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("window", [None, 20], ids=["plain", "window"])
def test_a_sequence_is_bit_identical_alone_and_beside_sequences_at_other_starts(window):
    rng = np.random.default_rng(1)
    q, k_pages, v_pages, table, q_pos = _case(rng, STARTS[CHUNK], CHUNK, dtype=jnp.bfloat16)
    batched = _kernel(q, k_pages, v_pages, table, q_pos, window)
    for b in (1, 3):
        alone = _kernel(q[b:b + 1], k_pages, v_pages, table[b:b + 1], q_pos[b:b + 1], window)
        np.testing.assert_array_equal(alone[0], batched[b], err_msg=f"sequence {b}, start {STARTS[CHUNK][b]}")
    order = np.asarray(rng.permutation(len(STARTS[CHUNK])))
    moved = _kernel(q[order], k_pages, v_pages, table[order], q_pos[order], window)
    np.testing.assert_array_equal(moved, batched[order])


@pytest.mark.parametrize("window", [None, 20], ids=["plain", "window"])
@pytest.mark.parametrize("T", [5, CHUNK], ids=["verify_5", "chunk_32"])
def test_nothing_outside_the_visible_pages_is_read(T, window):
    """Every page no visible table entry names is NaN, and the entries past a sequence's last
    position (and below its first query's window) point at such pages or anywhere else: the output is
    finite and does not move."""
    rng = np.random.default_rng(2)
    starts = STARTS[CHUNK] if T == CHUNK else [0, 7, 8, 100]
    q, k_pages, v_pages, table, q_pos = _case(rng, starts, T)
    want = _kernel(q, k_pages, v_pages, table, q_pos, window)
    table, k_pages, v_pages = np.array(table), np.array(k_pages), np.array(v_pages)
    live = np.zeros(k_pages.shape[0], bool)
    stray = rng.integers(0, k_pages.shape[0], table.shape)
    for b, start in enumerate(starts):
        first = max(start - window + 1, 0) // PS if window else 0
        end = -(-(start + T) // PS)
        live[table[b, first:end]] = True
        table[b, :first], table[b, end:] = stray[b, :first], stray[b, end:]
    k_pages[~live], v_pages[~live] = np.nan, np.nan
    got = _kernel(q, jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(table), q_pos, window)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


def test_the_padding_of_a_final_chunk_does_not_reach_its_real_queries():
    """A final chunk padded to its rung: the engine writes the padding's keys and values into the
    chunk's pages like any token's, and they hold whatever the padding made of them. Queries at real
    positions read the same with them large or zero; the padding's own rows are numbers."""
    rng = np.random.default_rng(3)
    start, T, real = 72, CHUNK, 19
    q, k_pages, v_pages, table, q_pos = _case(rng, [start], T)

    def with_padding(value):
        k, v = np.array(k_pages), np.array(v_pages)
        for pos in range(start + real, start + T):
            k[table[0, pos // PS], :, pos % PS], v[table[0, pos // PS], :, pos % PS] = value, value
        return jnp.asarray(k), jnp.asarray(v)

    clean = _decomposition(q, *with_padding(0.0), table, q_pos, None)
    got = _kernel(q, *with_padding(1e4), table, q_pos, None)
    np.testing.assert_allclose(got[:, :, :real], clean[:, :, :real], atol=2e-5, rtol=2e-5)
    assert np.isfinite(got).all()


def test_a_smaller_budget_takes_fewer_pages_a_step_and_a_smaller_query_tile(monkeypatch):
    """The blocks follow the budget: the same call with 448 KiB and with the default 14 MiB."""
    monkeypatch.setattr(budget, "PAGED_CHUNK_MAX_ROWS", 512)
    monkeypatch.setattr(budget, "PAGED_CHUNK_KEYS_PER_STEP", 64)
    rng = np.random.default_rng(4)
    args = _case(rng, [0, 40, 100, 192], 64, g=4, Hkv=2, D=128, Dv=128, ps=16, npm=16)
    want = _decomposition(*args, None)
    sizes = (16, 128, 4, 64, 4, 4)
    assert budget.paged_chunk_blocks(*sizes, n_kv_heads=2) == (64, 2, 4)
    np.testing.assert_allclose(_kernel(*args, None), want, atol=2e-5, rtol=2e-5)
    monkeypatch.setattr(budget, "paged_vmem_limit", lambda: 448 * 2**10)
    assert budget.paged_chunk_blocks(*sizes, n_kv_heads=2) == (16, 1, 3)
    np.testing.assert_allclose(_kernel(*args, None), want, atol=2e-5, rtol=2e-5)


# -- what the benchmark recognises the call by -------------------------------------------------

def _pallas_calls(window, T):
    B, H, Hkv, D, Dv, ps, npm = 2, 8, 2, 128, 256, 16, 6
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda q, k, v, t, p: pallasex.paged_chunk_decode(
        q, k, v, t, p, None, window, interpret=True))(
        sds((B, H, T, D), jnp.bfloat16), sds((9, Hkv, ps, D), jnp.bfloat16),
        sds((9, Hkv, ps, Dv), jnp.bfloat16), sds((B, npm), jnp.int32), sds((B, T), jnp.int32))
    return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"], (B, Hkv, H // Hkv, D, Dv, ps, npm)


def _hlo_operand(aval):
    kind = {"int32": "s32", "bfloat16": "bf16", "float32": "f32"}[str(aval.dtype)]
    return f"{kind}[{','.join(map(str, aval.shape))}]{{0}} %arg"


@pytest.mark.parametrize("T", [5, 64], ids=["one_tile", "four_tiles"])
@pytest.mark.parametrize("window,classes,name", [(None, "classes.json", "paged_chunk"),
                                                 (512, "classes_sambay.json", "window_chunk")])
def test_the_call_keeps_the_operands_the_kernel_classes_match(window, classes, name, T):
    """One pallas_call a claimed symbol, operands (table s32[B,P], a coverage bound a tile s32[B x
    tiles], with a window each tile's first page s32[B x tiles], queries, query positions s32[B,rows,1],
    K pool, V pool 4-d): what `benchmark/kernels/*.json` read in the HLO text, tried in the order
    `readers.pallas_class` does."""
    calls, (B, Hkv, g, D, Dv, ps, npm) = _pallas_calls(window, T)
    assert len(calls) == 1
    (call,) = calls
    avals = [v.aval for v in call.invars]
    tiles = T // budget.paged_chunk_blocks(ps, D, g, T, 2, 2, Dv=Dv, n_kv_heads=Hkv)[0]
    assert tiles == (1 if T == 5 else 4)
    scalars = [(B, npm), (B * tiles,)] + ([(B * tiles,)] if window else [])
    G, R = (1, g * T) if tiles == 1 else (g, T)
    assert [(a.shape, str(a.dtype)) for a in avals] == (
        [(s, "int32") for s in scalars]
        + [((B, Hkv, G, R, D), "bfloat16"), ((B, g * T, 1), "int32"),
           ((9, Hkv, ps, D), "bfloat16"), ((9, Hkv, ps, Dv), "bfloat16")])
    (out,) = call.outvars
    assert (out.aval.shape, str(out.aval.dtype)) == ((B, Hkv, G, R, Dv), "bfloat16")
    text = (f"%x = bf16[{','.join(map(str, out.aval.shape))}]{{4,3,2,1,0}} "
            f"custom-call({', '.join(_hlo_operand(a) for a in avals)})")
    files = ["classes.json"] + sorted(f for f in os.listdir(os.path.join(ROOT, "benchmark", "kernels"))
                                      if f != "classes.json")
    matched = None
    for f in files:
        with open(os.path.join(ROOT, "benchmark", "kernels", f)) as fh:
            for c in json.load(fh)["classes"]:
                if matched is None and re.search(c["pattern"], text):
                    matched = (f, c["class"])
    assert matched == (classes, name)


# -- the budget --------------------------------------------------------------------------------

class _Proxy:
    def __init__(self, shape, dtype="bfloat16"):
        self.shape, self.ndim, self.dtype = shape, len(shape), dtype


@pytest.fixture
def forced_claim(pallas_claims):
    from thunder_tpu import observability
    observability.enable()
    observability.reset()
    yield observability
    observability.disable()


def test_the_estimate_follows_the_tile_the_heads_and_the_pages(monkeypatch):
    monkeypatch.undo()  # the blocks as served
    est = lambda q_tile, heads, pps, Dv=128: budget.paged_chunk_vmem_bytes(  # noqa: E731
        64, 128, 4, q_tile, 2, 2, heads=heads, pages_per_step=pps, Dv=Dv)
    page = 64 * (128 + 128) * 2  # K and V of one head of one page
    assert est(128, 2, 16) - est(128, 2, 15) == 2 * 2 * page + 4 * 512 * 64 * 4  # two buffers, a step's scores
    assert est(128, 2, 16) - est(128, 1, 16) >= 2 * 16 * page  # a head more: its pages twice over
    assert est(256, 2, 16) > est(128, 2, 16) and est(128, 2, 16, Dv=256) > est(128, 2, 16)
    # the cells' shapes: tiles of 128 queries (512 rows a head), 1,024 keys a step, inside the budget
    assert budget.paged_chunk_blocks(64, 128, 4, 512, 2, 2, n_kv_heads=8) == (128, 2, 16)
    assert budget.paged_chunk_blocks(64, 128, 4, 512, 2, 2, n_kv_heads=10, Dv=256) == (128, 1, 16)
    # a verify step's few rows take every head of a page at once; narrow heads two a row are g = 2
    assert budget.paged_chunk_blocks(64, 128, 4, 5, 2, 2, n_kv_heads=8) == (5, 8, 16)
    assert budget.paged_chunk_blocks(64, 128, 2, 512, 2, 2, n_kv_heads=8) == (256, 2, 16)
    # a T with no divisor that is a multiple of 16 is one tile, if that fits
    assert budget.paged_chunk_blocks(64, 128, 4, 136, 2, 2, n_kv_heads=8)[0] == 136
    for shape in ((64, 128, 4, 512), (64, 128, 4, 5), (16, 128, 4, 128), (64, 128, 1, 512)):
        q_tile, heads, pps = budget.paged_chunk_blocks(*shape, 2, 2, n_kv_heads=8)
        assert budget.within_vmem(budget.paged_chunk_vmem_bytes(*shape[:3], q_tile, 2, 2, heads=heads,
                                                                pages_per_step=pps), budget.paged_vmem_limit())


def test_a_page_of_which_not_one_fits_is_declined_and_counted(forced_claim):
    q, table, q_pos = _Proxy((2, 32, 64, 128)), _Proxy((2, 4), "int32"), _Proxy((2, 64), "int32")
    fits = _Proxy((8, 8, 64, 128))
    huge = _Proxy((8, 8, 32768, 128))  # one page of one head: 8 MiB of K and 8 of V, twice over
    assert budget.paged_chunk_blocks(32768, 128, 4, 64, 2, 2, n_kv_heads=8) == (0, 0, 0)
    assert pallasex.paged_chunk_attention_supported(q, fits, fits, table, q_pos)
    assert not forced_claim.counters().get("pallas.decline.paged_chunk_attention.vmem")
    assert not pallasex.paged_chunk_attention_supported(q, huge, huge, table, q_pos)
    assert forced_claim.counters()["pallas.decline.paged_chunk_attention.vmem"] == 1


def test_a_pool_narrower_than_the_lanes_is_declined_and_counted(forced_claim, monkeypatch):
    """The kernel copies whole pages out of HBM itself, and Mosaic takes such a copy only of rows that
    fill the 128 lanes: on a TPU a 64-wide pool (heads the engine could not pack: serving/runner.py
    heads_a_row) runs the decomposition (the interpreter of a forced claim takes any width)."""
    q, table, q_pos = _Proxy((2, 16, 64, 64)), _Proxy((2, 4), "int32"), _Proxy((2, 64), "int32")
    narrow = _Proxy((8, 16, 64, 64))
    assert pallasex.paged_chunk_attention_supported(q, narrow, narrow, table, q_pos)
    monkeypatch.setattr(pallasex, "_on_tpu", lambda: True)
    assert not pallasex.paged_chunk_attention_supported(q, narrow, narrow, table, q_pos)
    assert forced_claim.counters()["pallas.decline.paged_chunk_attention.lanes"] == 1
    q128, k128 = _Proxy((2, 16, 64, 128)), _Proxy((8, 16, 64, 128))
    assert not pallasex.paged_chunk_attention_supported(q128, k128, _Proxy((8, 16, 64, 192)), table, q_pos)
    assert pallasex.paged_chunk_attention_supported(q128, k128, _Proxy((8, 16, 64, 256)), table, q_pos)


@pytest.mark.parametrize("pools,names", [(((8, 16, 64, 64), (8, 16, 64, 64)), "128 lanes"),
                                         (((8, 8, 32768, 128), (8, 8, 32768, 128)), "VMEM budget")],
                         ids=["lanes", "vmem"])
def test_a_direct_call_the_checker_would_decline_is_refused_by_name(pools, names, monkeypatch):
    """What the checker declines, `paged_chunk_decode` refuses with the constraint's name when the
    kernel is to be compiled, and not with the compiler's error."""
    monkeypatch.setattr(pallasex, "_on_tpu", lambda: True)
    k, v = (jnp.zeros(shape, jnp.bfloat16) for shape in pools)
    q = jnp.zeros((2, 16, 64, k.shape[3]), jnp.bfloat16)
    with pytest.raises(ValueError, match=names):
        pallasex.paged_chunk_decode(q, k, v, jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, 64), jnp.int32))
