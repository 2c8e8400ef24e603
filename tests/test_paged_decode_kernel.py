"""The paged decode kernel (executors/pallasex.py: one grid program a sequence, a loop over its live
pages, several a step) in interpret mode on the CPU: against the ``ltorch.paged_attention`` gather
decomposition, row by row alone and batched, with everything outside the live range poisoned; the
operand list the benchmark's kernel classes recognise the call by; the VMEM estimate, the block of
pages it gives and the checker's declines; the engine's counters of pages walked and spanned."""
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
PS, NPM = 8, 20  # a table of 20 pages of 8: two and a half steps of 8 pages


def _case(rng, lens, *, g=4, Hkv=2, D=16, Dv=16, dtype=jnp.float32, ps=PS, npm=NPM):
    """A pool, a table that owns distinct pages up to each length and the null page past it, and
    queries: (q, k_pages, v_pages, table, lens)."""
    B = len(lens)
    P = 1 + B * npm
    k_pages = jnp.asarray(rng.normal(size=(P, Hkv, ps, D)), dtype)
    v_pages = jnp.asarray(rng.normal(size=(P, Hkv, ps, Dv)), dtype)
    table = np.zeros((B, npm), np.int32)
    perm = 1 + rng.permutation(B * npm).reshape(B, npm)
    for b, n in enumerate(lens):
        table[b, :-(-n // ps)] = perm[b, :-(-n // ps)]
    q = jnp.asarray(rng.normal(size=(B, Hkv * g, D)), dtype)
    return q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(lens, jnp.int32)


def _decomposition(q, k_pages, v_pages, table, lens, window):
    return np.asarray(tt.jit(lambda *a: ltorch.paged_attention(*a, window=window))(
        q, k_pages, v_pages, table, lens), np.float32)


def _kernel(q, k_pages, v_pages, table, lens, window):
    return np.asarray(pallasex.paged_attention_decode(q, k_pages, v_pages, table, lens, None, window,
                                                      interpret=True), np.float32)


# lengths: an idle slot on the null page, inside the first page, on a page boundary, one past it,
# a whole step of 8 pages, one page more (not a multiple of pages_per_step), the full table
LENS = [1, 5, 16, 17, 64, 72, 121, NPM * PS]


HEADS = {"g4": (4, 2, 16), "g1": (1, 4, 16), "g4_v2d": (4, 2, 32), "g1_v2d_odd_heads": (1, 3, 32),
         "g2_six_heads": (2, 6, 16)}


@pytest.mark.parametrize("window", [None, 5, 20, 1000],
                         ids=["plain", "window_in_a_page", "window_across_pages", "window_past_the_sequence"])
@pytest.mark.parametrize("heads,dtype", [(h, jnp.float32) for h in HEADS]
                         + [("g4", jnp.bfloat16), ("g1_v2d_odd_heads", jnp.bfloat16)],
                         ids=lambda v: v if isinstance(v, str) else v.__name__)
def test_the_kernel_matches_the_gather_decomposition(heads, dtype, window):
    g, Hkv, Dv = HEADS[heads]
    rng = np.random.default_rng(0)
    args = _case(rng, LENS, g=g, Hkv=Hkv, Dv=Dv, dtype=dtype)
    assert budget.paged_pages_per_step(PS, 16, g, args[1].dtype.itemsize, args[0].dtype.itemsize,
                                       Dv=Dv, n_kv_heads=Hkv) == 8
    want, got = _decomposition(*args, window), _kernel(*args, window)
    assert got.shape == (len(LENS), Hkv * g, Dv)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [None, 20], ids=["plain", "window"])
def test_a_row_is_bit_identical_alone_and_beside_rows_of_other_lengths(window):
    rng = np.random.default_rng(1)
    q, k_pages, v_pages, table, lens = _case(rng, LENS, dtype=jnp.bfloat16)
    batched = _kernel(q, k_pages, v_pages, table, lens, window)
    for b in range(len(LENS)):
        alone = _kernel(q[b:b + 1], k_pages, v_pages, table[b:b + 1], lens[b:b + 1], window)
        np.testing.assert_array_equal(alone[0], batched[b], err_msg=f"row {b}, length {LENS[b]}")
    # and in another slot of another batch: the order of the reduction is the row's own
    order = np.asarray(rng.permutation(len(LENS)))
    moved = _kernel(q[order], k_pages, v_pages, table[order], lens[order], window)
    np.testing.assert_array_equal(moved, batched[order])


@pytest.mark.parametrize("window", [None, 20], ids=["plain", "window"])
def test_nothing_outside_the_live_pages_is_read(window):
    """Every page no live table entry names is NaN, the entries past a sequence (and below its
    window) point at such pages or anywhere else: the output is finite and does not move."""
    rng = np.random.default_rng(2)
    q, k_pages, v_pages, table, lens = _case(rng, LENS)
    want = _kernel(q, k_pages, v_pages, table, lens, window)
    table, k_pages, v_pages = np.array(table), np.array(k_pages), np.array(v_pages)
    live = np.zeros(k_pages.shape[0], bool)
    stray = rng.integers(0, k_pages.shape[0], table.shape)
    for b, n in enumerate(LENS):
        first = max(n - window, 0) // PS if window else 0
        end = -(-n // PS)
        live[table[b, first:end]] = True
        table[b, :first], table[b, end:] = stray[b, :first], stray[b, end:]
    k_pages[~live], v_pages[~live] = np.nan, np.nan
    got = _kernel(q, jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(table), lens, window)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


def test_a_pool_too_large_for_eight_pages_takes_fewer_a_step(monkeypatch):
    """The block follows the budget: the same call with 256 KiB and with the default 14 MiB."""
    rng = np.random.default_rng(3)
    args = _case(rng, [1, 40, 100, 256], g=4, Hkv=2, D=128, Dv=128, ps=16, npm=16)
    want = _decomposition(*args, None)
    sizes = (16, 128, 4, 4, 4)
    assert budget.paged_pages_per_step(*sizes, n_kv_heads=2) == 8
    monkeypatch.setattr(budget, "paged_vmem_limit", lambda: 2**18)
    assert budget.paged_pages_per_step(*sizes, n_kv_heads=2) == 3
    got = _kernel(*args, None)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# -- what the benchmark recognises the call by -------------------------------------------------

def _pallas_calls(window):
    B, H, Hkv, D, Dv, ps, npm = 4, 8, 2, 128, 256, 16, 6
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda q, k, v, t, n: pallasex.paged_attention_decode(
        q, k, v, t, n, None, window, interpret=True))(
        sds((B, H, D), jnp.bfloat16), sds((9, Hkv, ps, D), jnp.bfloat16),
        sds((9, Hkv, ps, Dv), jnp.bfloat16), sds((B, npm), jnp.int32), sds((B,), jnp.int32))
    return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"], (B, Hkv, H // Hkv, D, Dv, ps, npm)


def _hlo_operand(aval):
    kind = {"int32": "s32", "bfloat16": "bf16", "float32": "f32"}[str(aval.dtype)]
    return f"{kind}[{','.join(map(str, aval.shape))}]{{0}} %arg"


@pytest.mark.parametrize("window,classes,name", [(None, "classes.json", "paged_decode"),
                                                 (512, "classes_sambay.json", "window_decode")])
def test_the_call_keeps_the_operands_the_kernel_classes_match(window, classes, name):
    """One pallas_call a claimed symbol, operands (table s32[B,P], lengths s32[B], with a window
    the first page s32[B], queries [B,Hkv,g,D], K pool, V pool 4-d), output (B, Hkv, g, Dv): what
    `benchmark/kernels/*.json` read in the HLO text, tried in the order `readers.pallas_class` does."""
    calls, (B, Hkv, g, D, Dv, ps, npm) = _pallas_calls(window)
    assert len(calls) == 1
    (call,) = calls
    avals = [v.aval for v in call.invars]
    scalars = [(B, npm), (B,)] + ([(B,)] if window else [])
    assert [(a.shape, str(a.dtype)) for a in avals] == (
        [(s, "int32") for s in scalars]
        + [((B, Hkv, g, D), "bfloat16"), ((9, Hkv, ps, D), "bfloat16"), ((9, Hkv, ps, Dv), "bfloat16")])
    assert [(v.aval.shape, str(v.aval.dtype)) for v in call.outvars] == [((B, Hkv, g, Dv), "bfloat16")]
    text = f"%x = bf16[{B},{Hkv},{g},{Dv}]{{3,2,1,0}} custom-call({', '.join(_hlo_operand(a) for a in avals)})"
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


def test_the_estimate_follows_blocks_of_whole_pages_double_buffered():
    one = budget.paged_decode_vmem_bytes(64, 128, 4, 2, 2, n_kv_heads=8, pages_per_step=1)
    eight = budget.paged_decode_vmem_bytes(64, 128, 4, 2, 2, n_kv_heads=8, pages_per_step=8)
    page = 8 * 64 * (128 + 128) * 2  # K and V of every head of one page
    assert eight - one >= 2 * 7 * page          # two buffers a page more
    assert eight - one < 2 * 7 * page + 2**20   # and beside them only the scores of a head block
    assert budget.paged_decode_vmem_bytes(64, 128, 4, 2, 2, Dv=256, n_kv_heads=8, pages_per_step=8) > eight
    # the cells' shapes take eight pages a step inside the budget
    for Hkv in (8, 10):
        assert budget.paged_pages_per_step(64, 128, 4, 2, 2, n_kv_heads=Hkv) == 8
    assert budget.paged_head_block(8, 4) == 2 and budget.paged_head_block(10, 4) == 2
    assert budget.paged_head_block(16, 1) == 8 and budget.paged_head_block(3, 1) == 3
    assert budget.paged_head_block(4, 8) == 1


def test_a_page_of_which_not_one_fits_is_declined_and_counted(forced_claim):
    q, table, lens = _Proxy((2, 32, 128)), _Proxy((2, 4), "int32"), _Proxy((2,), "int32")
    fits = _Proxy((8, 8, 64, 128))
    huge = _Proxy((8, 8, 4096, 128))  # one page: 8 MiB of K and 8 of V, twice over
    assert budget.paged_pages_per_step(4096, 128, 4, 2, 2, n_kv_heads=8) == 0
    assert pallasex.paged_attention_supported(q, fits, fits, table, lens)
    assert not forced_claim.counters().get("pallas.decline.paged_attention.vmem")
    assert not pallasex.paged_attention_supported(q, huge, huge, table, lens)
    assert forced_claim.counters()["pallas.decline.paged_attention.vmem"] == 1


def test_a_pool_narrower_than_the_lanes_is_declined_and_counted(forced_claim, monkeypatch):
    """The kernel copies whole pages out of HBM itself, and Mosaic takes such a copy only of rows
    that fill the 128 lanes: on a TPU a 64-wide pool (heads the engine could not pack:
    serving/runner.py heads_a_row) runs the decomposition (the interpreter of a forced claim takes
    any width)."""
    q, table, lens = _Proxy((2, 16, 64)), _Proxy((2, 4), "int32"), _Proxy((2,), "int32")
    narrow = _Proxy((8, 16, 64, 64))
    assert pallasex.paged_attention_supported(q, narrow, narrow, table, lens)
    monkeypatch.setattr(pallasex, "_on_tpu", lambda: True)
    assert not pallasex.paged_attention_supported(q, narrow, narrow, table, lens)
    assert forced_claim.counters()["pallas.decline.paged_attention.lanes"] == 1
    wide_v = _Proxy((8, 16, 64, 192))
    q128, k128 = _Proxy((2, 16, 128)), _Proxy((8, 16, 64, 128))
    assert not pallasex.paged_attention_supported(q128, k128, wide_v, table, lens)
    assert pallasex.paged_attention_supported(q128, k128, _Proxy((8, 16, 64, 256)), table, lens)


@pytest.mark.parametrize("pools,names", [(((8, 16, 64, 64), (8, 16, 64, 64)), "128 lanes"),
                                         (((8, 8, 4096, 128), (8, 8, 4096, 128)), "VMEM budget")],
                         ids=["lanes", "vmem"])
def test_a_direct_call_the_checker_would_decline_is_refused_by_name(pools, names, monkeypatch):
    """What the checker declines, `paged_attention_decode` refuses with the constraint's name when
    the kernel is to be compiled, and not with the compiler's error."""
    monkeypatch.setattr(pallasex, "_on_tpu", lambda: True)
    k, v = (jnp.zeros(shape, jnp.bfloat16) for shape in pools)
    q = jnp.zeros((2, 16, k.shape[3]), jnp.bfloat16)
    with pytest.raises(ValueError, match=names):
        pallasex.paged_attention_decode(q, k, v, jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32))
