"""The page writer (`ltorch.paged_write`, `pallasex.paged_write_rows`): a program's new key and
value rows into both pools of a layer by read-modify-write of the aligned tiles they fall in.

Interpreted on the CPU against the decomposition (one row scatter a pool): the pools come out
bit for bit the same outside the null page, which the kernel never writes. The checker's
declines, the compile for a described v5e at the served cells' pool shapes, and the call's
signature against the benchmark's kernel classes."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from benchmark.lib import harness
from thunder_tpu.analysis import budget
from thunder_tpu.executors import pallasex
from thunder_tpu.ops import ltorch
from thunder_tpu.serving import runner

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

P, PS = 12, 32  # pages in the pool, rows a page


def _where(tables, pos_mat, live=None):
    """page_of and slot_in_page (B, K1) of rows at ``pos_mat`` as the runner finds them
    (`runner._token_pages`): past a table's coverage, or not ``live``, the null page."""
    cover = tables.shape[1] * PS
    page = np.take_along_axis(tables, np.minimum(pos_mat, cover - 1) // PS, 1)
    page = np.where(pos_mat < cover, page, 0)
    if live is not None:
        page = np.where(live, page, 0)
    return page.astype(np.int32), (pos_mat % PS).astype(np.int32)


def _write(claimed: bool, pools, page, slot, rows, monkeypatch):
    """``ltorch.paged_write`` through a fresh ``tt.jit``: the kernel interpreted where the claim is
    forced, else the decomposition."""
    monkeypatch.setattr(pallasex, "_claims_on_platform", lambda: claimed)
    fn = tt.jit(lambda k, v, p, s, kr, vr: ltorch.paged_write(k, v, p, s, kr, vr))
    out = fn(*pools, jnp.asarray(page), jnp.asarray(slot), *rows)
    return [np.asarray(o) for o in out], harness.pallas_claims(tt.last_traces(fn)[-1])["thunder.paged_write"]


def _case(rng, B, K1, Hkv=2, D=128, Dv=128, dtype=jnp.bfloat16):
    pools = (jnp.asarray(rng.normal(size=(P, Hkv, PS, D)), dtype),
             jnp.asarray(rng.normal(size=(P, Hkv, PS, Dv)), dtype))
    rows = (jnp.asarray(rng.normal(size=(B, K1, Hkv, D)), dtype),
            jnp.asarray(rng.normal(size=(B, K1, Hkv, Dv)), dtype))
    return pools, rows


def _same_outside_the_null_page(page, slot, pools, rows, monkeypatch):
    want, unclaimed = _write(False, pools, page, slot, rows, monkeypatch)
    assert unclaimed == 0
    got, calls = _write(True, pools, page, slot, rows, monkeypatch)
    for w, g, before, new in zip(want, got, pools, rows):
        assert g.dtype == w.dtype and np.array_equal(g[1:], w[1:])
        assert np.array_equal(g[0], np.asarray(before)[0])  # the null page is never written
        # and every live row is where it was asked to be
        for b, r in zip(*np.nonzero(page)):
            assert np.array_equal(g[page[b, r], :, slot[b, r]], np.asarray(new)[b, r])
    return calls


TABLES = np.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [10, 11, 0]], np.int32)


def _decode_rows():
    # one row a sequence; slot 1 is idle (a null-page table row, position 0)
    tables = TABLES.copy()
    tables[1] = 0
    return tables, np.array([[5], [0], [40], [63]]), None


def _block_pass(half):
    # two blocks of 4 a slot; the first (nothing to settle) or the second (nothing to denoise) is
    # inactive in some slots, an idle slot has neither; inactive rows carry position 0
    K = 4
    pos = np.array([8, 28, 60, 44])[:, None] + np.arange(2 * K)[None]
    live = np.ones((4, 2), bool)
    live[[0, 2], half] = False
    live[3] = False
    live = np.repeat(live, K, 1)
    return TABLES, np.where(live, pos, 0), live


def _verify_rows():
    # k + 1 = 5 rows a sequence; the last rows of slots 2 and 3 run past their tables
    return TABLES, np.array([3, 30, 94, 62])[:, None] + np.arange(5)[None], None


def _straddling():
    # 8 consecutive rows across a tile (16 bf16 rows) and across a page (32 rows)
    return TABLES, np.array([12, 28, 44, 60])[:, None] + np.arange(8)[None], None


def _window_pool():
    # a window pool: the pages below the window are handed back (null entries in the table)
    tables = TABLES.copy()
    tables[:, 0] = 0
    return tables, np.array([[70], [33], [95], [40]]), None


ROADS = {"decode-rows-idle-slot": _decode_rows,
         "block-pass-first-half-inactive": lambda: _block_pass(0),
         "block-pass-second-half-inactive": lambda: _block_pass(1),
         "verify-rows-past-the-table": _verify_rows,
         "rows-across-a-tile-and-a-page": _straddling,
         "window-pool": _window_pool}


@pytest.mark.parametrize("road", list(ROADS))
def test_the_writer_gives_the_decomposition_s_pools_outside_the_null_page(road, monkeypatch):
    tables, pos, live = ROADS[road]()
    page, slot = _where(tables, pos, live)
    rng = np.random.default_rng(len(road))
    pools, rows = _case(rng, *pos.shape)
    assert _same_outside_the_null_page(page, slot, pools, rows, monkeypatch) == 1


def test_head_packed_pools_take_their_rows_two_heads_a_row(monkeypatch):
    """Heads of 64 cached two a 128-lane row (`runner.heads_a_row`, as models/sambay.py caches a
    pair): the rows the engine hands the writer are the packed ones."""
    rng = np.random.default_rng(3)
    B, Hkv, T, hs = 4, 4, 1, 64
    r = runner.heads_a_row(Hkv, hs)
    assert r == 2
    k, v = (jnp.asarray(rng.normal(size=(B, Hkv, T, hs)), jnp.bfloat16) for _ in range(2))
    rows = tuple(jnp.transpose(jnp.asarray(runner._pack_heads(x, r)), (0, 2, 1, 3)) for x in (k, v))
    assert rows[0].shape == (B, T, Hkv // r, r * hs)
    pools = tuple(jnp.asarray(rng.normal(size=(P, Hkv // r, PS, r * hs)), jnp.bfloat16)
                  for _ in range(2))
    tables, pos, _ = _decode_rows()
    page, slot = _where(tables, pos)
    assert _same_outside_the_null_page(page, slot, pools, rows, monkeypatch) == 1


def test_values_wider_than_keys_and_float32_tiles(monkeypatch):
    """Values twice the keys' width (a differential pair's two value heads); float32 pools, whose
    tile is 8 rows: 8 rows a sequence still fit two tiles."""
    rng = np.random.default_rng(5)
    tables, pos, _ = _straddling()
    page, slot = _where(tables, pos)
    for dtype, Dv in ((jnp.bfloat16, 256), (jnp.float32, 128)):
        pools, rows = _case(rng, *pos.shape, Dv=Dv, dtype=dtype)
        assert _same_outside_the_null_page(page, slot, pools, rows, monkeypatch) == 1


def test_a_program_of_several_sequences_each(monkeypatch):
    """Where not every sequence fits one program the grid takes several, each its own share."""
    monkeypatch.setattr(budget, "paged_vmem_limit", lambda: budget.paged_write_vmem_bytes(
        2, 8, 2, 128, 128, 2))
    assert budget.paged_write_seqs_per_step(4, 8, 2, 128, 128, 2) == 2
    tables, pos, _ = _straddling()
    page, slot = _where(tables, pos)
    pools, rows = _case(np.random.default_rng(7), *pos.shape)
    assert _same_outside_the_null_page(page, slot, pools, rows, monkeypatch) == 1


# -- the checker -----------------------------------------------------------------------------------

class _Proxy:
    def __init__(self, shape, dtype="bfloat16"):
        self.shape, self.ndim, self.dtype = shape, len(shape), dtype


def _operands(B=4, K1=1, Hkv=8, ps=64, D=128, Dv=None, dtype="bfloat16"):
    Dv = D if Dv is None else Dv
    return (_Proxy((9, Hkv, ps, D), dtype), _Proxy((9, Hkv, ps, Dv), dtype),
            _Proxy((B, K1), "int32"), _Proxy((B, K1), "int32"),
            _Proxy((B, K1, Hkv, D), dtype), _Proxy((B, K1, Hkv, Dv), dtype))


@pytest.fixture
def forced_claim(pallas_claims):
    from thunder_tpu import observability
    observability.enable()
    observability.reset()
    yield observability
    observability.disable()


def test_the_served_shapes_are_claimed_with_no_decline(forced_claim, monkeypatch):
    monkeypatch.setattr(pallasex, "_on_tpu", lambda: True)
    for B, K1, Hkv in ((48, 1, 8), (64, 8, 4), (32, 1, 10), (4, 5, 8)):
        assert pallasex.paged_write_supported(*_operands(B, K1, Hkv))
    assert not {k for k in forced_claim.counters() if k.startswith("pallas.decline.paged_write")}
    # off the chip nothing is claimed, and nothing is counted
    monkeypatch.setattr(pallasex, "_claims_on_platform", lambda: False)
    assert not pallasex.paged_write_supported(*_operands())


@pytest.mark.parametrize("reason", ["lanes", "rows", "vmem"])
def test_what_the_writer_cannot_take_is_declined_and_counted(forced_claim, monkeypatch, reason):
    """Rows narrower than the lanes on the chip (the interpreter takes them), more rows a sequence
    than one tile holds (they could span three tiles), not one sequence's tiles in the budget."""
    monkeypatch.setattr(pallasex, "_on_tpu", lambda: True)
    ops = {"lanes": _operands(D=64), "rows": _operands(K1=17),
           "vmem": _operands(Hkv=64, D=4096)}[reason]
    assert not pallasex.paged_write_supported(*ops)
    assert forced_claim.counters()[f"pallas.decline.paged_write.{reason}"] == 1
    if reason == "lanes":  # interpreted, a narrow pool is claimed
        monkeypatch.setattr(pallasex, "_on_tpu", lambda: False)
        assert pallasex.paged_write_supported(*ops)


def test_pools_and_rows_that_do_not_agree_are_not_claimed(forced_claim):
    ops = list(_operands())
    assert not pallasex.paged_write_supported(*ops[:4], _Proxy((4, 1, 8, 128), "float32"), ops[5])
    assert not pallasex.paged_write_supported(*_operands(ps=24))  # a page of no whole tiles
    assert not pallasex.paged_write_supported(ops[0], ops[1], _Proxy((4,), "int32"), *ops[3:])
    assert not {k for k in forced_claim.counters() if k.startswith("pallas.decline")}


def test_a_direct_call_the_checker_would_decline_is_refused_by_name():
    kr = jnp.zeros((2, 1, 2, 64), jnp.bfloat16)
    pool = jnp.zeros((4, 2, 32, 64), jnp.bfloat16)
    idx = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match="128 lanes"):
        pallasex.paged_write_rows(pool, pool, idx, idx, kr, kr, interpret=False)


def test_the_estimate_counts_two_tiles_a_sequence_and_its_rows():
    tile = 8 * 16 * (128 + 128) * 2  # a tile of every head of both pools, bf16
    one = budget.paged_write_vmem_bytes(1, 1, 8, 128, 128, 2)
    assert one == 2 * tile + 2 * 8 * (128 + 128) * 2 * 2  # rows: 8 of 16 sublanes, padded
    assert budget.paged_write_vmem_bytes(2, 1, 8, 128, 128, 2) == 2 * one
    assert budget.paged_write_tile_rows(2) == 16 and budget.paged_write_tile_rows(4) == 8
    # the served cells: every sequence in one program
    assert budget.paged_write_seqs_per_step(48, 1, 8, 128, 128, 2) == 48
    assert budget.paged_write_seqs_per_step(64, 8, 4, 128, 128, 2) == 64
    assert budget.paged_write_seqs_per_step(32, 1, 10, 128, 128, 2) == 32
    assert budget.paged_write_seqs_per_step(4, 1, 64, 4096, 4096, 2) == 0


# -- the v5e's compiler, with no chip ----------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


CELLS = {"chat-48-slots": (1537, 8, 48, 1), "denoise-64-slots-x-two-blocks": (2049, 4, 64, 8),
         "reasoning-32-slots": (297, 10, 32, 1)}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_writer_compiles_for_the_v5e_in_place(one_chip, cell):
    """Mosaic takes the tile copies and the patch at the served cells' pools, and with the pools
    given up (as the serving programs give them) XLA aliases them to the call's results: no copy of
    a pool in the executable, which would cost more than the scatter it replaces."""
    P_, Hkv, B, K1 = CELLS[cell]
    bf = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):
        compiled = jax.jit(lambda k, v, p, s, kr, vr: pallasex.paged_write_rows(k, v, p, s, kr, vr, interpret=False),
                           donate_argnums=(0, 1)).lower(
            sds((P_, Hkv, 64, 128), bf), sds((P_, Hkv, 64, 128), bf), sds((B, K1), jnp.int32),
            sds((B, K1), jnp.int32), sds((B, K1, Hkv, 128), bf), sds((B, K1, Hkv, 128), bf)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    pool = f"bf16[{P_},{Hkv},64,128]"
    assert not [line for line in text.splitlines() if pool in line and re.search(r"\bcopy(-start)?\(", line)]


# -- what the benchmark recognises the call by -------------------------------------------------------

def _hlo_operand(aval):
    kind = {"int32": "s32", "bfloat16": "bf16", "float32": "f32"}[str(aval.dtype)]
    return f"{kind}[{','.join(map(str, aval.shape))}]{{0}} %arg"


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_call_matches_no_kernel_class_of_the_benchmark(cell):
    """The writer is a Mosaic call of a signature of its own (tables s32[B x K1] twice, rows 3-d,
    pools 4-d, two results): `readers.pallas_class` calls it unclassified, so no roofline of the
    paged attention kernels takes its time."""
    from benchmark.lib import readers

    P_, Hkv, B, K1 = CELLS[cell]
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda k, v, p, s, kr, vr: pallasex.paged_write_rows(k, v, p, s, kr, vr, interpret=True))(
        sds((P_, Hkv, 64, 128), jnp.bfloat16), sds((P_, Hkv, 64, 128), jnp.bfloat16),
        sds((B, K1), jnp.int32), sds((B, K1), jnp.int32),
        sds((B, K1, Hkv, 128), jnp.bfloat16), sds((B, K1, Hkv, 128), jnp.bfloat16))
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    avals = [v.aval for v in call.invars]
    assert [(a.shape, str(a.dtype)) for a in avals] == (
        [((B * K1,), "int32")] * 2 + [((B, K1 * Hkv, 128), "bfloat16")] * 2
        + [((P_, Hkv, 64, 128), "bfloat16")] * 2)
    results = ", ".join(f"bf16[{P_},{Hkv},64,128]{{3,2,1,0}}" for _ in call.outvars)
    text = (f"%x = ({results}) custom-call({', '.join(_hlo_operand(a) for a in avals)}), "
            f'custom_call_target="tpu_custom_call"')
    assert readers.pallas_class(ROOT, text) == "unclassified"
