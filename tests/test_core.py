"""Core IR tests: traces, symbols, proxies, passes, caching, prologues.

Counterpart of reference thunder/tests/test_core.py (SURVEY.md §4.4)."""
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.core import dtypes, prims
from thunder_tpu.core.proxies import TensorProxy, NumberProxy
from thunder_tpu.core.trace import TraceCtx, tracectx
from thunder_tpu.core.transform_common import cse, dce, flatten_to_prims
from thunder_tpu.ops import clang, ltorch


def make_proxy(shape, dtype=dtypes.float32):
    return TensorProxy(shape=shape, dtype=dtype)


class TestTraceConstruction:
    def test_record_and_print(self):
        trc = TraceCtx(None)
        with tracectx(trc):
            a = make_proxy((2, 3))
            b = make_proxy((2, 3))
            c = prims.add(a, b)
            prims.python_return(c)
        trc.args = (a, b)
        src = trc.python()
        assert "prims.add" in src
        assert "return" in src
        assert len(trc.bound_symbols) == 2

    def test_subsymbol_hierarchy(self):
        trc = TraceCtx(None)
        with tracectx(trc):
            a = make_proxy((4,))
            out = ltorch.softmax(a, 0)
            prims.python_return(out)
        trc.args = (a,)
        top = trc.bound_symbols[0]
        assert top.sym.name == "softmax"
        assert len(top.subsymbols) > 0
        flat = flatten_to_prims(trc)
        assert all(b.sym.is_prim for b in flat.bound_symbols)

    def test_unique_names(self):
        trc = TraceCtx(None)
        with tracectx(trc):
            ps = [make_proxy((1,)) for _ in range(100)]
        assert len({p.name for p in ps}) == 100


class TestPasses:
    def _trace_with_dead_code(self):
        trc = TraceCtx(None)
        with tracectx(trc):
            a = make_proxy((2,))
            live = prims.add(a, a)
            dead = prims.mul(a, a)  # noqa: F841 — dead
            prims.python_return(live)
        trc.args = (a,)
        return trc

    def test_dce(self):
        trc = self._trace_with_dead_code()
        out = dce(trc)
        names = [b.sym.name for b in out.bound_symbols]
        assert "mul" not in names
        assert "add" in names

    def test_cse(self):
        trc = TraceCtx(None)
        with tracectx(trc):
            a = make_proxy((2,))
            x = prims.add(a, a)
            y = prims.add(a, a)
            z = prims.mul(x, y)
            prims.python_return(z)
        trc.args = (a,)
        out = cse(trc)
        adds = [b for b in out.bound_symbols if b.sym.name == "add"]
        assert len(adds) == 1

    def test_dont_dce_random(self):
        trc = TraceCtx(None)
        with tracectx(trc):
            a = make_proxy((2,))
            prims.python_return(prims.add(a, a))
        trc.args = (a,)
        assert len(dce(trc).bound_symbols) == 2


class TestMetaFunctions:
    def test_matmul_meta_batched(self):
        with tracectx(TraceCtx(None)):
            a = make_proxy((7, 2, 3))
            b = make_proxy((1, 3, 5))
            out = prims.matmul(a, b)
        assert out.shape == (7, 2, 5)

    def test_matmul_meta_vec(self):
        with tracectx(TraceCtx(None)):
            a = make_proxy((3,))
            b = make_proxy((3, 5))
            assert prims.matmul(a, b).shape == (5,)

    def test_broadcast_shapes(self):
        assert clang.compute_broadcast_shape((2, 1, 3), (4, 3)) == (2, 4, 3)
        with pytest.raises(Exception):
            clang.compute_broadcast_shape((2,), (3,))

    def test_reduction_meta(self):
        with tracectx(TraceCtx(None)):
            a = make_proxy((2, 3, 4))
            assert prims.sum_prim(a, (1,)).shape == (2, 4)
            assert prims.amax(a, (0, 2)).shape == (3,)

    def test_slice_meta(self):
        with tracectx(TraceCtx(None)):
            a = make_proxy((10, 8))
            out = prims.slice_prim(a, (2, 0), (8, 8), (2, 1))
            assert out.shape == (3, 8)

    def test_conv_meta(self):
        with tracectx(TraceCtx(None)):
            a = make_proxy((1, 3, 32, 32))
            w = make_proxy((16, 3, 3, 3))
            out = prims.convolution(a, w, None, (1, 1), (1, 1), (1, 1), 1)
            assert out.shape == (1, 16, 32, 32)

    def test_elementwise_shape_mismatch_raises(self):
        with tracectx(TraceCtx(None)):
            a = make_proxy((2, 3))
            b = make_proxy((3, 2))
            with pytest.raises(Exception):
                prims.add(a, b)


class TestTypePromotion:
    def test_promote(self):
        assert dtypes.promote_dtypes(dtypes.int32, dtypes.float32) == dtypes.float32
        assert dtypes.promote_dtypes(dtypes.bfloat16, dtypes.float32) == dtypes.float32
        assert dtypes.promote_dtypes(dtypes.bfloat16, dtypes.float16) == dtypes.float32
        assert dtypes.promote_dtypes(dtypes.int8, dtypes.int32) == dtypes.int32
        assert dtypes.promote_dtypes(dtypes.bool8, dtypes.bool8) == dtypes.bool8

    def test_weak_scalars(self):
        # python float + int tensor -> float32 result dtype at clang level
        assert dtypes.promote_dtypes(dtypes.bfloat16, float) == dtypes.bfloat16
        assert dtypes.promote_dtypes(dtypes.int32, bool) == dtypes.int32


class TestJitCaching:
    def test_cache_hit_and_miss(self):
        calls = []

        def f(x):
            calls.append(1)
            return x * 2.0

        cf = tt.jit(f)
        x = jnp.ones((2, 2), jnp.float32)
        cf(x)
        cf(x)
        assert cf.cache_hits == 1 and cf.cache_misses == 1
        assert len(calls) == 1  # traced once
        cf(jnp.ones((3, 3), jnp.float32))  # new shape -> retrace
        assert cf.cache_misses == 2

    def test_prologue_validates(self):
        def f(x):
            return x + 1.0

        cf = tt.jit(f)
        out = cf(jnp.zeros((2,), jnp.float32))
        np.testing.assert_allclose(np.asarray(out), [1.0, 1.0])

    def test_static_number_respecialization(self):
        def f(x, n):
            return x * n

        cf = tt.jit(f)
        a = jnp.ones((2,), jnp.float32)
        np.testing.assert_allclose(np.asarray(cf(a, 2.0)), [2.0, 2.0])
        np.testing.assert_allclose(np.asarray(cf(a, 3.0)), [3.0, 3.0])
        assert cf.cache_misses == 2

    def test_last_traces(self):
        cf = tt.jit(lambda x: x + x)
        cf(jnp.ones((2,)))
        trcs = tt.last_traces(cf)
        assert len(trcs) >= 2
        assert "def" in trcs[-1].python()


class TestNumberProxy:
    def test_static_arithmetic(self):
        n = NumberProxy(3, int, name="n_test")
        assert n + 1 == 4
        assert n * 2 == 6
        assert int(n) == 3
        assert bool(NumberProxy(0, int, name="n_t2")) is False


class TestCheckTrace:
    """check_trace invariants (reference dev_utils/check_trace.py:23 +
    the in-place-into-fusion sanity check, transform_common.py:68)."""

    def _trace(self, fn, *args):
        cf = tt.jit(fn, disable_fusion=True)
        cf(*args)
        return tt.last_traces(cf)[-1]

    def test_valid_trace_passes(self, rng):
        from thunder_tpu.utils.check_trace import check_trace

        trc = self._trace(lambda x: ltorch.sum(ltorch.relu(x) * 2.0),
                          jnp.ones((3, 3)))
        check_trace(trc)

    def test_use_after_del_detected(self, rng):
        from thunder_tpu.core.prims import python_del
        from thunder_tpu.core.symbol import BoundSymbol
        from thunder_tpu.utils.check_trace import TraceCheckError, check_trace

        trc = self._trace(lambda x: ltorch.sum(ltorch.relu(x) * 2.0), jnp.ones((3, 3)))
        # find a proxy consumed by a later bsym and DEL it right before
        bsyms = list(trc.bound_symbols)
        target = None
        for i, b in enumerate(bsyms):
            for p in b.flat_proxy_args():
                target = (i, p)
                break
            if target:
                break
        i, p = target
        bsyms.insert(i, BoundSymbol(python_del, (p,), {}, None))
        from thunder_tpu.core.trace import from_trace

        bad = from_trace(trc)
        bad.bound_symbols = bsyms
        with pytest.raises(TraceCheckError, match="deleted|undefined"):
            check_trace(bad)

    def test_metadata_change_detected(self, rng):
        from thunder_tpu.core.proxies import TensorProxy
        from thunder_tpu.core import dtypes as dt
        from thunder_tpu.utils.check_trace import TraceCheckError, check_trace
        from thunder_tpu.core.trace import from_trace

        trc = self._trace(lambda x: ltorch.sum(x * 2.0), jnp.ones((3, 3)))
        bad = from_trace(trc)
        bsyms = list(trc.bound_symbols)
        # corrupt: replace an intermediate's shape in a later consumer
        for i, b in enumerate(bsyms):
            outs = b.flat_proxy_outs()
            if outs and isinstance(outs[0], TensorProxy) and outs[0].ndim == 2:
                clone = TensorProxy(outs[0].name, shape=(7, 7), dtype=outs[0].dtype,
                                    device=outs[0].device)
                for j in range(i + 1, len(bsyms)):
                    if any(p.name == outs[0].name for p in bsyms[j].flat_proxy_args()):
                        nb = bsyms[j]
                        new_args = tuple(clone if (isinstance(a, TensorProxy) and a.name == clone.name) else a
                                         for a in nb.args)
                        bsyms[j] = nb.replace(args=new_args)
                        bad.bound_symbols = bsyms
                        with pytest.raises(TraceCheckError, match="metadata"):
                            check_trace(bad)
                        return
        pytest.skip("no suitable intermediate found")


class TestPrologueParamGuards:
    """VERDICT round-1 weak #5: captured module params must be re-validated.
    On this stack params/buffers ride as explicit prologue-checked inputs, so
    metadata drift retraces (new cache entry) instead of silently reusing a
    stale program; the prologue rejects wrong-metadata inputs loudly."""

    def test_param_dtype_drift_recompiles(self, rng):
        from thunder_tpu import nn

        m = nn.Linear(4, 4, seed=0)
        tm = tt.jit(m)
        x = jnp.ones((2, 4), jnp.float32)
        tm(x)
        misses0 = tm._cfn.cache_misses
        m.weight.data = m.weight.data.astype(jnp.bfloat16)  # optimizer/quant swap
        out = tm(x)
        assert tm._cfn.cache_misses == misses0 + 1  # retraced, not stale
        assert out.dtype in (jnp.float32, jnp.bfloat16)

    def test_param_shape_drift_recompiles(self, rng):
        from thunder_tpu import nn

        m = nn.Linear(4, 4, seed=0)
        tm = tt.jit(m)
        x = jnp.ones((2, 4), jnp.float32)
        tm(x)
        misses0 = tm._cfn.cache_misses
        m.weight.data = jnp.ones((8, 4), jnp.float32)
        with pytest.raises(Exception):
            tm(x)  # shape mismatch surfaces (matmul meta), never silent reuse
        assert tm._cfn.cache_misses == misses0 + 1

    def test_prologue_rejects_wrong_metadata_inputs(self, rng):
        def f(x):
            return ltorch.sum(x * 2.0)

        cf = tt.jit(f)
        cf(jnp.ones((3, 3), jnp.float32))
        entry = next(iter(cf._cache.values()))
        with pytest.raises(Exception, match="shape|dtype|metadata|check"):
            entry.prologue_fn(jnp.ones((2, 2), jnp.float32))


def test_inplace_into_fusion_detected(rng):
    """A fusion consuming a tensor later mutated in place must be flagged
    (reference _inplace_copy_sanity_check, transform_common.py:68)."""
    from thunder_tpu.core import prims as P
    from thunder_tpu.core.proxies import TensorProxy
    from thunder_tpu.core.symbol import BoundSymbol, Symbol
    from thunder_tpu.core.trace import TraceCtx
    from thunder_tpu.utils.check_trace import TraceCheckError, check_inplace_into_fusion
    from thunder_tpu.core import dtypes as dt

    trc = TraceCtx(None)
    a = TensorProxy("a", shape=(4,), dtype=dt.float32, device=None)
    out = TensorProxy("t_out", shape=(4,), dtype=dt.float32, device=None)
    fused_sym = Symbol("xla_fusion_0", lambda *x: out, id="xla.fusion0", module="xla")
    trc.args = (a,)
    mutated = TensorProxy("a2", shape=(4,), dtype=dt.float32, device=None)
    copy_sym = Symbol("copy_with_setitem", lambda *x: mutated, id=P.PrimIDs.COPY_WITH_SETITEM)
    trc.bound_symbols = [
        BoundSymbol(fused_sym, (a,), {}, out),
        BoundSymbol(copy_sym, (a, 0, 1.0), {}, mutated),
    ]
    with pytest.raises(TraceCheckError, match="in-place"):
        check_inplace_into_fusion(trc)


def test_getitem_list_index(rng):
    """x[[0, 2]] advanced indexing with a Python list (review r3 finding)."""
    import jax.numpy as jnp

    from thunder_tpu.ops import clang

    x = jnp.asarray(rng.randn(3, 4).astype("float32"))
    out = tt.jit(lambda a: clang.getitem(a, [0, 2]))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x)[[0, 2]])
    out2 = tt.jit(lambda a: clang.getitem(a, ([2, 0], slice(None))))(x)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(x)[[2, 0], :])


def test_masked_fill_concrete_mask(rng):
    """masked_fill with a closure-captured concrete jax mask (review r3)."""
    import jax.numpy as jnp

    from thunder_tpu.ops import ltorch

    mask = jnp.asarray([[True, False, True]])
    x = jnp.asarray(rng.randn(2, 3).astype("float32"))
    out = tt.jit(lambda a: ltorch.masked_fill(a, mask, 0.0))(x)
    want = np.where(np.asarray(mask), 0.0, np.asarray(x))
    np.testing.assert_allclose(np.asarray(out), want)


class TestAliasGroupCacheKeys:
    """Runtime alias groups in the jit cache key (reference
    thunder/__init__.py:408-437): a call whose tensor args share a buffer
    must not reuse the specialization compiled for distinct buffers."""

    def test_aliased_numpy_args_get_own_specialization(self, rng):
        import numpy as np

        import thunder_tpu as tt
        from thunder_tpu.ops import ltorch

        cf = tt.jit(lambda a, b: ltorch.sum(a * b))
        base = rng.randn(4, 4).astype(np.float32)
        x = base[:2]
        y = base[2:]
        cf(x, y)               # distinct buffers... of the same base! -> aliased
        cf(x.copy(), y.copy())  # truly distinct
        from thunder_tpu import _alias_groups, _is_tensor_like
        from thunder_tpu.core.pytree import tree_flatten

        leaves, _ = tree_flatten(((x, y), {}))
        mask = [_is_tensor_like(l) for l in leaves]
        assert _alias_groups(leaves, mask) == ((0, 1),)
        leaves2, _ = tree_flatten(((x.copy(), y.copy()), {}))
        assert _alias_groups(leaves2, mask) == ()
        # the two structures landed in different cache entries
        assert cf._cs.cache_misses == 2

    def test_same_object_twice_groups(self, rng):
        import jax.numpy as jnp

        import thunder_tpu as tt
        from thunder_tpu import _alias_groups, _is_tensor_like
        from thunder_tpu.core.pytree import tree_flatten

        x = jnp.ones((3, 3))
        leaves, _ = tree_flatten(((x, x), {}))
        mask = [_is_tensor_like(l) for l in leaves]
        assert _alias_groups(leaves, mask) == ((0, 1),)

    def test_interop_identical_views_unify(self, rng):
        import numpy as np
        import torch

        from thunder_tpu.interop.torch_frontend import compile_torch_module

        class AddMod(torch.nn.Module):
            def forward(self, a, b):
                return a + b

        cm = compile_torch_module(AddMod())
        t = torch.randn(3, 3)
        out = cm(t, t.view(3, 3))  # same storage, same layout -> one buffer
        np.testing.assert_allclose(np.asarray(out), (t + t).numpy(), atol=1e-6)


def test_item_symbol_returns_python_number(rng):
    from thunder_tpu.ops import ltorch

    v = tt.jit(lambda a: ltorch.item(a))(jnp.asarray([3.25]))
    assert float(v) == 3.25
    with pytest.raises(Exception, match="item"):
        tt.jit(lambda a: ltorch.item(a))(jnp.ones((2, 2)))


def test_exponential_key_sampler(rng):
    import jax as _jax

    from thunder_tpu.ops import ltorch

    key = _jax.random.PRNGKey(3)
    out = tt.jit(lambda a, k: ltorch.exponential(a, 2.0, key=k))(jnp.ones((2000,)), key)
    m = float(jnp.mean(out))
    assert abs(m - 0.5) < 0.06, m  # mean of Exp(rate=2) is 0.5
    assert float(jnp.min(out)) >= 0.0
    with pytest.raises(Exception, match="rng key"):
        tt.jit(lambda a: ltorch.exponential(a, 2.0))(jnp.ones((4,)))


def test_device_resolves_only_to_what_is_there():
    """Device.jax_device() names a device this process has or raises: no CPU
    stand-in for a missing TPU, no clamping of an index past the last device."""
    import jax

    from thunder_tpu.core import devices

    assert devices.Device("cpu:0").jax_device() == jax.devices("cpu")[0]
    if jax.devices()[0].platform == "cpu":
        with pytest.raises(RuntimeError, match="tpu"):
            devices.Device("tpu:0").jax_device()
    with pytest.raises(RuntimeError, match="cpu"):
        devices.Device("cpu", len(jax.devices("cpu"))).jax_device()
