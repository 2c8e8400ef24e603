"""Where an op came from: the ``named_scope`` path on every trace symbol, through
autocast, autodiff and recomputation, into the compiled program's HLO, and back out
of it (observability/profiler.py: ``op_scopes``, ``scope_of``).

The models are two-block ``litgpt`` ones shaped like the benchmark's: pythia (parallel
residual, LayerNorm, biases, a quarter of each head rotated, GptNeox MLP) and mistral
(grouped-query attention, RMSNorm, SwiGLU), both trained with bf16 autocast and
recomputation of the blocks, and the mistral one served through the paged runner.
"""
import re
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import optim
from thunder_tpu.core.trace import named_scope, scope_of
from thunder_tpu.compile_service.parallel_compile import _region_avals
from thunder_tpu.executors import xlaex
from thunder_tpu.models import litgpt
from thunder_tpu.observability import profiler
from thunder_tpu.observability.profiler import PARTS, UNSCOPED, parse_hlo_text, path_scope
from thunder_tpu.ops import ltorch
from thunder_tpu.training import TrainStep
from thunder_tpu.transforms.autocast import AutocastTransform

SHAPES = {
    "pythia": dict(name="pythia-2l", block_size=64, vocab_size=320, n_layer=2, n_head=4, n_embd=64,
                   rotary_percentage=0.25, parallel_residual=True, bias=True,
                   norm_class_name="LayerNorm", mlp_class_name="GptNeoxMLP"),
    "mistral": dict(name="mistral-2l", block_size=64, vocab_size=320, n_layer=2, n_head=4,
                    n_query_groups=2, n_embd=64, intermediate_size=176,
                    norm_class_name="RMSNorm", mlp_class_name="LLaMAMLP"),
}


def instructions(text: str) -> list:
    return re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", text, re.M)


class Trained:
    def __init__(self, shape: str):
        cfg = litgpt.Config(**SHAPES[shape], activation_checkpoint=True)
        self.model = litgpt.GPTForCausalLM(cfg)
        self.step = TrainStep(tt.jit(self.model, transforms=[AutocastTransform()]),
                              optim.AdamW(lr=1e-3))
        toks = np.random.default_rng(0).integers(0, 300, (2, 33)).astype(np.int32)
        self.loss = float(self.step(toks[:, :-1], toks[:, 1:]))
        cs = self.step._vag._cs
        self.traces = {"forward": cs.last_traces[-1], "backward": cs.last_backward_traces[-1]}
        # two steps in one process print the same module name: this one's map is the one
        # that holds its instructions
        self.instructions = instructions(self.step.compiled().as_text())
        self.ops = profiler.op_scopes()["jit_tt_train_step"].holding(self.instructions)


@pytest.fixture(scope="module")
def trained():
    made = {}

    def get(shape):
        if shape not in made:
            made[shape] = Trained(shape)
        return made[shape]

    yield get
    made.clear()


def region_symbols(trace) -> list:
    """The symbols an executed trace runs, those inside its XLA regions one by one."""
    out = []
    for b in trace.bound_symbols:
        if b.sym.executor is xlaex.ex:
            out += list(b.subsymbols)
        elif b.sym.name not in ("python_return", "python_del", "unpack_trivial", "comment"):
            out.append(b)
    return out


# -- the path on the trace's symbols ------------------------------------------------------------

@pytest.mark.parametrize("which", ["forward", "backward"])
@pytest.mark.parametrize("shape", ["pythia", "mistral"])
def test_every_symbol_of_the_executed_traces_carries_a_part(trained, shape, which):
    t = trained(shape)
    assert np.isfinite(t.loss)
    syms = region_symbols(t.traces[which])
    assert len(syms) > 50
    parts = {path_scope(scope_of(b) + "/op")[2] if scope_of(b) else UNSCOPED for b in syms}
    # nothing here is inserted by a transform without a forward origin: no exception
    assert parts <= {"embed", "attn", "mlp", "head"}, parts


@pytest.mark.parametrize("shape", ["pythia", "mistral"])
def test_the_forward_trace_has_no_pass_and_the_backward_only_bwd_and_recompute(trained, shape):
    t = trained(shape)
    fwd = {scope_of(b).split("/")[0] for b in region_symbols(t.traces["forward"])}
    bwd = {scope_of(b).split("/")[0] for b in region_symbols(t.traces["backward"])}
    assert fwd <= {"embed", "attn", "mlp", "head"}
    assert bwd == {"bwd", "recompute"}


@pytest.mark.parametrize("shape, wanted", [
    ("pythia", {"bwd/attn/rope", "recompute/attn/rope", "bwd/mlp", "recompute/mlp", "bwd/head", "bwd/embed"}),
    ("mistral", {"bwd/attn", "recompute/attn", "bwd/mlp", "recompute/mlp", "bwd/head", "bwd/embed"}),
])
def test_the_backward_carries_the_whole_path_of_the_forward_symbol(trained, shape, wanted):
    paths = {scope_of(b) for b in region_symbols(trained(shape).traces["backward"])}
    assert wanted <= paths, sorted(wanted - paths)
    # the head and the embedding are not recomputed: only the blocks are checkpointed
    assert not {p for p in paths if p.startswith(("recompute/head", "recompute/embed"))}


@pytest.mark.parametrize("shape", ["pythia", "mistral"])
def test_the_casts_autocast_inserts_are_bound_inside_the_scope(trained, shape):
    casts = [b for b in region_symbols(trained(shape).traces["forward"])
             if b.sym.name == "convert_element_type"]
    assert len(casts) >= 8
    assert {path_scope(scope_of(b) + "/op")[2] for b in casts} >= {"attn", "mlp", "head"}


def test_a_scope_is_the_path_of_the_open_scopes_and_outside_a_trace_it_is_nothing():
    def f(x):
        with named_scope("attn"):
            with named_scope("rope"):
                y = ltorch.mul(x, 2.0)
            z = ltorch.add(y, 1.0)
        return ltorch.tanh(z)

    cfn = tt.jit(f)
    cfn(jnp.ones((4, 4)))
    by_name = {b.sym.name: scope_of(b) for b in tt.last_traces(cfn)[0].bound_symbols}
    assert (by_name["mul"], by_name["add"], by_name["tanh"]) == ("attn/rope", "attn", None)
    with named_scope("nothing"):  # no trace is open
        assert float(jnp.ones(()) + 1) == 2.0


# -- which road the rope takes ---------------------------------------------------------------------

class Roads:
    """A pythia-shaped model long enough for the flash checkers to claim (T 1,024, heads of
    64, a quarter of each rotated), traced twice from one set of weights: as it runs, and with
    the rope-flash checker saying no, which is the decomposed road of a CPU or a short sequence."""

    def __init__(self):
        from thunder_tpu.executors import pallasex

        cfg = litgpt.Config(**dict(SHAPES["pythia"], block_size=1024, n_head=2, n_embd=128),
                            activation_checkpoint=True)
        assert (cfg.head_size, cfg.rope_n_elem) == (64, 16)
        self.cfg = cfg
        model = litgpt.GPTForCausalLM(cfg)
        toks = np.random.default_rng(0).integers(0, 300, (1, 1025)).astype(np.int32)
        self.vag = {}
        self.out = {}
        claims = pallasex.rope_sdpa_supported
        for road in ("fused", "decomposed"):
            self.vag[road] = tt.value_and_grad(tt.jit(model))
            if road == "decomposed":
                pallasex.rope_sdpa_supported = lambda *a, **kw: False
            try:
                self.out[road] = self.vag[road](toks[:, :-1], toks[:, 1:])
            finally:
                pallasex.rope_sdpa_supported = claims

    def symbols(self, road: str, which: str) -> list:
        cs = self.vag[road]._cs
        if which == "traced":
            return list(cs.last_traces[0].bound_symbols)
        return region_symbols({"forward": cs.last_traces, "backward": cs.last_backward_traces}[which][-1])


@pytest.fixture(scope="module")
def roads():
    return Roads()


def test_a_partial_rope_is_traced_as_one_rope_sdpa_a_layer_and_the_kernels_claim_it(roads):
    r = roads
    n = r.cfg.n_layer
    traced = [b.sym.name for b in r.symbols("fused", "traced")]
    assert traced.count("rope_sdpa") == n and "sdpa" not in traced
    fwd = r.symbols("fused", "forward")
    bwd = r.symbols("fused", "backward")
    names = lambda syms: [b.sym.name for b in syms]
    assert names(fwd).count("rope_flash_fwd") == n
    assert names(bwd).count("rope_flash_fwd") == n  # the blocks are recomputed
    assert names(bwd).count("rope_flash_bwd") == n
    assert not [x for x in names(fwd) + names(bwd) if "flash_attention" in x]
    # no rotation is left to XLA: under attn/rope only the tables' slice, made once a step
    left = [b.sym.name for b in fwd + bwd if (scope_of(b) or "").endswith("attn/rope")]
    assert len(left) <= 2 and set(left) <= {"slice_prim"}, left


def test_the_decomposed_road_of_rope_sdpa_rotates_under_the_scope_rope(roads):
    r = roads
    assert [b.sym.name for b in r.symbols("decomposed", "traced")].count("rope_sdpa") == r.cfg.n_layer
    fwd = {scope_of(b) for b in r.symbols("decomposed", "forward")}
    bwd = {scope_of(b) for b in r.symbols("decomposed", "backward")}
    assert "attn/rope" in fwd and {"bwd/attn/rope", "recompute/attn/rope"} <= bwd
    rotated = [b for b in r.symbols("decomposed", "forward")
               if scope_of(b) == "attn/rope" and b.sym.name == "cat"]
    assert len(rotated) == 2 * r.cfg.n_layer  # q and k of every layer, three parts each
    assert all(len(b.args[0]) == 3 for b in rotated)


def test_the_two_roads_give_one_loss_and_one_gradient(roads):
    r = roads
    (loss, grads), (ref_loss, ref_grads) = r.out["fused"], r.out["decomposed"]
    assert np.isfinite(float(loss))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    assert set(grads) == set(ref_grads) and len(grads) > 20
    for name in grads:
        np.testing.assert_allclose(np.asarray(grads[name]), np.asarray(ref_grads[name]),
                                   atol=1e-6, rtol=1e-4, err_msg=name)


def test_a_context_parallel_trace_keeps_the_decomposed_rope_and_plain_sdpa():
    """Ring attention rewrites plain sdpa symbols: under an open context-parallel context the
    model rotates with `_apply_rope` (local rows, global positions) and calls `sdpa`."""
    from thunder_tpu.parallel import make_mesh
    from thunder_tpu.parallel.context_parallel import context_parallel

    cfg = litgpt.Config(**SHAPES["pythia"])
    toks = np.random.default_rng(0).integers(0, 300, (2, 65)).astype(np.int32)
    tm = tt.jit(litgpt.GPTForCausalLM(cfg))
    context_parallel(tm, make_mesh({"sp": 2}))
    step = TrainStep(tm, optim.SGD(lr=0.0))
    assert np.isfinite(float(step(toks[:, :-1], toks[:, 1:])))
    traced = step._vag._cs.last_traces[0].bound_symbols
    names = [b.sym.name for b in traced]
    assert names.count("ring_attention") == cfg.n_layer and "rope_sdpa" not in names
    assert {"attn/rope"} <= {scope_of(b) for b in traced}


# -- a scope is a trace-time name --------------------------------------------------------------

def lone(x):  # one elementwise op is not worth a region: it runs op by op
    with named_scope("attn"):
        return ltorch.tanh(x)


def test_a_symbol_that_runs_op_by_op_on_the_host_enters_no_named_scope():
    cfn = tt.jit(lone)
    cfn(jnp.ones((4, 4)))
    trace = tt.last_traces(cfn)[-1]
    ran = [b for b in trace.bound_symbols if b.sym.name == "tanh"]
    assert ran and scope_of(ran[0]) == "attn"          # the tag is there,
    assert "named_scope" not in trace.python_callable().__source__  # the program pays nothing
    assert "named_scope" in trace.python_callable(scoped=True).__source__


def test_under_an_outer_jit_the_same_symbol_is_traced_under_its_scope():
    import jax

    def outer(x):
        return tt.jit(lone)(x) * 2

    text = jax.jit(outer).lower(jnp.ones((4, 4))).compile().as_text()
    assert re.search(r'op_name="jit\(outer\)/attn/tanh"', text), text[-2000:]


@pytest.fixture(scope="module")
def served():
    from thunder_tpu.serving import ServingEngine

    cfg = litgpt.Config(**SHAPES["mistral"])
    gpt = litgpt.GPT(cfg, dtype=jnp.float32)
    engine = ServingEngine(gpt, dtype=jnp.float32, max_batch=2, page_size=8, max_seq=64,
                           n_pages=32)
    engine.start()
    try:
        engine.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=4).result(timeout=120)
    finally:
        engine.stop()
    yield engine


def test_the_host_program_of_a_decode_step_holds_no_named_scope_outside_a_region(served):
    trace = tt.last_traces(served.runner.decode_cfn._cfn)[-1]
    regions = [b for b in trace.bound_symbols if b.sym.executor is xlaex.ex]
    assert regions
    assert "named_scope" not in trace.python_callable().__source__
    for b in regions:  # inside a region, where jax.jit traces it once, it is
        assert "named_scope" in b.impl.subtrace.python_callable(scoped=True).__source__


# -- the op map of a compiled program ------------------------------------------------------------

@pytest.mark.parametrize("shape", ["pythia", "mistral"])
def test_every_instruction_of_the_compiled_train_step_is_in_op_scopes(trained, shape):
    t = trained(shape)
    held = {k.rsplit("/", 1)[-1] for k in t.ops}
    assert len(t.instructions) > 500 and set(t.instructions) <= held
    assert t.ops.module == "jit_tt_train_step" and t.ops.region == ""


def test_every_instruction_of_the_decode_program_is_in_op_scopes(served):
    maps = profiler.op_scopes()
    trace = tt.last_traces(served.runner.decode_cfn._cfn)[-1]
    regions = [b for b in trace.bound_symbols if b.sym.executor is xlaex.ex]
    for b in regions:
        ops = maps["jit_" + b.sym.name].holding([])
        assert ops.region == b.sym.name
        held = {k.rsplit("/", 1)[-1] for k in ops}
        names = instructions(b.impl.jitted.lower(*_region_avals(b)).compile().as_text())
        assert names and set(names) <= held


@pytest.mark.parametrize("wanted", [
    ("fwd", "embed"), ("fwd", "attn"), ("fwd", "mlp"), ("fwd", "head"),
    ("bwd", "attn"), ("bwd", "mlp"), ("bwd", "head"), ("bwd", "embed"),
    ("recompute", "attn"), ("recompute", "mlp"), ("optimizer", "optimizer"),
])
@pytest.mark.parametrize("shape", ["pythia", "mistral"])
def test_scope_of_tells_the_passes_and_the_parts_of_a_train_step(trained, shape, wanted):
    t = trained(shape)
    found = {(p, part.split("+")[0]) for _, p, part in (t.ops.scope(k) for k in t.ops if "/" not in k)}
    assert wanted in found
    # the matmuls are where the time goes: none of them without a part
    dots = [k for k, members in t.ops.members.items() if any(op == "dot" for _, op in members)]
    dots += [k for k in t.ops if "/" not in k and re.match(r"dot(\.\d+)?$", k)]
    assert dots and all(t.ops.scope(k)[2] != UNSCOPED for k in dots)


@pytest.mark.parametrize("wanted", ["embed", "attn", "kv_write", "mlp", "head"])
def test_scope_of_tells_the_parts_of_a_decode_program(served, wanted):
    maps = profiler.op_scopes()
    trace = tt.last_traces(served.runner.decode_cfn._cfn)[-1]
    found = set()
    for b in trace.bound_symbols:
        if b.sym.executor is xlaex.ex:
            ops = maps["jit_" + b.sym.name]
            found |= {ops.scope(k) for k in ops if "/" not in k}
            one = next(k for k in ops if "/" not in k)
            assert profiler.scope_of(ops.module + "(123)", one) == ops.scope(one)
    assert {p for _, p, _ in found} == {"fwd"}
    assert wanted in {part.split("+")[0] for _, _, part in found}
    assert {r for r, _, _ in found} <= {b.sym.name for b in trace.bound_symbols}


def test_an_executable_is_held_weakly_and_parsed_once():
    class Holder:
        compiled = None

    class Text:
        asked = 0

        def as_text(self):
            Text.asked += 1
            return "HloModule jit_weak_one\n\nENTRY %main (a: f32[2]) -> f32[2] {\n" \
                   '  ROOT %a = f32[2]{0} parameter(0), metadata={op_name="a"}\n}\n'

    h = Holder()
    h.compiled = Text()
    profiler.register_executable(h, lambda holder: holder.compiled, region="weak_one")
    assert "a" in profiler.op_scopes()["jit_weak_one"] and "a" in profiler.op_scopes()["jit_weak_one"]
    assert Text.asked == 1
    ref = weakref.ref(h)
    del h
    assert ref() is None and "jit_weak_one" not in profiler.op_scopes()


# -- one op_name, one fusion -----------------------------------------------------------------------

@pytest.mark.parametrize("op_name, wanted", [
    ("jit(tt_train_step)/tt_fwd_bwd/jit(xla_fusion_0)/xla_fusion_0/attn/rope/mul", ("xla_fusion_0", "fwd", "attn")),
    ("jit(tt_train_step)/tt_fwd_bwd/jit(xla_fusion_1)/xla_fusion_1/bwd/mlp/dot_general", ("xla_fusion_1", "bwd", "mlp")),
    ("jit(tt_train_step)/tt_fwd_bwd/xla_fusion_1/recompute/attn/kv_write/scatter", ("xla_fusion_1", "recompute", "kv_write")),
    ("jit(tt_train_step)/tt_optimizer/mul", ("tt_optimizer", "optimizer", "optimizer")),
    ("jit(xla_fusion_7)/xla_fusion_7/mamba/dot_general", ("xla_fusion_7", "fwd", "mamba")),
    ("jit(xla_fusion_7)/xla_fusion_7/all_gather", ("xla_fusion_7", "fwd", "unscoped")),
    ("jit(xla_fusion_7)/xla_fusion_7/bwd/add", ("xla_fusion_7", "bwd", "unscoped")),
    ("jit(xla_fusion_7)/xla_fusion_7/vision_tower/patches/conv_general_dilated", ("xla_fusion_7", "fwd", "vision_tower")),
    ("jit(raw_step_dist)/shard_map/tt_fwd_bwd/jit(xla_fusion_1)/xla_fusion_1/bwd/mlp/mul;xla_fusion_1/bwd/head/broadcast_in_dim",
     ("xla_fusion_1", "bwd", "mlp")),
    ("jit(raw_step_dist)/shard_map/tt_fwd_bwd/jit(xla_fusion_1)/xla_fusion_1/bwd/reduce_scatter", ("xla_fusion_1", "bwd", "unscoped")),
    ("jit(f)/while/body/attn/mul", ("", "fwd", "attn")),
    ("jit(f)/transpose(jvp(attn))/mlp/mul", ("", "bwd", "mlp")),
    ("jit(f)/checkpoint/rematted_computation/attn/mul", ("", "recompute", "attn")),
    ("tparam_arrays['gpt.wte.weight']", ("", "fwd", "unscoped")),
    ("", ("", "fwd", "unscoped")),
])
def test_one_op_name_gives_region_pass_and_part(op_name, wanted):
    assert path_scope(op_name) == wanted
    assert wanted[1] in profiler.PASSES and (wanted[2] in PARTS or wanted[2] in
                                             ("optimizer", "unscoped", "vision_tower"))


FUSED = """HloModule jit_tt_train_step, is_scheduled=true

%fused_computation.1 (p0: bf16[8,8], p1: f32[8,8]) -> (f32[8,8], bf16[8,8]) {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.1 = bf16[8,8]{1,0} dot(%p0, %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(tt_train_step)/tt_fwd_bwd/jit(xla_fusion_1)/xla_fusion_1/bwd/mlp/dot_general"}
  %convert.2 = f32[8,8]{1,0} convert(%dot.1), metadata={op_name="jit(tt_train_step)/tt_optimizer/convert_element_type"}
  %mul.3 = f32[8,8]{1,0} multiply(%convert.2, %p1), metadata={op_name="jit(tt_train_step)/tt_optimizer/mul"}
  %sub.4 = f32[8,8]{1,0} subtract(%p1, %mul.3), metadata={op_name="jit(tt_train_step)/tt_optimizer/sub"}
  ROOT %tuple.5 = (f32[8,8]{1,0}, bf16[8,8]{1,0}) tuple(%sub.4, %dot.1)
}

%fused_computation.2 (p0.1: f32[8,8]) -> f32[8,8] {
  %p0.1 = f32[8,8]{1,0} parameter(0)
  %add.6 = f32[8,8]{1,0} add(%p0.1, %p0.1), metadata={op_name="jit(tt_train_step)/tt_fwd_bwd/jit(xla_fusion_0)/xla_fusion_0/attn/add"}
  ROOT %rsqrt.7 = f32[8,8]{1,0} rsqrt(%add.6), metadata={op_name="jit(tt_train_step)/tt_fwd_bwd/jit(xla_fusion_0)/xla_fusion_0/mlp/rsqrt"}
}

%fused_computation.3 (p0.2: f32[8,8]) -> f32[8,8] {
  %p0.2 = f32[8,8]{1,0} parameter(0)
  %neg.8 = f32[8,8]{1,0} negate(%p0.2), metadata={op_name="jit(tt_train_step)/tt_fwd_bwd/jit(xla_fusion_0)/xla_fusion_0/head/neg"}
  ROOT %copy.9 = f32[8,8]{1,0} copy(%neg.8)
}

ENTRY %main.1 (a: bf16[8,8], b: f32[8,8]) -> f32[8,8] {
  %a = bf16[8,8]{1,0} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %fusion.1 = (f32[8,8]{1,0}, bf16[8,8]{1,0}) fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(tt_train_step)/tt_optimizer/sub"}
  %get-tuple-element.1 = f32[8,8]{1,0} get-tuple-element(%fusion.1), index=0
  %fusion.2 = f32[8,8]{1,0} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(tt_train_step)/tt_fwd_bwd/jit(xla_fusion_0)/xla_fusion_0/mlp/rsqrt"}
  %fusion.3 = f32[8,8]{1,0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3
  ROOT %copy.10 = f32[8,8]{1,0} copy(%fusion.3)
}
"""


@pytest.mark.parametrize("instruction, wanted", [
    # AdamW's update fused into a weight-gradient matmul: where the matmul is, and said to be two
    ("fusion.1", ("xla_fusion_1", "bwd", "mlp+optimizer")),
    # no matmul inside: the fusion's own op_name, and the other part its members hold
    ("fusion.2", ("xla_fusion_0", "fwd", "mlp+attn")),
    # no op_name of its own: where most of its members are
    ("fusion.3", ("xla_fusion_0", "fwd", "head")),
    # XLA gave it no op_name at all: where the instruction that reads it goes
    ("get-tuple-element.1", ("xla_fusion_0", "fwd", "mlp+attn")),
    ("copy.10", ("", "fwd", "unscoped")),  # nothing reads the root
    ("no.such", ("", "fwd", "unscoped")),
])
def test_a_fusion_goes_where_its_matmuls_are_and_one_over_two_parts_is_a_pair(instruction, wanted):
    ops = parse_hlo_text(FUSED)
    assert ops.module == "jit_tt_train_step"
    assert ops.scope(instruction) == wanted
    assert ops["fusion.1/dot.1"].endswith("bwd/mlp/dot_general") and ops["copy.10"] == ""
    assert ops.finer("fusion.1") == "mlp" and ops.finer("copy.10") == ""


def test_the_finer_names_below_a_part_can_be_read():
    text = FUSED.replace("xla_fusion_0/attn/add", "xla_fusion_0/attn/rope/add").replace("mlp/rsqrt", "attn/rope/rsqrt")
    ops = parse_hlo_text(text)
    assert ops.scope("fusion.2") == ("xla_fusion_0", "fwd", "attn") and ops.finer("fusion.2") == "attn/rope"


def test_two_executables_under_one_module_name_are_told_apart_by_what_they_hold():
    first = parse_hlo_text(FUSED, "xla_fusion_4")
    other = parse_hlo_text(FUSED.replace("fusion.3", "fusion.33").replace("head/neg", "embed/neg"),
                           "xla_fusion_9")
    first.others.append(other)
    assert first.holding(["fusion.1", "fusion.3"]) is first
    assert first.holding(["fusion.1", "fusion.33"]) is other
    assert other.scope("fusion.33") == ("xla_fusion_9", "fwd", "embed")  # the name it runs under here


def test_attribute_puts_an_event_on_the_region_of_its_instruction():
    ops = parse_hlo_text(FUSED)
    regions = {n: {"bsym_ids": [], "flops": 0.0, "bytes": 0, "level": lvl} for n, lvl in
               (("xla_fusion_0", 0), ("xla_fusion_1", 0), ("tt_optimizer", 1), ("tt_train_step", 2))}
    events = [{"ph": "X", "pid": 1, "tid": 1, "ts": 10.0 * i, "dur": d, "name": n,
               "args": {"hlo_module": "jit_tt_train_step", "hlo_op": n}}
              for i, (n, d) in enumerate([("fusion.1", 7.0), ("fusion.2", 2.0), ("fusion.3", 1.0),
                                          ("copy.10", 0.5)])]
    prof = profiler.attribute(events, region_map=regions, op_map={ops.module: ops})
    got = {n: r.us for n, r in prof.regions.items()}
    # fusion.1's own path is the optimizer's; its region is the matmul's, which is the finer one
    assert got == {"xla_fusion_1": 7.0, "xla_fusion_0": 3.0, "tt_train_step": 0.5}
    assert prof.unattributed_us == 0.0


def test_scope_names_are_what_the_models_use():
    import inspect

    from thunder_tpu.models import latent_moe, moe, sambay, shortcut_moe
    from thunder_tpu.serving import runner

    used = set()
    for mod in (litgpt, sambay, latent_moe, moe, shortcut_moe, runner):
        used |= set(re.findall(r'named_scope\("([\w/]+)"\)', inspect.getsource(mod)))
    used = {seg for name in used for seg in name.split("/")}
    assert used - {"rope"} <= PARTS, used - PARTS
