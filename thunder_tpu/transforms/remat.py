"""Rematerialization / activation checkpointing.

Counterpart of reference activation checkpointing (torch.utils.checkpoint
lookaside tagging RECOMPUTE_IN_BACKWARD, thunder/core/jit_ext.py:1080) and the
nvFuser min-cut rematerialization pass (thunder/core/rematerialization.py:239).

On TPU the remat engine is XLA itself: ``jax.checkpoint`` (jax.remat) applied
to a region makes XLA recompute it in the backward instead of saving
residuals. Two surfaces:

  - checkpoint(fn): user-facing functional activation checkpointing for
    model code (the torch.utils.checkpoint analog) — the wrapped segment is
    traced through an opaque symbol whose VJP uses jax.checkpoint, so saved
    memory = segment inputs only.
  - RematTransform: tags fusion regions with jax.checkpoint policies
    (e.g. save-only-matmul-results: dots_saveable)."""
from __future__ import annotations

from typing import Callable

import jax

from ..core.transform_common import Transform
from ..core.trace import TraceCtx, from_trace


def checkpoint(fn: Callable) -> Callable:
    """Wrap a model segment for recompute-in-backward.

    Usage inside Module.forward:
        h = remat.checkpoint(self.block)(x)
    The segment must be a function of proxies; it is traced inline but its
    bsyms are tagged RECOMPUTE so the autodiff split recomputes them."""
    from ..core.symbol import OpTags
    from ..core.trace import get_tracectx

    def wrapped(*args, **kwargs):
        trc = get_tracectx()
        if trc is None:
            return fn(*args, **kwargs)
        with trc.push_scope() as scope:
            out = fn(*args, **kwargs)
        # re-emit tagged: autodiff's fwd/bwd split will prefer recomputing
        for bsym in scope:
            bsym.tags.add(OpTags.RECOMPUTE_IN_BACKWARD)
            trc.add_bound_symbol(bsym)
        return out

    return wrapped


class RematTransform(Transform):
    """Apply a jax.checkpoint policy to every XLA fusion region in the claimed
    trace — the whole-program analog of min-cut remat: XLA recomputes
    everything in the region's backward except tensors the policy saves."""

    POLICIES = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        "everything": jax.checkpoint_policies.everything_saveable,
    }

    def __init__(self, policy: str = "dots"):
        self.policy = self.POLICIES[policy]

    def transform_trace_post_optimization(self, trc: TraceCtx, *, compile_data=None) -> TraceCtx:
        out = from_trace(trc)
        new = []
        for bsym in trc.bound_symbols:
            impl = bsym.impl
            jitted = getattr(impl, "jitted", None) if impl is not None else None
            if jitted is None:
                new.append(bsym)
                continue
            raw = getattr(impl, "subtrace", None)
            inner = raw.python_callable(scoped=True) if raw is not None else jitted
            ck = jax.jit(jax.checkpoint(inner, policy=self.policy))

            def wrapped(*args, __ck=ck):
                return __ck(*args)

            wrapped.jitted = ck
            wrapped.subtrace = raw
            new.append(bsym.replace(impl=wrapped))
        out.bound_symbols = new
        out.set_provenance("Rematerialization (jax.checkpoint policy)")
        return out
