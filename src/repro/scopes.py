"""Names the program gives its work in a profile.

Device scopes are ``jax.named_scope`` names: they land in the ``op_name``
metadata of every HLO instruction traced under them (fusions included),
cost nothing on the device, and let a reader of a profile group the
device's ops by what the code does.  Host spans are
``jax.profiler.TraceAnnotation`` events on the timeline of the thread
that opens them.  The model code and the benchmark's readers both import
these names, so a rename moves both.

Names are dotted, one level: ``<layer>.<phase>``.  Scopes nest: each
layer's work sits in ``transformer.block``, the phases inside it, and the
exchange's own scopes inside ``moe.exchange``; a reader takes the
innermost scope on an op's path.  The layer ``scan`` of the decode and
prefill steps sits in ``attention.kv_cache``: what XLA adds around the
body, slicing the stacked cache out per layer and writing it back, then
carries that scope alone.
"""

from __future__ import annotations

import functools
import sys

# -- device scopes (jax.named_scope) ------------------------------------------

MOE_ROUTE = "moe.route"              # router logits, softmax, top-k
MOE_DISPATCH = "moe.dispatch"        # argsort, capacity cumsum, scatter
MOE_EXPERT_FFN = "moe.expert_ffn"    # the grouped expert einsums
MOE_COMBINE = "moe.combine"          # gather back, gate, sum over top-k
MOE_EXCHANGE = "moe.exchange"        # the a2a between expert shards
ATTENTION_PROJ = "attention.proj"    # q/k/v and output projections
ATTENTION_CORE = "attention.core"    # scores, softmax, context
ATTENTION_KV_CACHE = "attention.kv_cache"  # decode cache read and write
LM_HEAD = "lm.head"                  # final norm and logits
TRANSFORMER_BLOCK = "transformer.block"  # a layer's norms and residuals
EXCHANGE_PACK = "exchange.pack"      # plan exchange: slot packing
EXCHANGE_STAGE = "exchange.stage"    # intra-pod a2a and ppermute stages
EXCHANGE_UNPACK = "exchange.unpack"  # plan exchange: slot unpacking

DEVICE_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT_FFN, MOE_COMBINE,
                 MOE_EXCHANGE, ATTENTION_PROJ, ATTENTION_CORE,
                 ATTENTION_KV_CACHE, LM_HEAD, TRANSFORMER_BLOCK,
                 EXCHANGE_PACK, EXCHANGE_STAGE, EXCHANGE_UNPACK)


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``."""
    def decorate(fn):
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return traced
    return decorate


# -- host spans (jax.profiler.TraceAnnotation) --------------------------------

SERVE_PREFILL = "serve.prefill"          # dispatch of the prefill step
SERVE_DECODE_STEP = "serve.decode_step"  # dispatch of one decode step
SERVE_ARGMAX = "serve.argmax"            # the eager argmax in generate
PLAN_SUBMIT = "plan.submit"          # client fast path; outcome=hit|queued
PLAN_SYNTHESIZE = "plan.synthesize"  # a worker's miss path; kind=...

HOST_SPANS = (SERVE_PREFILL, SERVE_DECODE_STEP, SERVE_ARGMAX, PLAN_SUBMIT,
              PLAN_SYNTHESIZE)


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **attrs):
        pass


_NO_SPAN = _NoSpan()


def span(name: str, **attrs):
    """A host span named ``name``, with ``attrs`` as its event stats.

    A no-op unless a profiler runs.  Only ``jax.profiler`` starts one, so
    while JAX is not loaded (the plan daemon alone) this imports nothing.
    """
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _NO_SPAN
    return profiler.TraceAnnotation(name, **attrs)
