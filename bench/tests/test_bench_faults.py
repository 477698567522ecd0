"""A run whose timed path is broken underneath must come out not
correct: a token altered where it is produced, a decode step that
returns its cache unchanged, half of the batch answered with the other
half's results.  The harness runs as it does on the chip, minus the
chip check, at a size the CPU holds."""

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import smoke
from repro.launch import serve


def run(cell, seed=31):
    return harness.run(cell, seed, 1.5, False, jax.devices())


def _altered_token(real):
    def make(*a, **k):
        fn = real(*a, **k)

        def prefill(params, batch):
            logits, cache = fn(params, batch)
            return jnp.roll(logits, 1, axis=-1), cache
        return prefill
    return make


def _cache_unchanged(real):
    def make(*a, **k):
        fn = real(*a, **k)

        def step(params, cache, tokens, pos):
            logits, _ = fn(params, cache, tokens, pos)
            return logits, cache
        return step
    return make


def _half_batch(real):
    def make(*a, **k):
        fn = real(*a, **k)

        def prefill(params, batch):
            t = batch["tokens"]
            half = t.shape[0] // 2
            return fn(params, {"tokens": jnp.concatenate(
                [t[:half], t[:half]])})
        return prefill
    return make


@pytest.mark.parametrize("fault,target,kind", [
    ("token altered", "make_prefill_step", "prefill"),
    ("half of the batch", "make_prefill_step", "prefill"),
    ("cache unchanged", "make_serve_step", "decode"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, target,
                                            kind):
    wrap = {"token altered": _altered_token,
            "half of the batch": _half_batch,
            "cache unchanged": _cache_unchanged}[fault]
    monkeypatch.setattr(serve, target, wrap(getattr(serve, target)))
    out = run(smoke.cell(kind))
    assert not out["line"]["correct"], out["line"]["checks"]


def test_a_stale_plan_is_not_correct(monkeypatch):
    """The daemon answering every request with the family's first plan."""
    from repro.serving import PlanClient

    real = PlanClient.get_plan
    first = {}

    def stale(self, w):
        answer = real(self, w)
        return first.setdefault(id(self), answer)

    monkeypatch.setattr(PlanClient, "get_plan", stale)
    out = run(smoke.cell("decode"))
    checks = out["line"]["checks"]
    assert checks["plan_bytes_err"]["value"] > \
        checks["plan_bytes_err"]["limit"]
    assert not out["line"]["correct"]
