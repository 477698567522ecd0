"""Per-arch smoke tests (reduced configs, 1 CPU device): one forward/train
step asserting output shapes + no NaNs, one decode step, and decode==forward
consistency for a representative subset."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import list_archs, smoke_config
from repro.models import build_model
from repro.models.transformer import layer_kinds

B, S = 2, 16


def _with_scan(archs):
    """(arch, scan_layers) cases: every arch unrolled, as its smoke config
    has it, and the homogeneous ones also scanned over stacked layers."""
    cases = []
    for arch in archs:
        cases.append(pytest.param(arch, False, id=arch))
        if len(set(layer_kinds(smoke_config(arch)))) == 1:
            cases.append(pytest.param(arch, True, id=f"{arch}-scan"))
    return cases


def _batch(cfg, key=0):
    toks = jax.random.randint(jax.random.PRNGKey(key), (B, S), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    if cfg.frontend == "vision_stub":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(key + 1),
            (B, cfg.frontend_len, cfg.d_model)) * 0.1
    if cfg.frontend == "audio_stub":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(key + 2),
            (B, cfg.encoder_len, cfg.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("arch", list_archs())
def test_train_step_smoke(arch):
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    loss, metrics = jax.jit(lambda p, b: m.loss(p, b))(params, _batch(cfg))
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: loss not finite"
    grads = jax.grad(lambda p: m.loss(p, _batch(cfg))[0])(params)
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert gnorm > 0 and jnp.isfinite(gnorm), f"{arch}: bad grads"


@pytest.mark.parametrize("arch", list_archs())
def test_decode_step_smoke(arch):
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    cache = m.init_cache(B, 32)
    logits, cache2 = jax.jit(
        lambda p, c, t, pos: m.decode_step(p, c, t, pos))(
        params, cache, jnp.ones((B,), jnp.int32), jnp.int32(3))
    assert logits.shape == (B, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: decode NaN"


@pytest.mark.parametrize("arch,scan", _with_scan([
    "llama3.2-1b", "qwen3-0.6b", "mixtral-8x7b", "dbrx-132b",
    "xlstm-125m", "hymba-1.5b", "granite-3-2b", "mistral-large-123b",
    "megatron-moe-32e"]))
def test_decode_matches_forward(arch, scan):
    """Teacher-forced decode chain reproduces the training forward."""
    cfg = dataclasses.replace(smoke_config(arch), compute_dtype="float32",
                              scan_layers=scan)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    from repro.models.transformer import lm_forward
    logits_fwd, _ = lm_forward(cfg, params, toks, {"tokens": toks})
    cache = m.init_cache(B, S)
    scale = float(jnp.abs(logits_fwd).max()) + 1e-9
    step = jax.jit(lambda p, c, t, pos: m.decode_step(p, c, t, pos))
    for t in range(S):
        lg, cache = step(params, cache, toks[:, t], jnp.int32(t))
        err = float(jnp.abs(lg - logits_fwd[:, t]).max()) / scale
        assert err < 1e-5, (arch, t, err)


@pytest.mark.parametrize("arch,scan", _with_scan([
    "llama3.2-1b", "mixtral-8x7b", "xlstm-125m", "hymba-1.5b"]))
def test_prefill_then_decode(arch, scan):
    cfg = dataclasses.replace(smoke_config(arch), compute_dtype="float32",
                              scan_layers=scan)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    from repro.models.transformer import lm_forward, lm_prefill
    logits_fwd, _ = lm_forward(cfg, params, toks, {"tokens": toks})
    scale = float(jnp.abs(logits_fwd).max()) + 1e-9
    half = S // 2
    lg, cache = lm_prefill(cfg, params, toks[:, :half], cache_len=S)
    assert float(jnp.abs(lg - logits_fwd[:, half - 1]).max()) / scale < 1e-5
    for t in range(half, S):
        lg, cache = m.decode_step(params, cache, toks[:, t], jnp.int32(t))
        err = float(jnp.abs(lg - logits_fwd[:, t]).max()) / scale
        assert err < 1e-5, (arch, t, err)


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
def test_sliding_window_masks_history(scan):
    """A windowed arch must ignore tokens beyond the window."""
    cfg = dataclasses.replace(smoke_config("mixtral-8x7b"),
                              compute_dtype="float32", swa_window=4,
                              n_layers=1, moe=None, family="dense",
                              scan_layers=scan)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, cfg.vocab)
    from repro.models.transformer import lm_forward
    base, _ = lm_forward(cfg, params, toks, None)
    # perturb a token far outside the window of the last position
    toks2 = toks.at[0, 1].set((toks[0, 1] + 7) % cfg.vocab)
    pert, _ = lm_forward(cfg, params, toks2, None)
    # last position only sees tokens 8..11: unchanged
    assert float(jnp.abs(base[0, -1] - pert[0, -1]).max()) < 1e-5
    # position 2 sees token 1: changed
    assert float(jnp.abs(base[0, 2] - pert[0, 2]).max()) > 1e-6


def test_scanned_step_matches_unrolled_through_the_ring():
    """The scanned serve step, which carries the stacked cache through the
    layer scan and writes each new row in place (the cache donated), gives
    the unrolled step's logits and caches over 8 steps in float32, through
    a 4-slot ring that wraps; both follow the training forward."""
    from repro.launch.serve import make_serve_step
    from repro.models.transformer import lm_forward

    n = 8
    base = dataclasses.replace(smoke_config("mixtral-8x7b"),
                               compute_dtype="float32", swa_window=4)
    scan_cfg = dataclasses.replace(base, scan_layers=True)
    roll_cfg = dataclasses.replace(base, scan_layers=False)
    params = build_model(scan_cfg).init(jax.random.PRNGKey(1))
    unrolled = dict(params, blocks=[
        jax.tree.map(lambda a, i=i: a[i], params["blocks"])
        for i in range(base.n_layers)])
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, n), 0, base.vocab)
    logits_fwd, _ = lm_forward(roll_cfg, unrolled, toks)
    scale = float(jnp.abs(logits_fwd).max()) + 1e-9
    cache_s = build_model(scan_cfg).init_cache(B, n)
    cache_r = build_model(roll_cfg).init_cache(B, n)
    assert cache_s["k"].shape == (base.n_layers, B, base.n_kv_heads, 4,
                                  base.resolved_head_dim)
    step_s = make_serve_step(scan_cfg, None)
    step_r = make_serve_step(roll_cfg, None)
    for t in range(n):
        lg_s, cache_s = step_s(params, cache_s, toks[:, t], jnp.int32(t))
        lg_r, cache_r = step_r(unrolled, cache_r, toks[:, t], jnp.int32(t))
        assert float(jnp.abs(lg_s - lg_r).max()) / scale < 1e-5, t
        assert float(jnp.abs(lg_s - logits_fwd[:, t]).max()) / scale < 1e-5
    for name in ("k", "v"):
        stacked = jnp.stack([c[name] for c in cache_r])
        assert float(jnp.abs(cache_s[name] - stacked).max()) < 1e-5, name


def test_vlm_patch_prefix_used():
    cfg = dataclasses.replace(smoke_config("internvl2-1b"),
                              compute_dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b = _batch(cfg)
    l1, _ = m.loss(params, b)
    b2 = dict(b)
    b2["patch_embeds"] = b["patch_embeds"] + 1.0
    l2, _ = m.loss(params, b2)
    assert abs(float(l1) - float(l2)) > 1e-6, "patch embeds ignored"
