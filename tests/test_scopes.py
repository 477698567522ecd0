"""The program's profiler names: device scopes in the compiled HLO of the
served steps (metadata only: the program is otherwise unchanged), host
spans around step dispatch and plan-daemon work, and the daemon's queue
wait counter."""

import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro import scopes
from repro.configs import smoke_config
from repro.core.traffic import ClusterSpec, moe_workload
from repro.launch import serve
from repro.models import build_model
from repro.serving import PlanServer, Telemetry

B, P, G = 4, 16, 4


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("megatron-moe-32e", scan_layers=True)


def _hlo(cfg, kind):
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if kind == "prefill":
        step = serve.make_prefill_step(cfg, None, cache_len=P + G)
        args = (params, {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)})
    else:
        step = serve.make_serve_step(cfg, None)
        args = (params, jax.eval_shape(lambda: model.init_cache(B, P + G)),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
    return step.lower(*args).compile().as_text()


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def _strip(text):
    """The computations of an HLO module's text without metadata, names
    numbered in order of first use.  Lowering numbers instructions from
    counters that scoped call sites can advance differently (inner jitted
    functions are lowered once per name stack); the program is the same."""
    body = text[text.index("\n%") if "\n%" in text else 0:]
    body = re.sub(r", metadata=\{[^{}]*\}", "", body)
    names, counts = {}, {}

    def renumber(m):
        name = m.group(0)
        if name not in names:
            base = re.sub(r"\.\d+", "", name)
            counts[base] = counts.get(base, 0) + 1
            names[name] = f"{base}.{counts[base]}"
        return names[name]

    return re.sub(r"%[\w.\-]+", renumber, body)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_compiled_steps_carry_the_moe_and_cache_scopes(cfg, kind):
    parts = {p for name in _op_names(_hlo(cfg, kind))
             for p in name.split("/")}
    assert {scopes.MOE_ROUTE, scopes.MOE_DISPATCH, scopes.MOE_EXPERT_FFN,
            scopes.MOE_COMBINE, scopes.ATTENTION_KV_CACHE,
            scopes.ATTENTION_CORE, scopes.LM_HEAD} <= parts
    assert parts & set(scopes.DEVICE_SCOPES) <= set(scopes.DEVICE_SCOPES)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_scopes_change_only_the_metadata(cfg, kind, monkeypatch):
    scoped = _hlo(cfg, kind)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _hlo(cfg, kind)
    assert scopes.MOE_EXPERT_FFN in scoped
    assert scopes.MOE_EXPERT_FFN not in bare
    assert _strip(scoped) == _strip(bare)


def test_step_callables_keep_the_jitted_function(cfg):
    step = serve.make_prefill_step(cfg, None, cache_len=P + G)
    assert callable(step.lower) and callable(step.eval_shape)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    out = step.eval_shape(params, {"tokens": jax.ShapeDtypeStruct(
        (B, P), jnp.int32)})
    assert out[0].shape == (B, cfg.vocab)


def _profile_events(trace_dir):
    """{name: [(line index, stats)]} of every host event in a profile."""
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.setdefault(ev.name, []).append((i, dict(ev.stats)))
    return out


def test_generate_opens_a_span_around_each_dispatch(cfg, tmp_path):
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(0))
    prefill = serve.make_prefill_step(cfg, None, cache_len=P + G)
    step = serve.make_serve_step(cfg, None)
    tokens = jnp.zeros((B, P), jnp.int32)
    serve.generate(prefill, step, params, tokens, G)      # compile first
    jax.profiler.start_trace(str(tmp_path))
    _, out = serve.generate(prefill, step, params, tokens, G)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    events = _profile_events(tmp_path)
    assert len(events[scopes.SERVE_PREFILL]) == 1
    assert len(events[scopes.SERVE_DECODE_STEP]) == G - 1
    assert len(events[scopes.SERVE_ARGMAX]) == G


def test_plan_server_spans_in_a_profile(tmp_path):
    w = moe_workload(ClusterSpec(2, 4), 256, 2, seed=0)
    jax.profiler.start_trace(str(tmp_path))
    with PlanServer(workers=1) as srv:
        srv.request(w)            # miss: queued, synthesized cold
        srv.request(w)            # hit on the client's fast path
        srv.drain(30.0)
    jax.profiler.stop_trace()
    events = _profile_events(tmp_path)
    submits = events[scopes.PLAN_SUBMIT]
    assert [s["outcome"] for _, s in submits] == ["queued", "hit"]
    assert isinstance(submits[0][1]["request_id"], int)
    synth = events[scopes.PLAN_SYNTHESIZE]
    kinds = [s["kind"] for _, s in synth]
    assert kinds[0] == "cold"
    assert set(kinds) <= {"cold", "warm", "upgrade", "prewarm", "rerepair"}
    # the client and the worker are different threads, so different lines
    assert synth[0][0] != submits[0][0]


def test_spans_import_nothing_without_jax(subproc):
    out = subproc("""
import sys
from repro import scopes
from repro.serving import PlanServer
from repro.core.traffic import ClusterSpec, moe_workload
with scopes.span(scopes.PLAN_SUBMIT) as s:
    s.set_metadata(outcome="hit")
with PlanServer(workers=1) as srv:
    srv.request(moe_workload(ClusterSpec(2, 2), 64, 2, seed=1))
print("JAX_LOADED", "jax" in sys.modules)
""", n_devices=1)
    assert "JAX_LOADED False" in out


def test_queue_wait_counts_every_dequeued_request():
    srv = PlanServer(workers=2, prewarm=False)
    taken = []
    real_get = srv.queue.get

    def get(timeout=None):
        req = real_get(timeout)
        if req is not None:
            taken.append(req)
        return req

    srv.queue.get = get
    with srv:
        for seed in range(6):
            srv.request(moe_workload(ClusterSpec(2, 4), 256, 2, seed=seed))
        srv.drain(30.0)
        snap = srv.telemetry_snapshot()
    assert len(taken) >= 6
    wait = snap["queue_wait"]
    assert wait["count"] == len(taken) and wait["seconds_sum"] >= 0.0
    assert wait["by_tier"]["INTERACTIVE"]["count"] == sum(
        r.tier.name == "INTERACTIVE" for r in taken)


def test_telemetry_queue_wait_schema():
    t = Telemetry()
    assert t.snapshot()["queue_wait"] == {"count": 0, "seconds_sum": 0.0,
                                          "by_tier": {}}
    t.observe_queue_wait("INTERACTIVE", 0.25)
    t.observe_queue_wait("INTERACTIVE", 0.5)
    t.observe_queue_wait("BACKGROUND", 2.0)
    assert t.snapshot()["queue_wait"] == {
        "count": 3, "seconds_sum": 2.75,
        "by_tier": {"INTERACTIVE": {"count": 2, "seconds_sum": 0.75},
                    "BACKGROUND": {"count": 1, "seconds_sum": 2.0}}}


def test_plan_exchange_carries_its_scopes_on_four_devices(subproc):
    out = subproc("""
import re
from functools import partial
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import scopes
from repro.comm import plan_all_to_all
from repro.core.schedulers import get_scheduler
from repro.core.traffic import ClusterSpec, moe_workload
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("pod", "data"))
plan = get_scheduler("flash").synthesize(
    moe_workload(ClusterSpec(2, 2), 256, 2, seed=0))
spec = P(("pod", "data"))
f = jax.shard_map(partial(plan_all_to_all, slow_axis="pod",
                          fast_axes=("data",), plan=plan),
                  mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
text = jax.jit(f).lower(jnp.zeros((16, 8, 128), jnp.float32)).compile() \\
    .as_text()
parts = {p for n in re.findall(r'op_name="([^"]*)"', text)
         for p in n.split("/")}
for name in (scopes.EXCHANGE_PACK, scopes.EXCHANGE_STAGE,
             scopes.EXCHANGE_UNPACK):
    assert name in parts, name
print("EXCHANGE_SCOPES_OK")
""", n_devices=4)
    assert "EXCHANGE_SCOPES_OK" in out


def test_scope_names_are_dotted_and_unique():
    names = scopes.DEVICE_SCOPES + scopes.HOST_SPANS
    assert len(set(names)) == len(names)
    for name in names:
        assert re.fullmatch(r"[a-z0-9_]+\.[a-z0-9_]+", name), name
