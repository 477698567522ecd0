"""Run one benchmark cell once: set up, measure a window, check outputs.

The served path is the program's own: ``repro.launch.serve``'s
``make_prefill_step`` / ``make_serve_step`` / ``generate`` over weights
that the benchmark makes from the seed, with ``a2a_impl="plan"`` and the
daemon's plan on a mesh, and ``repro.serving``'s ``PlanServer`` /
``PlanClient`` for the plan stream.  The harness adds only a closed-loop
request pool around ``generate`` (every generated token is fetched to the
host as it is produced, as a streaming server must), an open-loop plan
stream, host clocks, and the check against the plain reference.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import cells, peaks, trace_reduce
from .compile_log import CompileLog
from .seeds import seed_seq
from .traffic.moe_traffic import DriftStream, moe_matrix
from .traffic.prompts import ZipfPrompts

METRICS_DIR = os.path.join(cells.BENCH_DIR, "metrics")
E2E_DIR = os.path.join(cells.BENCH_DIR, "end_to_end")
TRACE_ROOT = os.path.join(cells.ROOT, ".bench_traces")
ANSWER_WAIT_S = 60.0          # how long past the close answers may come


def process_age_s() -> float:
    """Seconds since this process started (Linux)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - start


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- what a run measured --------------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything a metric reader may read; see ``bench/metrics``."""

    cell: cells.Cell
    arch: object
    dims: dict
    chips: int
    peaks: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    tokens: int = 0
    useful_flops: float = 0.0
    batches: int = 0
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    tpot_s: List[float] = dataclasses.field(default_factory=list)
    plan_latency_s: List[float] = dataclasses.field(default_factory=list)
    programs_in_window: int = 0
    telemetry: Optional[Dict] = None
    trace: Optional[trace_reduce.Trace] = None
    trace_lo: float = 0.0
    trace_hi: float = 0.0
    trace_devices: List[int] = dataclasses.field(default_factory=list)
    plan: object = None


class _Closed(Exception):
    """The window closed while a batch was still decoding."""


# -- the request pool -----------------------------------------------------------

class RequestPool:
    """Closed loop: a batch of requests is issued when the previous
    batch's last tokens reach the host.  Each call's tokens are fetched
    as they are produced; a token counts when it reaches the host."""

    def __init__(self, serve, prefill, step, params, put, traffic: dict,
                 run: Run):
        self.serve, self.prefill, self.step = serve, prefill, step
        self.params, self.put = params, put
        self.batch = traffic["batch"]
        self.prompt_len = traffic["prompt_len"]
        self.gen_len = traffic["gen_len"]
        self.run = run
        self.completed: List[tuple] = []     # (prompts, served [B, G])
        self.attempted = 0
        self.failed = 0

    def _batch(self, prompts: np.ndarray, t_end: float, count: bool):
        from jax.profiler import TraceAnnotation

        arrivals: List[float] = []
        served: List[np.ndarray] = []

        def fetch(toks):
            with TraceAnnotation("bench.token_fetch"):
                served.append(np.asarray(toks))
            arrivals.append(time.perf_counter())
            if arrivals[-1] > t_end:
                raise _Closed

        def prefill(params, batch):
            with TraceAnnotation("bench.prefill"):
                return self.prefill(params, batch)

        def step(params, cache, toks, pos):
            fetch(toks)
            with TraceAnnotation("bench.decode_step"):
                return self.step(params, cache, toks, pos)

        t_issue = time.perf_counter()
        with TraceAnnotation("bench.prompt_put"):
            tokens = self.put(prompts)
        done = False
        try:
            _, out = self.serve.generate(prefill, step, self.params, tokens,
                                         self.gen_len)
            fetch(out[-1])
            done = True
        except _Closed:
            pass
        if count:
            self._account(t_issue, arrivals, t_end)
        return done, np.stack(served, axis=1) if served else None

    def _account(self, t_issue: float, arrivals: List[float],
                 t_end: float) -> None:
        run, b, p = self.run, self.batch, self.prompt_len
        for i, t in enumerate(arrivals):
            if t > t_end:
                break
            if i == 0:
                run.tokens += b * p + b
                run.useful_flops += run.arch.prefill_flops(run.dims, b, p)
                run.ttft_s.extend([t - t_issue] * b)
            else:
                run.tokens += b
                run.useful_flops += run.arch.decode_step_flops(
                    run.dims, b, p + i - 1)
                run.tpot_s.append(t - arrivals[i - 1])

    def warm_up(self, prompts: np.ndarray) -> None:
        """Every program the window uses, run once: prefill, argmax, one
        decode step."""
        saved = self.gen_len
        self.gen_len = min(saved, 2)
        try:
            self._batch(prompts, float("inf"), count=False)
        finally:
            self.gen_len = saved

    def run_window(self, prompts: ZipfPrompts, t_end: float) -> None:
        while time.perf_counter() < t_end:
            batch = prompts.batch(self.batch, self.prompt_len)
            try:
                done, served = self._batch(batch, t_end, count=True)
            except Exception as exc:  # a failed batch fails its requests
                log(f"batch failed: {type(exc).__name__}: {exc}")
                self.attempted += self.batch
                self.failed += self.batch
                continue
            if done:
                self.attempted += self.batch
                self.completed.append((batch, served))
                self.run.batches += 1


# -- the plan stream ------------------------------------------------------------

class PlanStream:
    """Open loop: every period each client asks the daemon for the plan
    of its family's next dispatch matrix at its due time, and waits for
    the answer; latency runs from the due time.  A period's requests are
    spread evenly over its first ``spread_s`` seconds (0: all at once).
    A request the daemon refuses counts as failed."""

    def __init__(self, params: dict, seed: int):
        from repro.core.traffic import ClusterSpec
        from repro.serving import PlanClient, PlanServer

        self.p = params
        self.seed = seed
        self.cluster = ClusterSpec(params["n_servers"], params["m_gpus"])
        n_gpus = params["n_servers"] * params["m_gpus"]
        self.streams = [DriftStream(
            n_gpus, params["tokens_per_gpu"], params["bytes_per_token"],
            top_k=params["top_k"], expert_skew=params["expert_skew"],
            repeat_p=params["repeat_p"],
            drift_p=params["drift_p"],
            drift_low=params["drift_low"], drift_high=params["drift_high"],
            seed=seed_seq(seed, "plan", i))
            for i in range(params["clients"])]
        self.server = PlanServer(workers=params["daemon_workers"])
        self.server.start()
        self.clients = [PlanClient(self.server, algorithm=params["algorithm"],
                                   inline_fallback=False, max_retries=0,
                                   timeout=ANSWER_WAIT_S)
                        for _ in self.streams]
        self.answers: List[tuple] = []   # (matrix, plan, previous plan)
        self.latency: List[float] = []
        self.due: List[float] = []
        self.attempted = 0
        self.failed = 0
        self._lock = threading.Lock()
        self._threads: List[threading.Thread] = []
        self._prev: List[object] = [None] * len(self.streams)

    def _client(self, i: int, t0: float, t_end: float, record: bool,
                offset: float):
        from jax.profiler import TraceAnnotation

        from repro.core.traffic import Workload

        period = self.p["period_s"]
        k = 0
        while True:
            due = t0 + k * period + offset
            if due >= t_end:
                return
            mat = self.streams[i].next()
            w = Workload(self.cluster, mat)
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            try:
                with TraceAnnotation("bench.plan_request"):
                    plan = self.clients[i].get_plan(w).plan
                ok = True
            except Exception as exc:  # refused or never answered
                log(f"plan request failed: {type(exc).__name__}: {exc}")
                plan, ok = None, False
            t_ans = time.perf_counter()
            if record:
                with self._lock:
                    self.attempted += 1
                    if ok:
                        self.latency.append(t_ans - due)
                        self.due.append(due)
                        self.answers.append((mat, plan, self._prev[i]))
                    else:
                        self.failed += 1
            if ok:
                self._prev[i] = plan
            k += 1

    def periods(self, t0: float, t_end: float, record: bool,
                spread_s: float) -> None:
        """Start the clients for [t0, t_end); ``join`` waits for them."""
        n = len(self.streams)
        self._threads = [threading.Thread(
            target=self._client,
            args=(i, t0, t_end, record, i * spread_s / n),
            name=f"plan-client-{i}", daemon=True)
            for i in range(n)]
        for t in self._threads:
            t.start()

    def join(self, timeout: float) -> int:
        """Wait for every client; returns how many are still waiting."""
        deadline = time.perf_counter() + timeout
        for t in self._threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        return sum(t.is_alive() for t in self._threads)

    def warm(self) -> None:
        """The first period, answered before the window opens."""
        now = time.perf_counter()
        self.periods(now, now + 1e-9, record=False, spread_s=0.0)
        self.join(ANSWER_WAIT_S)

    def telemetry(self) -> Dict:
        snap = self.server.telemetry_snapshot()
        c = snap["counters"]
        return {"requests": c.get("requests", 0), "hits": c.get("hits", 0),
                "synth_count": snap["synthesis"]["count"],
                "synth_seconds": snap["synthesis"]["seconds_sum"]}

    def close(self) -> None:
        self.server.stop()


# -- set-up ---------------------------------------------------------------------

# Keys a configuration file sets on the program's registry entry: its
# depth and dtypes.  Every other size must already be the registry's.
RUN_KEYS = ("n_layers", "param_dtype", "compute_dtype")


def _replace(obj, key: str, value):
    """``obj`` with the field ``key`` set; ``a.b`` sets ``b`` of field
    ``a``."""
    head, _, rest = key.partition(".")
    if rest:
        value = _replace(getattr(obj, head), rest, value)
    return dataclasses.replace(obj, **{head: value})


def model_config(cell: cells.Cell):
    """The program's ModelConfig for the cell's configuration file; every
    size in the file must be the registry's or one of its listed cuts.
    The file's ``program`` entries set the program's own options (a
    dotted key sets a field of a nested one); its architecture module
    says which sizes of the program's configuration the file states."""
    from repro.configs import get_config, smoke_config

    conf = cell.config
    model = conf["model"]
    base = smoke_config if conf.get("smoke") else get_config
    cfg = base(conf["registry"])
    for key, value in [*((k, model[k]) for k in RUN_KEYS),
                       *conf.get("program", {}).items()]:
        cfg = _replace(cfg, key, value)
    have = cells.load_arch(conf["arch"]).program_sizes(cfg)
    bad = {k: (model.get(k), have[k]) for k in have if model.get(k) != have[k]}
    if bad:
        raise ValueError(f"configuration {conf['name']}: file vs program "
                         f"(file, program): {bad}")
    return cfg


def daemon_plan(arch, dims: dict, cluster_shape, batch: int,
                prompt_len: int, seed: int):
    """The exchange's plan from the plan daemon, as ``chip_smoke.py``
    gets it: the dispatch matrix of one prefill batch."""
    from repro.core.traffic import ClusterSpec, Workload
    from repro.serving import PlanClient, PlanServer

    pods, fast = cluster_shape
    cluster = ClusterSpec(pods, fast)
    n = pods * fast
    top_k, n_experts, token_bytes = arch.dispatch(dims)
    mat = moe_matrix(n, batch * prompt_len // n, token_bytes, top_k=top_k,
                     n_experts=n_experts, seed=seed_seq(seed, "exchange"))
    with PlanServer(workers=1) as srv:
        client = PlanClient(srv, algorithm="flash", inline_fallback=False)
        answer, sched = client.get_device_schedule(Workload(cluster, mat))
    log(f"plan: daemon answered ({answer.source}) for {pods}x{fast}; "
        f"{sched.n_stages} ppermute stages, pairs {list(sched.pairs)}")
    return answer.plan


def placement(cell: cells.Cell, cfg, tree, device):
    """(mesh or None, the parameters' shardings, the prompts' sharding) of
    the cell's served path: the cell's mesh, else ``device``."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from repro.launch.mesh import make_mesh
    from repro.launch.shardings import batch_shardings, param_shardings

    from . import weights

    shapes = weights.shapes(tree)
    if not cell.mesh:
        one = SingleDeviceSharding(device)
        return None, jax.tree.map(lambda _: one, shapes), one
    mesh = make_mesh(tuple(cell.mesh["shape"]), tuple(cell.mesh["axes"]))
    tokens = jax.ShapeDtypeStruct(
        (cell.traffic["batch"], cell.traffic["prompt_len"]), np.int32)
    return (mesh, param_shardings(cfg, mesh, shapes),
            batch_shardings(mesh, {"tokens": tokens})["tokens"])


def served_steps(cell: cells.Cell, cfg, mesh, plan):
    """The prefill step and the decode step, as the window runs them."""
    from repro.launch import serve

    traffic = cell.traffic
    cache_len = traffic["prompt_len"] + traffic["gen_len"]
    return (serve.make_prefill_step(cfg, mesh, cell.a2a_impl, plan=plan,
                                    cache_len=cache_len),
            serve.make_serve_step(cfg, mesh, cell.a2a_impl, plan=plan))


# -- the run --------------------------------------------------------------------

def run(cell: cells.Cell, seed: int, seconds: float, trace: bool, devices,
        *, lows: Sequence[str] = (), keep_trace: Optional[str] = None,
        compiles: Optional[CompileLog] = None) -> dict:
    """One run of ``cell``: returns the result line's fields and the
    run's info; ``lows`` adds the readings of the reference in those
    precisions (``check.CONTROL``, ``check.WITNESS``)."""
    import jax

    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model

    from . import weights
    from .reference import check

    enable_compile_cache()
    compiles = compiles or CompileLog()
    dev0 = devices[0]
    traffic = cell.traffic
    cfg = model_config(cell)
    arch = cells.load_arch(cell.config["arch"])
    dims = cell.config["model"]
    tree = arch.param_tree(dims)
    run_rec = Run(cell=cell, arch=arch, dims=dims, chips=cell.chips,
                  peaks=peaks.peaks_for(dev0.device_kind)
                  if dev0.platform == "tpu" else {})

    # weights, mesh, plan, programs
    layout = weights.check_layout(
        jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0)), tree)
    if layout:
        raise ValueError(layout)
    mesh, param_sh, tok_sh = placement(cell, cfg, tree, dev0)
    params = weights.make_params(tree, seed, out_shardings=param_sh)
    jax.block_until_ready(params)
    plan = None
    if cell.a2a_impl == "plan":
        plan = daemon_plan(arch, dims, cell.plan_cluster, traffic["batch"],
                           traffic["prompt_len"], seed)
    run_rec.plan = plan
    prefill, step = served_steps(cell, cfg, mesh, plan)
    pool = RequestPool(serve, prefill, step, params,
                       lambda a: jax.device_put(a, tok_sh), traffic, run_rec)
    s = traffic["prompt_tokens"]["s"]
    pool.warm_up(ZipfPrompts(dims["vocab"], s, seed_seq(seed, "warm"))
                 .batch(traffic["batch"], traffic["prompt_len"]))
    stream = None
    if traffic.get("plan_stream"):
        stream = PlanStream(traffic["plan_stream"], seed)
        stream.warm()
    prompts = ZipfPrompts(dims["vocab"], s, seed_seq(seed, "prompts"))

    # the window
    trace_dir = None
    if trace:
        trace_dir = os.path.join(TRACE_ROOT, cell.name, str(seed))
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    tel0 = stream.telemetry() if stream else None
    made0 = compiles.programs_made()
    run_rec.setup_s = process_age_s()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    if stream:
        stream.periods(t0, t_end, record=True,
                       spread_s=stream.p["spread_s"])
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        pool.run_window(prompts, t_end)
    run_rec.window_s = seconds
    run_rec.programs_in_window = compiles.programs_made() - made0
    if trace:
        jax.profiler.stop_trace()
    t_close = time.perf_counter()
    waiting = stream.join(ANSWER_WAIT_S + 5.0) if stream else 0
    if stream:
        run_rec.telemetry = {k: v - tel0[k]
                             for k, v in stream.telemetry().items()}
        run_rec.plan_latency_s = list(stream.latency)
        stream.close()

    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices[:cell.chips])
    if trace:
        run_rec.trace = trace_reduce.load(trace_dir)
        run_rec.trace_lo, run_rec.trace_hi = trace_reduce.window_bounds(
            run_rec.trace)
        used = {d.id for d in devices[:cell.chips]}
        run_rec.trace_devices = sorted(used & set(run_rec.trace.device_ops))
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the program's state goes before the reference runs
    completed = pool.completed
    pool_attempted, pool_failed = pool.attempted, pool.failed
    del pool, params, prefill, step, plan
    jax.clear_caches()

    checks, info = check.check_outputs(
        cell, arch, dims, seed, completed, devices[:cell.chips],
        stream.answers if stream else None,
        unanswered=waiting,
        m_gpus=traffic["plan_stream"]["m_gpus"] if stream else 0,
        lows=lows)
    correct = check.passes(checks)

    attempted = pool_attempted + waiting + (stream.attempted if stream
                                            else 0)
    failed = pool_failed + waiting + (stream.failed if stream else 0)
    line = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        line["metrics"] = cells.read_metrics(cell.per_layer, run_rec,
                                             METRICS_DIR)
    else:
        line["metrics"] = cells.read_metrics(cell.end_to_end, run_rec,
                                             E2E_DIR)
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": cell.chips, "memory_peak_bytes": int(memory_peak)}
    if trace:
        tr = run_rec.trace
        lo, hi = run_rec.trace_lo, run_rec.trace_hi
        device["busy_s"] = trace_reduce.device_busy_s(
            tr, run_rec.trace_devices, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        line["device"] = device
        line["breakdown"] = trace_reduce.breakdown(tr, run_rec.trace_devices,
                                                   lo, hi)
    else:
        line["device"] = device
    line["checks"] = checks
    info.update({"compile": compiles.line(), "batches": run_rec.batches,
                 "seconds_after_close": time.perf_counter() - t_close,
                 "programs_in_window": run_rec.programs_in_window})
    if stream and stream.latency:
        lat = np.asarray(stream.latency)[np.argsort(stream.due)]
        third = max(1, len(lat) // 3)
        info.update({
            "plan_requests": stream.attempted,
            "plan_ms_p50": float(np.median(lat)) * 1e3,
            "plan_ms_max": float(lat.max()) * 1e3,
            "plan_first_third_ms_p50": float(np.median(lat[:third])) * 1e3,
            "plan_last_third_ms_p50": float(np.median(lat[-third:])) * 1e3})
    if run_rec.tpot_s:
        info["tpot_ms_p50"] = float(np.median(run_rec.tpot_s)) * 1e3
    if run_rec.ttft_s:
        info["ttft_ms_p50"] = float(np.median(run_rec.ttft_s)) * 1e3
    return {"line": line, "info": info}
