"""Decide ``correct``: what the timed path produced against the reference.

Served tokens.  After the window, a sample of the finished batches (and,
where the cell says so, of the requests in them) drawn from the seed is
run through the plain float32 reference (``model.py``) with the served
tokens fed back, routed in the same groups as the served calls.  At
each compared position the gap is how far the served token's logit lies
below the reference's best logit (greedy decoding picks the best, so a
sound program's gap is rounding, or a route or capacity drop that
rounding flipped).  From the gaps come the numbers a cell may compare
(``gap_stats``): the widest gap, the mean gap, and the share of
positions whose served token is not the reference's best.  A cell
compares the ones its file gives a limit.

Plans.  A sample of the plan daemon's answers is checked against the
dispatch matrix each was asked for (``plan_check.py``): the largest
per-pair byte mismatch (``plan_bytes_err``), and requests still without
an answer a minute after the window closed (``plan_unanswered``); a
request the daemon refused has its answer and counts as failed.

The control reads the same numbers with the reference computed in
float8-e4m3 in the program's place (the token it puts first at each
position), and with the daemon answering each family's previous plan;
``passes`` judges both alike.  A witness in bfloat16, the served
precision, shows how far rounding alone moves the served tokens.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import weights
from ..seeds import seed_seq
from . import model, plan_check

CONTROL = "e4m3"
WITNESS = "bf16"
TOKENS_PER_PASS = 131072      # tokens of one reference pass on one device


def _n_shards(cell) -> int:
    if not cell.mesh:
        return 1
    n = 1
    for size, axis in zip(cell.mesh["shape"], cell.mesh["axes"]):
        if axis != "model":
            n *= size
    return n


def sample(cell, seed: int, completed: Sequence[tuple]
           ) -> Tuple[List[tuple], np.ndarray]:
    """The batches and rows compared: drawn from the seed."""
    rng = np.random.default_rng(seed_seq(seed, "sample"))
    k = min(cell.correct["sample_batches"], len(completed))
    pick = sorted(rng.choice(len(completed), size=k, replace=False))
    batch = cell.traffic["batch"]
    n_rows = cell.correct.get("sample_requests", batch)
    rows = np.sort(rng.choice(batch, size=min(n_rows, batch),
                              replace=False))
    return [completed[i] for i in pick], rows


def _jobs(dims: dict, batches: List[tuple], rows: np.ndarray,
          n_shards: int, devices, globals_: list) -> List[dict]:
    """One forward per device over its share of ``batches``."""
    jobs = []
    for i, dev in enumerate(devices):
        mine = batches[i::len(devices)]
        if not mine:
            continue
        prompts = np.concatenate([b[0] for b in mine])
        served = np.concatenate([b[1] for b in mine])
        per = len(mine[0][0])
        sel = np.concatenate([j * per + rows for j in range(len(mine))])
        jobs.append({"device": dev, "prompts": prompts, "served": served,
                     "rows": sel, "n_shards": n_shards * len(mine),
                     "prompt_len": prompts.shape[1],
                     "tokens": model.forward_tokens(prompts, served),
                     "globals": globals_[i],
                     "embed": globals_[i]["embed"]})
    return jobs


def reference_gaps(dims: dict, seed: int, batches: List[tuple],
                   rows: np.ndarray, n_shards: int, devices,
                   lows: Sequence[str] = ()) -> Dict:
    """Served-token gaps of ``batches`` at ``rows``, and for each
    precision in ``lows`` the gaps of the tokens that the reference
    computed in it puts first.  The batches go through in passes of at
    most ``TOKENS_PER_PASS`` tokens a device, so that any sample fits."""
    maker = weights.LayerMaker(dims)
    devices = list(devices)[:max(1, len(batches))]
    globals_ = [weights.make_globals(dims, seed, device=d) for d in devices]
    ref = model.Reference(dims, maker)
    lowered = {low: model.Reference(dims, maker, low=low) for low in lows}
    (rows_in, p), n_gen = batches[0][0].shape, batches[0][1].shape[1]
    per_pass = max(1, TOKENS_PER_PASS // (rows_in * (p + n_gen - 1)))
    step = per_pass * len(devices)
    out = {"served_gap": [], "dropped": 0, **{low: [] for low in lows}}
    for start in range(0, len(batches), step):
        jobs = _jobs(dims, batches[start:start + step], rows, n_shards,
                     devices, globals_)
        firsts = {low: [_score(job, h, [], low)[1] for job, (h, _) in
                        zip(jobs, r.hidden(seed, jobs))]
                  for low, r in lowered.items()}
        served = zip(jobs, ref.hidden(seed, jobs))
        for i, (job, (h, dropped)) in enumerate(served):
            g, _ = _score(job, h, [firsts[low][i] for low in lows], None)
            out["served_gap"].append(g[..., 0].reshape(-1))
            for j, low in enumerate(lows):
                out[low].append(g[..., 1 + j].reshape(-1))
            out["dropped"] += dropped
    return {k: (np.concatenate(v) if isinstance(v, list) else v)
            for k, v in out.items()}


def _score(job: dict, hidden, extra: List[np.ndarray], low: Optional[str]):
    """(gaps [rows, P, 1 + len(extra)], argmax [rows, P]) at the served
    positions, for the served tokens and each array of ``extra``."""
    import jax

    served, rows = job["served"], job["rows"]
    pos = model.served_positions(job["prompt_len"], served.shape[1])
    cand = np.stack([served[rows]] + list(extra), axis=-1)
    h = hidden[rows][:, pos]
    g = job["globals"]
    best, arg, at = model.score(g["final_norm"]["scale"], g["lm_head"], h,
                                jax.device_put(cand.astype(np.int32),
                                               job["device"]), low=low)
    return model.gaps(best, at), np.asarray(arg)


def gap_stats(g: np.ndarray) -> Dict:
    """The numbers a cell may compare, and the gaps' spread."""
    q = np.percentile(g, [50, 90, 99])
    return {"logit_gap_max": float(g.max()),
            "logit_gap_mean": float(g.mean()),
            "off_best_share": float((g > 0).mean()),
            "p50": float(q[0]), "p90": float(q[1]), "p99": float(q[2]),
            "share_over_0.5": float((g > 0.5).mean())}


def passes(checks: Dict) -> bool:
    """Every number compared has a limit and is within it."""
    return bool(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())


def check_outputs(cell, dims: dict, seed: int, completed: Sequence[tuple],
                  devices, answers: Optional[List[tuple]], unanswered: int,
                  m_gpus: int, lows: Sequence[str] = ()):
    """({name: {"value", "limit"}}, info) for one run.  With
    ``CONTROL`` in ``lows``, ``info["control_checks"]`` holds the
    control's numbers against the same limits."""
    limits = cell.correct["limits"]
    values, control, info = {}, {}, {}
    gap_names = [k for k in limits if k not in ("plan_bytes_err",
                                                "plan_unanswered")]
    if completed:
        t0 = time.perf_counter()
        batches, rows = sample(cell, seed, completed)
        got = reference_gaps(dims, seed, batches, rows, _n_shards(cell),
                             devices, lows)
        stats = gap_stats(got["served_gap"])
        values.update({k: stats[k] for k in gap_names})
        info.update({"compared_tokens": int(got["served_gap"].size),
                     "compared_batches": len(batches),
                     "ref_dropped_assignments": got["dropped"],
                     "ref_seconds": time.perf_counter() - t0,
                     "gaps": stats})
        for low in lows:
            info[f"{low}_gaps"] = gap_stats(got[low])
        if CONTROL in lows:
            control.update({k: info[f"{CONTROL}_gaps"][k]
                            for k in gap_names})
    else:
        values.update({k: float("inf") for k in gap_names})
    if answers is not None:
        rng = np.random.default_rng(seed_seq(seed, "plans"))
        n = min(cell.traffic["plan_stream"]["check_sample"], len(answers))
        pick = rng.choice(len(answers), size=n, replace=False) \
            if answers else []
        errs = [plan_check.plan_error(answers[i][1], answers[i][0], m_gpus)
                for i in pick]
        values["plan_bytes_err"] = max(errs) if errs else float("inf")
        values["plan_unanswered"] = float(unanswered)
        info["compared_plans"] = len(errs)
        if CONTROL in lows:
            stale = [plan_check.plan_error(answers[i][2], answers[i][0],
                                           m_gpus)
                     for i in pick if answers[i][2] is not None]
            control["plan_bytes_err"] = max(stale) if stale else None
            control["plan_unanswered"] = float(unanswered)

    def judged(vals):
        return {k: {"value": v, "limit": limits.get(k)}
                for k, v in vals.items()}

    if CONTROL in lows:
        info["control_checks"] = judged(control)
        info["control_correct"] = passes(
            {k: c for k, c in info["control_checks"].items()
             if c["value"] is not None})
    return judged(values), info
