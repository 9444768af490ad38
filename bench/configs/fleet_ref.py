"""Plain reference of the decide step of the paper's Algorithm 1
(arXiv:2511.16182, Section V.B), for one cell's candidate rows.

For every candidate job ``j`` (running at site ``s_j``) and destination
site ``d``:

    t_transfer = 8 * checkpoint_bytes / bandwidth(s_j, d)       (inf at 0)
    t_cost     = t_transfer + t_load + t_downtime
    feasible   = t_cost < alpha * window(d)                      (time)
               and (P_sys / P_node) * t_transfer < window(d)      (energy)
               and t_transfer < class-B limit                     (class C)
    benefit    = gamma * max(0, min(window(d), rem) - min(green(s_j), rem))
                 - beta * queue_penalty * (queue_load(d) - load(s_j))
                 - queue_penalty  if d has no free slot
    valid      = feasible and d != s_j
                 and benefit > max(t_cost, min_benefit)

The destination is the valid ``d`` of largest benefit, ties broken by the
smaller transfer time and then the lower site id; ``-1`` where no ``d``
is valid.  With ``eps > 0`` and ``forecast_sigma_s > 0`` the time gate
uses the eps-quantile of the window, ``window + Phi^-1(eps) * sigma``
clipped at 0.

Everything is computed in the dtype given (float64 for the reference,
a lower precision for the control).

Beside the decide step, the episode's guarantees as the configuration
states them: the job stream the seed gives (:func:`job_stream`, the
arrival process of the paper's Section VII mix), and :func:`audit` of an
episode's outcome against it: every job present once with its own
arrival, size, class, home and compute time, every job finished with
all its compute done and no sooner than its compute allows, the energy
drawn equal to the jobs' compute energy plus the migrations' energy, and
the migrations counted once.  It imports nothing of the program.
"""
from __future__ import annotations

import statistics
from typing import Dict

import numpy as np

HOUR = 3600.0
GB = 1e9


def destinations(rows: Dict[str, np.ndarray], params: Dict[str, float],
                 consts: Dict[str, float], dtype=np.float64) -> np.ndarray:
    """Destination per job row (``-1``: stay).  ``rows`` holds the
    unpadded columns: per job ``sizes, t_loads, rem, cur_green,
    load_src, s_i`` and ``bw`` (jobs x sites); per site ``W, bq_load,
    free_slots``."""
    f = lambda x: np.asarray(x, np.float64).astype(dtype)  # noqa: E731
    size, t_load, rem = f(rows["sizes"]), f(rows["t_loads"]), f(rows["rem"])
    green, load_src = f(rows["cur_green"]), f(rows["load_src"])
    bw, W, bq = f(rows["bw"]), f(rows["W"]), f(rows["bq_load"])
    s_i = np.asarray(rows["s_i"], np.int64)
    k, n = bw.shape
    with np.errstate(divide="ignore", invalid="ignore"):
        tt = (f(8.0) * size[:, None]) / bw
    tt = np.where(bw > 0, tt, f(np.inf))
    Wd = W[None, :]
    t_cost = tt + t_load[:, None] + f(consts["t_downtime_s"])
    alpha = f(params["alpha"])
    eps, sigma = params.get("eps", 0.0), params.get("forecast_sigma_s", 0.0)
    if eps > 0.0 and sigma > 0.0:
        lo = Wd + f(statistics.NormalDist().inv_cdf(eps) * sigma)
        time_ok = t_cost < alpha * np.maximum(lo, f(0.0))
    else:
        time_ok = t_cost < alpha * Wd
    energy_ok = f(consts["p_sys_kw"] / consts["p_node_kw"]) * tt < Wd
    not_c = tt < f(consts["class_b_max_s"])
    remj = rem[:, None]
    avoided = np.maximum(f(0.0), np.minimum(Wd, remj)
                         - np.minimum(green[:, None], remj))
    qp = params["queue_penalty_s"]
    benefit = (f(params["gamma"]) * avoided
               - f(params["beta"] * qp) * (bq[None, :] - load_src[:, None]))
    no_slot = np.asarray(rows["free_slots"]) <= 0
    benefit = benefit + np.where(no_slot, f(-qp), f(0.0))[None, :]
    other = np.arange(n)[None, :] != s_i[:, None]
    valid = (time_ok & energy_ok & not_c & other
             & (benefit > np.maximum(t_cost, f(params["min_benefit_s"]))))
    best = np.where(valid, benefit, f(-np.inf)).max(axis=1, initial=-np.inf)
    tie = valid & (benefit == best[:, None])
    tmin = np.where(tie, tt, f(np.inf)).min(axis=1, initial=np.inf)
    tie = tie & (tt == tmin[:, None])
    return np.where(valid.any(axis=1), tie.argmax(axis=1), -1)


def job_stream(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    """The episode's jobs from its seed: arrival, compute seconds,
    checkpoint bytes, class (0, 1, 2 for A, B, C) and home site, in
    arrival order."""
    rng = np.random.default_rng(seed + 1)
    horizon = cfg["days"] * 24 * HOUR
    n = cfg["n_jobs"]
    arrivals = np.sort(rng.uniform(0, horizon * 0.75, n))
    skew = np.asarray(cfg["arrival_skew"][: cfg["n_sites"]], float)
    skew = skew / skew.sum()
    sigma = 0.6
    mu = np.log(cfg["mean_compute_h"]) - sigma ** 2 / 2
    out = {k: np.zeros(n) for k in ("compute_s", "ckpt_bytes")}
    out["cls"] = np.zeros(n, np.int64)
    out["home"] = np.zeros(n, np.int64)
    ranges = (cfg["size_a_gb"], cfg["size_b_gb"], cfg["size_c_gb"])
    for i in range(n):
        u = rng.random()
        cls = 0 if u < cfg["frac_a"] else (
            1 if u < cfg["frac_a"] + cfg["frac_b"] else 2)
        lo, hi = ranges[cls]
        out["cls"][i] = cls
        out["ckpt_bytes"][i] = rng.uniform(lo, hi) * GB
        out["compute_s"][i] = float(np.clip(rng.lognormal(mu, sigma),
                                            0.5, 24.0)) * HOUR
        out["home"][i] = int(rng.choice(cfg["n_sites"], p=skew))
    out["arrival_s"] = arrivals
    return out


def audit(cfg: Dict, seed: int, jobs: Dict[str, np.ndarray],
          totals: Dict[str, float]) -> Dict[str, float]:
    """The episode's outcome against its guarantees.  ``jobs`` holds the
    episode's per-job columns (``jid``, ``arrival_s``, ``compute_s``,
    ``ckpt_bytes``, ``cls``, ``home``, ``progress_s``, ``done_s``,
    ``migrations``), ``totals`` its ``grid_kwh``, ``renewable_kwh``,
    ``migration_kwh`` and ``migrations``.  Returns the counts of
    departures and the energy balance's relative gap."""
    want = job_stream(cfg, seed)
    order = np.argsort(jobs["jid"], kind="stable")
    got = {k: np.asarray(v)[order] for k, v in jobs.items()}
    n = cfg["n_jobs"]
    if len(order) != n or not np.array_equal(got["jid"], np.arange(n)):
        return {"jobs_differ": float(n),
                "jobs_unfinished": float(n), "energy_balance_gap": np.inf,
                "migrations_differ": float(n)}
    differ = np.zeros(n, bool)
    for k in ("arrival_s", "compute_s", "ckpt_bytes", "cls", "home"):
        differ |= got[k] != want[k]
    # finished: all compute done, and not before arrival plus compute
    earliest = want["arrival_s"] + want["compute_s"]
    unfinished = ((got["done_s"] < 0)
                  | (got["progress_s"] != want["compute_s"])
                  | (got["done_s"] < earliest * (1.0 - 1e-9)))
    p_node = cfg["algorithm1"]["p_node_kw"]
    compute_kwh = float(np.sum(want["compute_s"])) * p_node / HOUR
    drawn = totals["grid_kwh"] + totals["renewable_kwh"]
    mig = totals["migration_kwh"]
    balance = abs(drawn - (compute_kwh + mig)) / (compute_kwh + mig)
    if not (0.0 <= mig <= totals["grid_kwh"]
            and totals["renewable_kwh"] >= 0.0):
        balance = np.inf  # migration energy is billed to the grid alone
    return {"jobs_differ": float(differ.sum()),
            "jobs_unfinished": float(unfinished.sum()),
            "energy_balance_gap": float(balance),
            "migrations_differ": float(abs(int(np.sum(got["migrations"]))
                                           - int(totals["migrations"])))}
