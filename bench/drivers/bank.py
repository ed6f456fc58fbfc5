"""Closed loop of bank queries: one client sends one query at a time, and
each query is ranked against every template of a bank held on the device
(``CorrelationEngine.load_bank`` once, then ``match_bank`` per query).

The bank is M templates of S^2 coefficients with Re and Im N(0, 1) on the
mask, drawn from the seed.  Each query of the pool is a template drawn
from the seed (the planted one) rotated by a hidden rotation, beta kept
0.2 from the poles.  Nothing is dispatched ahead: a query's latency is the
time from its call to its answer.

The check, once the window has closed, against ``bench.reference_bank``
(the f64 reference correlation of one template at a time) on every
query's winner and a sample of templates drawn from the seed:

  winner_misses   queries whose winner is not the planted template
  rotation_steps  every winner's rotation against the hidden one, in grid
                  steps (pi / B)
  peak_max        the answer's peak against Re C_ref at its grid index,
                  over max Re C_ref
  argmax_gap      max Re C_ref - Re C_ref at the answer's grid index, over
                  max Re C_ref: a peak found in the wrong place of a grid
  stencil_max     the six neighbours the refinement read against Re C_ref
                  at the same indices, over max Re C_ref
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import reference, reference_bank, traffic

BETA_MARGIN = 0.2          # hidden beta stays this far from 0 and pi
SAMPLED_TEMPLATES = 12     # besides every query's winner
WARM_QUERIES = 2


def s2_mask(B: int) -> np.ndarray:
    return np.abs(np.arange(-(B - 1), B))[None, :] <= np.arange(B)[:, None]


def template_bank(B: int, M: int, seed: int) -> np.ndarray:
    """(M, B, 2B-1) complex64 templates: Re and Im N(0, 1) on the mask."""
    r = traffic.rng(seed, 2000)
    shape = (M, B, 2 * B - 1)
    g = (r.normal(size=shape) + 1j * r.normal(size=shape)) * s2_mask(B)
    return g.astype(np.complex64)


def query_pool(templates: np.ndarray, n: int, seed: int):
    """n (query, planted template, hidden rotation) triples; distinct
    planted templates."""
    B = templates.shape[1]
    r = traffic.rng(seed, 2001)
    planted = r.choice(len(templates), size=n, replace=False)
    pool = []
    for m in planted.tolist():
        rot = (r.uniform(0, 2 * np.pi),
               r.uniform(BETA_MARGIN, np.pi - BETA_MARGIN),
               r.uniform(0, 2 * np.pi))
        f = reference.rotate_s2(templates[m].astype(np.complex128), rot)
        pool.append((f.astype(np.complex64), m, rot))
    return pool


def angle_error(a: float, b: float) -> float:
    d = abs(a - b) % (2 * np.pi)
    return min(d, 2 * np.pi - d)


def template_readings(ref: dict, answer) -> dict:
    """peak_max, argmax_gap and stencil_max of one template's answer."""
    re = ref["re"]
    top = re.max()
    at = re[tuple(answer.index)]
    near = [re[p] for p in reference_bank.neighbours(answer.index,
                                                     re.shape[0])]
    return {"peak_max": float(abs(answer.peak - at) / top),
            "argmax_gap": float((top - at) / top),
            "stencil_max": float(max(abs(s - v) for s, v in
                                     zip(answer.stencil, near)) / top)}


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int, log=sys.stderr):
        self.config, self.mix, self.seed, self.log = config, mix, seed, log
        self.B, self.M = int(config["B"]), int(config["M"])
        self.templates = template_bank(self.B, self.M, seed)
        self.pool = query_pool(self.templates, int(mix["pool"]), seed)
        self.order = traffic.closed_order(mix, seed)

    def setup(self) -> None:
        import jax.numpy as jnp
        from repro import plan

        t0 = time.perf_counter()
        t = plan(self.B, jnp.float32, **self.config.get("plan", {}))
        self.engine = t.engine()
        t_bank = time.perf_counter()
        self.bank = self.engine.load_bank(self.templates)
        t_warm = time.perf_counter()
        for f, _, _ in self.pool[:WARM_QUERIES]:   # compiles the chain once
            t_query = time.perf_counter()
            self.engine.match_bank(f, self.bank)
        t_end = time.perf_counter()
        d = t.describe()
        print("plan: " + " ".join(f"{k}={d[k]}" for k in
                                  ("impl", "V", "lchunk", "precision", "tk")),
              file=self.log, flush=True)
        print(f"set-up: plan {t_bank - t0:.3f} s, bank upload "
              f"{t_warm - t_bank:.3f} s ({self.M} templates), warm-up "
              f"{t_end - t_warm:.3f} s (last query {t_end - t_query:.3f} s)",
              file=self.log, flush=True)

    def window(self, seconds: float) -> dict:
        import jax

        stats0 = dict(self.engine.stats)
        self.answers = []
        latencies = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            k = int(self.order[len(latencies) % len(self.order)])
            t_sent = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.query"):
                best, results = self.engine.match_bank(self.pool[k][0],
                                                       self.bank)
            latencies.append(time.perf_counter() - t_sent)
            self.answers.append((k, best, results))
        window_s = time.perf_counter() - t0
        n = len(latencies)
        return {"window_s": window_s, "latencies_s": latencies,
                "attempted": n, "failed": 0,
                "counters": {"completed": n, "templates": n * self.M,
                             "launches": self.engine.stats["launches"]
                             - stats0["launches"]}}

    def collect(self) -> None:
        del self.bank, self.engine

    def sample(self) -> list[tuple[int, int]]:
        """(answer, template) pairs the check compares: every answer's
        winner, and SAMPLED_TEMPLATES pairs drawn from the seed."""
        r = traffic.rng(self.seed, 2002)
        pairs = [(a, best) for a, (_, best, _) in enumerate(self.answers)]
        if self.answers:
            pairs += list(zip(
                r.integers(0, len(self.answers), SAMPLED_TEMPLATES).tolist(),
                r.integers(0, self.M, SAMPLED_TEMPLATES).tolist()))
        return pairs

    def readings(self) -> dict:
        out = {"winner_misses": 0.0, "rotation_steps": 0.0, "peak_max": 0.0,
               "argmax_gap": 0.0, "stencil_max": 0.0}
        if not self.answers:
            return {name: float("inf") for name in out}
        for k, best, results in self.answers:
            _, planted, rot = self.pool[k]
            out["winner_misses"] += float(best != planted)
            errs = [angle_error(x, y) for x, y in
                    zip(results[best].euler, rot)]
            out["rotation_steps"] = max(out["rotation_steps"],
                                        max(errs) * self.B / np.pi)
        refs = {}
        for a, m in self.sample():
            k, _, results = self.answers[a]
            if (k, m) not in refs:
                refs[k, m] = reference_bank.match(self.pool[k][0],
                                                  self.templates[m])
            for name, v in template_readings(refs[k, m], results[m]).items():
                out[name] = max(out[name], v)
        return out

    def check(self) -> list[dict]:
        got = self.readings()
        return [{"name": name, "value": got[name], "limit": limit}
                for name, limit in self.config["limits"].items()]
