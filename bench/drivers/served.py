"""Open loop against ``SO3Service``: rotational-matching requests arrive
at the mix's fixed rate, each one (f, g) pair of band-limited S^2
functions with f = Lambda(R) g for a hidden rotation R drawn from the seed.

Latency is the client's: from when a request was due to when its Future
resolved, so a stall counts against every request it delays.  The
generator's lag (actual submit time minus due time) is printed apart, so a
starved generator is not read as a fast server.

The check, once the window has closed:

  grid_max      the correlation grids of a sample of launches, drawn from
                the seed, against the f64 reference (max |C - C_ref| over
                max |C_ref|)
  peak_max      every answer's peak against Re C_ref at its grid index,
                over max Re C_ref
  rotation_steps  every recovered rotation against its hidden one, in
                grid steps (pi / B)

and every request due in the window must have resolved with an answer.
"""
from __future__ import annotations

import sys
import threading
import time

import numpy as np

from bench import reference, traffic

BETA_MARGIN = 0.2          # hidden beta stays this far from 0 and pi
SAMPLED_LAUNCHES = 3
RESOLVE_GRACE_S = 60.0


def s2_mask(B: int) -> np.ndarray:
    return np.abs(np.arange(-(B - 1), B))[None, :] <= np.arange(B)[:, None]


def request_pool(B: int, n: int, seed: int):
    """n (f, g, hidden rotation) triples; f and g complex64 coefficients."""
    pool = []
    for i in range(n):
        r = traffic.rng(seed, 1000 + i)
        g = (r.normal(size=(B, 2 * B - 1))
             + 1j * r.normal(size=(B, 2 * B - 1))) * s2_mask(B)
        rot = (r.uniform(0, 2 * np.pi),
               r.uniform(BETA_MARGIN, np.pi - BETA_MARGIN),
               r.uniform(0, 2 * np.pi))
        f = reference.rotate_s2(g, rot)
        pool.append((f.astype(np.complex64), g.astype(np.complex64), rot))
    return pool


def angle_error(a: float, b: float) -> float:
    d = abs(a - b) % (2 * np.pi)
    return min(d, 2 * np.pi - d)


def answer_readings(B: int, ref_C: np.ndarray, answer, rot) -> dict:
    """peak_max and rotation_steps of one answer."""
    re = ref_C.real
    best = re.max()
    at = re[tuple(answer.index)]
    errs = [angle_error(x, y) for x, y in
            zip((answer.alpha, answer.beta, answer.gamma), rot)]
    return {"peak_max": float(abs(answer.peak - at) / best),
            "rotation_steps": max(errs) * B / np.pi}


def grid_reading(C: np.ndarray, ref_C: np.ndarray) -> float:
    return float(np.abs(C - ref_C).max() / np.abs(ref_C).max())


def control_grids(_engine, fs, gs) -> np.ndarray:
    """The control: the reference computed in bfloat16, put in the place of
    ``CorrelationEngine.correlation_grids``."""
    return np.stack([reference.correlation(
        np.asarray(f, np.complex128), np.asarray(g, np.complex128),
        reference.bf16_round) for f, g in zip(fs, gs)])


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int, log=sys.stderr):
        self.config, self.mix, self.seed, self.log = config, mix, seed, log
        self.B = int(config["B"])
        self.pool = request_pool(self.B, int(mix["pool"]), seed)
        self.captured = []
        self.launches = 0
        self.sample = set()

    def setup(self) -> None:
        import jax.numpy as jnp
        from repro.so3 import SO3Service

        t0 = time.perf_counter()
        self.svc = SO3Service(bandwidths=(self.B,), dtype=jnp.float32,
                              **self.config.get("service", {}))
        self.svc.warmup()
        t_groups = time.perf_counter()
        eng = self.svc.engine(self.B)
        self.V = eng.lane_width
        # every group size a launch can take compiles here, not in the window
        for n in range(self.V, 0, -1):
            futs = [self.svc.submit(f, g) for f, g, _ in self.pool[:n]]
            self.svc.drain()
            for fu in futs:
                fu.result()
        launch = eng.correlation_grids

        def capture(fs, gs):
            C = launch(fs, gs)
            self.launches += 1
            if self.launches in self.sample:
                self.captured.append((list(fs), list(gs), C))
            return C

        eng.correlation_grids = capture
        self.engine = eng
        self.svc.start()
        print(f"set-up: service warm-up {t_groups - t0:.3f} s, group sizes "
              f"{time.perf_counter() - t_groups:.3f} s", file=self.log,
              flush=True)

    def window(self, seconds: float) -> dict:
        import jax

        due, idx = traffic.open_schedule(self.mix, self.seed, seconds)
        r = traffic.rng(self.seed, 3)
        self.launches = 0
        self.sample = set((1 + r.integers(0, max(len(due) // self.V, 1),
                                          SAMPLED_LAUNCHES)).tolist())
        stats0 = dict(self.engine.stats)
        done_at = [None] * len(due)
        all_done = threading.Event()
        left = [len(due)]
        lock = threading.Lock()

        def on_done(_fu, i):
            done_at[i] = time.perf_counter()
            with lock:
                left[0] -= 1
                if not left[0]:
                    all_done.set()

        futs, lag = [], []
        t0 = time.perf_counter()
        for i, (t_due, k) in enumerate(zip(due, idx)):
            wait = t0 + t_due - time.perf_counter()
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench.until_due"):
                    time.sleep(wait)
            f, g, _ = self.pool[k]
            with jax.profiler.TraceAnnotation("bench.submit"):
                fu = self.svc.submit(f, g)
            lag.append(time.perf_counter() - t0 - t_due)
            fu.add_done_callback(lambda fu, i=i: on_done(fu, i))
            futs.append(fu)
        all_done.wait(timeout=max(seconds - (time.perf_counter() - t0), 0)
                      + RESOLVE_GRACE_S)
        window_s = time.perf_counter() - t0
        lag = np.asarray(lag)
        print(f"generator lag: median {np.median(lag) * 1e3:.3f} ms, p95 "
              f"{np.percentile(lag, 95) * 1e3:.3f} ms, max "
              f"{lag.max() * 1e3:.3f} ms over {len(lag)} arrivals",
              file=self.log, flush=True)
        self.results = []
        latencies, failed = [], 0
        for i, fu in enumerate(futs):
            if not fu.done() or fu.exception() is not None:
                failed += 1
                continue
            latencies.append(done_at[i] - t0 - due[i])
            self.results.append((int(idx[i]), fu.result()))
        st = self.engine.stats
        return {"window_s": window_s, "latencies_s": latencies,
                "attempted": len(futs), "failed": failed,
                "counters": {"completed": len(latencies),
                             "launches": st["launches"] - stats0["launches"],
                             "transforms": st["transforms"]
                             - stats0["transforms"]}}

    def gather(self) -> None:
        """The sampled launches' grids, read back; the service runs on."""
        self.grids = []
        for fs, gs, C in self.captured:
            for n in range(len(fs)):
                self.grids.append((np.asarray(fs[n]), np.asarray(gs[n]),
                                   np.asarray(C[n])))
        self.captured = []

    def collect(self) -> None:
        self.svc.close(drain=False)
        self.gather()
        del self.svc, self.engine

    def _pool_index(self, f: np.ndarray, g: np.ndarray) -> int | None:
        for k, (pf, pg, _) in enumerate(self.pool):
            if np.array_equal(pf, f) and np.array_equal(pg, g):
                return k
        return None

    def readings(self) -> dict:
        refs = {}

        def ref(k):
            if k not in refs:
                f, g, _ = self.pool[k]
                refs[k] = reference.correlation(f.astype(np.complex128),
                                                g.astype(np.complex128))
            return refs[k]

        out = {"grid_max": 0.0, "peak_max": 0.0, "rotation_steps": 0.0}
        for f, g, C in self.grids:
            k = self._pool_index(f, g)
            val = float("inf") if k is None else grid_reading(C, ref(k))
            out["grid_max"] = max(out["grid_max"], val)
        if not self.grids:
            out["grid_max"] = float("inf")
        for k, ans in self.results:
            got = answer_readings(self.B, ref(k), ans, self.pool[k][2])
            for name, v in got.items():
                out[name] = max(out[name], v)
        return out

    def check(self) -> list[dict]:
        got = self.readings()
        return [{"name": name, "value": got[name], "limit": limit}
                for name, limit in self.config["limits"].items()]
