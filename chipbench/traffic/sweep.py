"""A Monte-Carlo / corner sweep: back-to-back steps, each the refactor and
refined solve of K seeded value sets of the configuration's pattern
(``factor_batched`` + ``solve_batched`` on a plan from the plan cache).

Parameters (the traffic file):
  systems_per_step  K, the systems of one step
  samples_per_step  systems of each step kept for the reference check,
                    drawn from the seed
  trace_steps       steps in a traced (--trace 1) window

``systems_per_s`` counts the systems that met the configuration's accuracy
contract in the steps that ended within the window, over the time from the
window's start to the end of the last of them.  A step is not started when
the longest step so far would carry it past the window.

Every step draws fresh values and right-hand sides from the seed (stream
``2 + t`` for step t, the warm-up from stream 0), inside the window and
under the ``inputs`` span: a sweep never repeats an input, so a cache of
factors or answers keyed by input has nothing to hit.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import harness
from chipbench.harness import Outcome, rng
from chipbench.reference import perturbed_values


class Traffic:
    def __init__(self, env, params, seed, seconds, traced):
        self.env = env
        self.k = int(params["systems_per_step"])
        self.samples = int(params["samples_per_step"])
        self.trace_steps = int(params["trace_steps"])
        self.seed = seed
        t0 = time.perf_counter()
        self.pick = rng(seed, 1)
        self.pattern = (env.a.indptr, env.a.indices)
        # warm-up: the cell's one shape, compiled and run once
        with env.span("warmup"):
            self._step(self._inputs(0))
        harness.progress(t0, "warm-up step done")
        self.steps = []          # (end s, n_refine, substitutions, failed)
        self.kept = []           # (values, b, x) of the sampled systems

    def _inputs(self, stream):
        """K value sets and right-hand sides, fresh from ``stream``."""
        with self.env.span("inputs"):
            r = rng(self.seed, stream)
            values = perturbed_values(self.env.a_sp, self.k, r)
            return values, r.standard_normal((self.k, self.env.a.n))

    def _step(self, inputs):
        from repro.core import batched

        values, rhs = inputs
        with self.env.span("factor_batched"):
            bst = batched.factor_batched(self.env.analysis, self.pattern,
                                         values)
        with self.env.span("solve_batched"):
            return batched.solve_batched(bst, rhs)

    def window(self, seconds, traced):
        longest = 0.0
        t0 = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - t0
            if traced and len(self.steps) >= self.trace_steps:
                break
            if self.steps and (elapsed >= seconds
                               or elapsed + longest > seconds):
                break
            values, rhs = self._inputs(2 + len(self.steps))
            x, info = self._step((values, rhs))
            end = time.perf_counter() - t0
            longest = max(longest, end - elapsed)
            failed = np.asarray(info["refine_failed"]) | \
                ~np.isfinite(np.asarray(x)).all(axis=1)
            subst = int(self.k + np.sum(info["n_refine_per_system"]))
            self.steps.append((end, int(info["n_refine"]), subst,
                               int(failed.sum())))
            for j in self.pick.choice(self.k, self.samples, replace=False):
                self.kept.append((values[j].copy(), rhs[j].copy(),
                                  np.array(x[j])))
        self.seconds = seconds
        took = np.diff([0.0] + [s[0] for s in self.steps])
        harness.progress(t0, f"{len(took)} steps in the window, step s "
                         f"min {took.min():.4f} median "
                         f"{np.median(took):.4f} max {took.max():.4f}")

    def release(self):
        pass

    def outcome(self):
        done = [s for s in self.steps if s[0] <= self.seconds]
        rate = None
        if done:
            rate = sum(self.k - s[3] for s in done) / done[-1][0]
        counters = dict(steps=len(self.steps), k=self.k,
                        step_ends=[s[0] for s in self.steps],
                        n_refine=[s[1] for s in self.steps],
                        substitutions=[s[2] for s in self.steps])
        e2e = {} if rate is None else {"systems_per_s": rate}
        return Outcome(end_to_end=e2e,
                       attempted=self.k * len(self.steps),
                       failed=sum(s[3] for s in self.steps),
                       counters=counters, cases=self.kept)
