"""Shared on-chip timing harness: chained-fori-loop trip-count differencing.

The ONE implementation of the differencing methodology for the profiler
tools (bench.py carries its own copy by design — it must stay
self-contained): dependency-chain the body inside one jit via
optimization barriers, difference two trip counts of the same program,
keep the best positive delta.  Returns None when every repeat differenced
non-positive (timing noise) — callers must record an error, not divide.
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def chained_loop(body):
    """jit(data, iters) running ``body`` iters times, dependency-chained;
    the FULL output tree passes through an optimization barrier, so no
    part of the body is dead-code-eliminated."""
    @jax.jit
    def run(data, iters):
        def step(_, carry):
            acc, d = carry
            din = lax.optimization_barrier((d, acc))[0]
            out = body(din)
            out = lax.optimization_barrier(out)
            leaves = [l for l in jax.tree_util.tree_leaves(out) if l.size]
            probe = (lax.convert_element_type(jnp.ravel(leaves[0])[0],
                                              jnp.int32)
                     if leaves else jnp.int32(0))
            return (acc + probe) % jnp.int32(65521), d
        acc, _ = lax.fori_loop(0, iters, step, (jnp.int32(0), data))
        return acc
    return run


def time_diff(body, data, lo: int = 2, hi: int = 8,
              repeats: int = 2) -> float | None:
    """Steady-state seconds/iteration, or None if timing was unusable."""
    run = chained_loop(body)
    np.asarray(run(data, lo))            # compile + warm
    best = None
    good = 0
    for _ in range(repeats + 3):
        t0 = time.perf_counter()
        np.asarray(run(data, lo))
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(run(data, hi))
        t_hi = time.perf_counter() - t0
        per = (t_hi - t_lo) / (hi - lo)
        if per <= 0:
            continue
        good += 1
        best = per if best is None else min(best, per)
        if good >= repeats:
            break
    return best
