"""Tiny nvbench-style benchmark harness.

The reference drives its microbenchmarks with nvbench states and axes
(``benchmarks/row_conversion.cpp:140-149``: named int/string axes, per-state
timed regions, global-memory throughput summaries).  This is the framework's
equivalent: declare axes, get the cartesian product of states, time a
closure per state (warmup + measured iterations, device-synchronised), and
report a table plus machine-readable JSON lines.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import time
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def sync(tree) -> None:
    """Force device execution to complete.

    Materializes one element of every output leaf: the result bytes
    cannot arrive before the computation that produces them has run, on
    any backend (a stricter barrier than ``jax.block_until_ready``).
    """
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and getattr(leaf, "size", 1):
            np.asarray(jax.numpy.ravel(leaf)[0])


def chain_carry(tree) -> jnp.ndarray:
    """A cheap scalar data-dependent on every leaf of ``tree``.

    Feeding this into the next timed iteration chains the iterations so that
    one final :func:`sync` provably executes them all (a lazy backend would
    otherwise skip unmaterialized intermediate calls entirely).
    """
    acc = jnp.zeros((), jnp.int32)
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and getattr(leaf, "size", 1):
            acc = acc + jax.lax.convert_element_type(
                jnp.ravel(leaf)[0], jnp.int32)
    # bounded but NOT statically foldable (x % 1 would simplify to 0 and
    # sever the chain)
    return acc % jnp.int32(251)


@jax.jit
def tie(x, carry):
    """Return ``x`` unchanged but data-dependent on ``carry``.

    ``lax.optimization_barrier`` is opaque to XLA's simplifier, so the
    dependency survives without perturbing values — closures use this to
    chain their inputs to the previous iteration's outputs.
    """
    return jax.lax.optimization_barrier((x, carry))[0]


@dataclasses.dataclass
class State:
    """One point in the axis product; mirrors nvbench's state object."""

    params: Mapping[str, object]
    bytes_per_iter: int = 0      # set by the benchmark body for GB/s

    def __getitem__(self, name):
        return self.params[name]


@dataclasses.dataclass
class Result:
    bench: str
    params: Mapping[str, object]
    seconds: float
    gb_per_s: float


class Bench:
    def __init__(self, name: str, fn: Callable[[State], Callable[..., object]],
                 axes: Mapping[str, Sequence[object]],
                 skip: Callable[[State], str | None] = lambda s: None):
        """``fn(state)`` prepares inputs and returns the timed closure.

        The closure takes one argument — a scalar ``carry`` it must fold into
        its device inputs (e.g. add to one input column) — and returns its
        device outputs.  The harness chains iterations through the carry and
        forces execution once at the end (:func:`sync`), so the measured
        window is device time, amortizing the per-sync round-trip latency
        across all iterations.  ``skip``
        may return a reason string (the reference skips >1M-row string
        states, ``benchmarks/row_conversion.cpp:117-120``).
        """
        self.name, self.fn, self.axes, self.skip = name, fn, axes, skip

    def states(self):
        names = list(self.axes)
        for combo in itertools.product(*(self.axes[n] for n in names)):
            yield State(dict(zip(names, combo)))

    def run(self, warmup: int = 2, iters: int = 5) -> list[Result]:
        results = []
        for state in self.states():
            reason = self.skip(state)
            tag = ", ".join(f"{k}={v}" for k, v in state.params.items())
            if reason:
                print(f"  SKIP {self.name}[{tag}]: {reason}", flush=True)
                continue
            closure = self.fn(state)
            carry = jnp.zeros((), jnp.int32)
            for _ in range(warmup):
                carry = chain_carry(closure(carry))
            sync(carry)
            t0 = time.perf_counter()
            carry = jnp.zeros((), jnp.int32)
            for _ in range(iters):
                carry = chain_carry(closure(carry))
            sync(carry)
            dt = (time.perf_counter() - t0) / iters
            gbps = state.bytes_per_iter / dt / 1e9 if state.bytes_per_iter else 0.0
            results.append(Result(self.name, dict(state.params), dt, gbps))
            print(f"  {self.name}[{tag}]: {dt * 1e3:.2f} ms"
                  + (f"  {gbps:.2f} GB/s" if gbps else ""), flush=True)
        return results


def report(results: Sequence[Result], json_path: str | None = None) -> None:
    """Markdown summary table + one JSON line per state (nvbench's dual
    human/CSV output)."""
    if not results:
        return
    keys = list(results[0].params)
    header = ["bench"] + keys + ["ms", "GB/s"]
    print("\n| " + " | ".join(header) + " |")
    print("|" + "|".join("---" for _ in header) + "|")
    lines = []
    for r in results:
        row = [r.bench] + [str(r.params[k]) for k in keys] \
            + [f"{r.seconds * 1e3:.2f}", f"{r.gb_per_s:.2f}"]
        print("| " + " | ".join(row) + " |")
        lines.append(json.dumps({"bench": r.bench, **r.params,
                                 "seconds": r.seconds,
                                 "gb_per_s": round(r.gb_per_s, 3)}))
    if json_path:
        with open(json_path, "w") as f:
            f.write("\n".join(lines) + "\n")
    print()
