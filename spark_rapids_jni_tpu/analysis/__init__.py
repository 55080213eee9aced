"""First-party static analysis + runtime sanitizers.

Two halves, one discipline:

* **Static** (``core``, ``concurrency``, ``tracepass``, ``knobpass``) —
  AST passes over the whole package run by ``tools/srjt_lint.py`` and
  gated in CI (``ci/lint_smoke.sh``).  They catch the bug classes this
  repo has historically found *by hand*: lock-order inversions and
  unguarded shared mutation (hostcache/join_plan LRU races, prefetch
  take-before-load), trace-poisoning host syncs and silent retraces
  (PR 11's ``jax.default_device`` recompile), and knob drift (environ
  reads whose defaults/docs live nowhere).
* **Runtime** (``sanitize``) — ``SRJT_SANITIZE=1`` arms a lock-order
  watchdog and a retrace tripwire in the live process; ``strict`` makes
  violations raise (``tests/conftest.py`` runs all of tier-1 strict).

This ``__init__`` stays import-light on purpose: ``analysis.sanitize``
is imported by hot modules (``utils``, ``exec``) at process start, so
nothing here may pull in jax or the rest of the package.
"""

from __future__ import annotations

__all__ = ["core", "concurrency", "tracepass", "knobpass", "sanitize"]
