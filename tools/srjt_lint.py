#!/usr/bin/env python3
"""srjt_lint — first-party static analysis over the package.

Runs the three passes from ``spark_rapids_jni_tpu/analysis/`` (see the
README "Static analysis & sanitizers" section for the rule catalog):

* concurrency  — ``conc-lock-order``, ``conc-mixed-guard``,
  ``conc-global-augassign``
* retrace/host-sync — ``trace-host-sync``, ``trace-branch``,
  ``trace-iter``
* knobs        — ``knob-env``, ``knob-unregistered``, ``knob-undoc``

Usage::

  python tools/srjt_lint.py                  # lint vs ci/lint_baseline.json
  python tools/srjt_lint.py --no-baseline    # every finding, raw
  python tools/srjt_lint.py --write-baseline # accept the current tree
  python tools/srjt_lint.py --knob-table     # refresh README knob table

Exit status: 0 when no non-baselined findings, 1 otherwise.  The tool
imports only the stdlib + the ``analysis`` package (which is stdlib-only)
— no jax, so it runs anywhere in <30 s (CI budget, ``ci/lint_smoke.sh``).

Suppression: ``# srjt-lint: disable=<rule>[,<rule>]`` on (or directly
above) the finding line, or an entry in the baseline file.  Baseline
entries match on (rule, path, message) so line drift doesn't resurrect
them; ratchet by fixing and running ``--write-baseline``.
"""

from __future__ import annotations

import argparse
import collections
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

# The analysis package is stdlib-only; import its modules directly so the
# lint tool never touches jax-importing package code.
from spark_rapids_jni_tpu.analysis import (  # noqa: E402
    concurrency, core, knobpass, tracepass)

SCAN_SUBDIRS = ("spark_rapids_jni_tpu", "tools")
SCAN_EXTRA = ("chip_smoke.py",)
BASELINE_DEFAULT = os.path.join("ci", "lint_baseline.json")
KNOB_TABLE_BEGIN = "<!-- knob-table:begin (generated by tools/srjt_lint.py --knob-table; do not hand-edit) -->"
KNOB_TABLE_END = "<!-- knob-table:end -->"


def collect(root: str) -> list:
    return core.collect_sources(root, subdirs=SCAN_SUBDIRS,
                                extra_files=SCAN_EXTRA)


def run_all(root: str, sources: list) -> list:
    registry = knobpass.load_registry(root)
    readme_path = os.path.join(root, "README.md")
    readme_text = None
    if os.path.isfile(readme_path):
        with open(readme_path, encoding="utf-8") as f:
            readme_text = f.read()
    findings = []
    findings += concurrency.run(sources)
    findings += tracepass.run(sources)
    findings += knobpass.run(sources, set(registry), readme_text)
    return findings


def refresh_knob_table(root: str) -> int:
    """Regenerate the README knob table between the markers in place."""
    import importlib.util
    path = os.path.join(root, "spark_rapids_jni_tpu", "utils", "knobs.py")
    spec = importlib.util.spec_from_file_location("_srjt_knobs_cli", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    table = mod.markdown_table()
    readme = os.path.join(root, "README.md")
    with open(readme, encoding="utf-8") as f:
        text = f.read()
    try:
        head, rest = text.split(KNOB_TABLE_BEGIN, 1)
        _old, tail = rest.split(KNOB_TABLE_END, 1)
    except ValueError:
        print("README.md is missing the knob-table markers:\n  "
              f"{KNOB_TABLE_BEGIN}\n  {KNOB_TABLE_END}", file=sys.stderr)
        return 1
    new = head + KNOB_TABLE_BEGIN + "\n\n" + table + "\n" \
        + KNOB_TABLE_END + tail
    if new != text:
        with open(readme, "w", encoding="utf-8") as f:
            f.write(new)
        print("README.md knob table refreshed "
              f"({len(mod.REGISTRY)} knobs).")
    else:
        print(f"README.md knob table already current "
              f"({len(mod.REGISTRY)} knobs).")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=_ROOT)
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default {BASELINE_DEFAULT})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, ignoring the baseline")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept the current tree's findings as baseline")
    ap.add_argument("--knob-table", action="store_true",
                    help="refresh the README knob table and exit")
    args = ap.parse_args(argv)

    root = os.path.abspath(args.root)
    if args.knob_table:
        return refresh_knob_table(root)

    t0 = time.monotonic()
    sources = collect(root)
    by_rel = {s.rel: s for s in sources}
    raw = run_all(root, sources)

    # inline suppressions always apply; baseline unless --no-baseline
    baseline_path = os.path.join(
        root, args.baseline or BASELINE_DEFAULT)
    inline_filtered = core.filter_findings(raw, by_rel, baseline=None)

    if args.write_baseline:
        core.Baseline.write(baseline_path, inline_filtered)
        print(f"wrote {len(inline_filtered)} findings to "
              f"{os.path.relpath(baseline_path, root)}")
        return 0

    baseline = None if args.no_baseline \
        else core.Baseline.load(baseline_path)
    findings = core.filter_findings(raw, by_rel, baseline=baseline)

    for f in findings:
        print(f)

    per_rule = collections.Counter(f.rule for f in findings)
    baselined = len(inline_filtered) - len(findings)
    dt = time.monotonic() - t0
    print(f"--- srjt_lint: {len(sources)} files, "
          f"{len(findings)} finding(s)"
          + (f" ({baselined} baselined)" if baseline is not None else "")
          + f", {dt:.1f}s")
    for rule in sorted(per_rule):
        print(f"    {rule:24s} {per_rule[rule]}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
