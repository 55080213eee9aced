"""Sums over the program's compile ledger (``utils/metrics.py``): its
``xla:<fun>`` entries, one a program that JAX traced, lowered and compiled
or fetched from the persistent cache in this process; the plans' entries
beside them are left out.  The entries cover the whole process up to the
reader's call, and a correct run compiles nothing in its window
(``compiles_in_window`` has limit 0), so the sums are set-up's.  A program
whose ledger has no ``xla:`` entry (one older than the compile listeners)
gives nothing; a sum may read 0.

``params``: ``add`` names the fields summed, ``subtract`` those taken off,
``scale`` multiplies the result (``0.001``: ms to s).
"""


def read(ctx: dict, params: dict):
    entries = [e for plan, e in ctx["program_metrics"].ledger_snapshot().items()
               if plan.startswith("xla:")]
    if not entries:
        return None
    total = sum(e.get(f, 0) for e in entries for f in params["add"])
    total -= sum(e.get(f, 0) for e in entries
                 for f in params.get("subtract", ()))
    return total * params.get("scale", 1)
