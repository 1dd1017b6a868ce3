"""Percent of the round's row updates that changed a row's value.

``100 · Σ changed_rows / Σ row_updates`` over the program's ``repro.solve``
records of the traced solves: the last ``len(run.timed)``, whose rounds must
be the window's.
"""

from bench import scopes


def read(run):
    records = scopes.program_spans("repro.solve")
    if not records or not run.timed or len(records) < len(run.timed):
        return None
    last = records[-len(run.timed):]
    if [r.attrs.get("rounds") for r in last] != [s.rounds for s in run.timed]:
        return None
    updates = sum(r.attrs["row_updates"] for r in last)
    if not updates or any(r.attrs.get("changed_rows") is None for r in last):
        return None
    return 100.0 * sum(r.attrs["changed_rows"] for r in last) / updates
