"""What decides ``correct``: the sink's rows against the reference's, window
by window, and the guarantees the configuration states. Plain data in,
plain data out; every number compared is printed beside its limit."""

from __future__ import annotations

from collections import Counter


def compare_windows(due: list[int], got: dict[int, list[tuple]],
                    want: dict[int, list[tuple]]) -> dict:
    """``due``: window starts whose result was due in the measured window.
    ``got``/``want``: window start -> rows. A window fails once, as missing
    (no row of it reached the sink by the drain deadline), doubled (a row
    more often than the reference has it, none wrong) or wrong (anything
    else, an off-by-one price as much as a stray row)."""
    missing, wrong, doubled = [], [], []
    for ws in due:
        g, w = Counter(got.get(ws, ())), Counter(want.get(ws, ()))
        if g == w:
            continue
        if not g and w:
            missing.append(ws)
        elif set(g) == set(w) and all(g[r] >= w[r] for r in w):
            doubled.append(ws)
        else:
            wrong.append(ws)
    return {"missing": missing, "wrong": wrong, "doubled": doubled}


def short_of_chips(aggregates: list[dict], chips: int) -> list[dict]:
    """The aggregates (each with ``devices``, the distinct devices its state
    lies on) whose state lies on fewer devices than the cell's ``chips``: a
    cell that asks for four chips and keeps its state on one measures one."""
    return [a for a in aggregates if a["devices"] < chips]


def judge(due: list[int], got: dict, want: dict, guarantees: dict) -> dict:
    """-> correct, attempted, failed and the list of numbers compared.
    ``guarantees`` carries what the run saw of each stated guarantee:
    checkpoints_triggered / checkpoints_not_completed (epoch lists),
    ingest (per first-level aggregate: rows_received, rows_expected; None
    where the reference names no count for it),
    late_rows, spilled_rows, off_platform (aggregates whose state is not on
    the expected platform), short_of_chips (``short_of_chips()`` of the
    run's aggregates), compiles_in_window (program names),
    partials_compared / partials_wrong (windows of first-level aggregates'
    own output held against the reference's, and those that differ)."""
    w = compare_windows(due, got, want)
    failed = len(w["missing"]) + len(w["wrong"]) + len(w["doubled"])
    # an aggregate the reference names no count for is off by all it received
    ingest_off = sum(max(1, a["rows_received"]) if a["rows_expected"] is None
                     else abs(a["rows_received"] - a["rows_expected"])
                     for a in guarantees["ingest"])
    g = guarantees

    def at_most(name, what, value, limit=0):
        return {"name": name, "what": what, "value": value, "limit": limit}

    def at_least(name, what, value, least=1):
        return {"name": name, "what": what, "value": value, "at_least": least}

    compared = [
        at_least("windows_due", "windows due in the measured window", len(due)),
        at_most("windows_missing", "windows missing at the sink", len(w["missing"])),
        at_most("windows_wrong", "windows whose rows differ from the reference's",
                len(w["wrong"])),
        at_most("windows_doubled", "windows with a row more often than the reference has it",
                len(w["doubled"])),
        at_least("partials_compared", "first-level aggregate windows held against the reference",
                 g["partials_compared"]),
        at_most("partials_wrong",
                "first-level aggregate windows whose rows differ from the reference's",
                len(g["partials_wrong"])),
        at_least("checkpoints_triggered", "checkpoints triggered in the window",
                 len(g["checkpoints_triggered"])),
        at_most("checkpoints_not_completed",
                "checkpoints triggered in the window that did not complete",
                len(g["checkpoints_not_completed"])),
        at_least("aggregates_checked", "first-level aggregates checked for lost or doubled rows",
                 len(g["ingest"])),
        at_most("rows_lost_or_doubled",
                "rows lost or doubled between source and first-level aggregates", ingest_off),
        at_most("rows_late", "rows dropped as late (the stream is in order)", g["late_rows"]),
        at_most("rows_spilled", "rows spilled to the host store (the configuration states none)",
                g["spilled_rows"]),
        at_most("aggregates_off_platform",
                "aggregates whose state is not on the expected platform", len(g["off_platform"])),
        at_most("aggregates_short_of_chips",
                "aggregates whose state lies on fewer devices than the cell's chips",
                len(g["short_of_chips"])),
        at_most("compiles_in_window", "programs compiled inside the measured window",
                len(g["compiles_in_window"])),
    ]
    correct = all(
        c["value"] <= c["limit"] if "limit" in c else c["value"] >= c["at_least"]
        for c in compared)
    return {"correct": correct, "attempted": len(due), "failed": failed,
            "compared": compared, "windows": w}
