"""The reader of the account's ``sketch`` (metrics/agg_sketch_us_per_event)
over a hand-made ring: the aggregate tasks' seconds summed per event of the
span, nothing from a program whose account lacks the field (the parent of
PR 45), nothing without an aggregate task; and the manifest lists it for the
cells that report ``events_per_s``."""

import threading

import pytest

from harness import cells

from arroyo_tpu.obs import trace

S = 1_000_000_000
# windows no record of a real run overlaps: a century of monotonic time on,
# clear of test_stall_metrics.py's
BASE = 3_100_000_000 * S
KEYS = dict.fromkeys(("cpu", "inbox_wait", "put_wait", "device_wait", "self_time"), 0.0)


def ring(records):
    """Append hand-made task.account marks to a ring of their own thread's."""
    def write():
        r = trace._ring()
        for node, t, args in records:
            r.append(("task.account", ("hand-made", node, 0), None, t, t, args))
    t = threading.Thread(target=write)
    t.start()
    t.join()


def run_over(i, tasks, events=1_000_000):
    lo = BASE + i * 60 * S
    return {"window": {"opened": lo / 1e9, "closed": (lo + 10 * S) / 1e9},
            "span": {"seconds": 10.0, "events": events}, "tasks": tasks}, lo


def read(run):
    return cells.Cell("q7-sat").reader("agg_sketch_us_per_event")(run)


def test_the_aggregates_sketch_seconds_per_event_of_the_span():
    tasks = [{"node": "agg_s4", "stage": "aggregate"}, {"node": "agg_s9", "stage": "aggregate"},
             {"node": "join_s13", "stage": "join"}]
    run, lo = run_over(0, tasks)
    ring([("agg_s4", lo + S, dict(KEYS, sketch=1.0)), ("agg_s4", lo + 9 * S, dict(KEYS, sketch=1.3)),
          ("agg_s9", lo + S, dict(KEYS, sketch=0.0)), ("agg_s9", lo + 9 * S, dict(KEYS, sketch=0.1)),
          ("join_s13", lo + S, dict(KEYS, sketch=0.0)), ("join_s13", lo + 9 * S, dict(KEYS, sketch=5.0))])
    assert read(run) == pytest.approx(0.4)  # 0.3 s + 0.1 s over a million events
    run["tasks"] = tasks[2:]
    assert read(run) is None  # no aggregate task: nothing to read
    run["tasks"], run["span"]["events"] = tasks, 0
    assert read(run) is None


def test_an_account_without_the_field_gives_nothing():
    """The parent's marks carry no ``sketch``: the reader returns nothing and
    does not raise, and the line leaves the metric out."""
    run, lo = run_over(1, [{"node": "agg_p4", "stage": "aggregate"}])
    ring([("agg_p4", lo + S, dict(KEYS)), ("agg_p4", lo + 9 * S, dict(KEYS))])
    assert read(run) is None
    run, _lo = run_over(2, [{"node": "agg_none", "stage": "aggregate"}])
    assert read(run) is None  # no mark at all


def test_the_manifest_lists_it_for_the_cells_that_report_the_rate():
    manifest = cells.manifest()
    entry = next(m for m in manifest["per_layer"] if m["name"] == "agg_sketch_us_per_event")
    rate = next(m for m in manifest["end_to_end"] if m["name"] == "events_per_s")
    assert entry == {"name": "agg_sketch_us_per_event", "unit": "us/event", "better": "lower",
                     "source": "program_counter", "layer": "slot aggregate",
                     "moves": "events_per_s", "workloads": entry["workloads"]}
    assert set(entry["workloads"]) == set(rate["workloads"])
