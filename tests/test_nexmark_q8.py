"""NEXmark Query 8 ("monitor new users") end to end on the CPU at a small
size: the benchmark's own query text (``benchmark/configs/
nexmark-q8-new-users.sql``) through ``plan_query`` into the engine, its sink's
rows and both first-level aggregates' output held to a plain numpy
computation over the connector's own batches."""

import json
import os
import string
from datetime import datetime, timezone

import numpy as np
import pytest

from arroyo_tpu.batch import TIMESTAMP_FIELD

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERY = os.path.join(REPO, "benchmark", "configs", "nexmark-q8-new-users.sql")
INTER, WIDTH = 5_000, 10_000_000   # 2,000 events a 10 s window: 40 persons, 120 auctions
EVENTS, SEED = 12_000, 35          # six windows


def q8_sql(out_path: str) -> str:
    with open(QUERY) as f:
        text = string.Template(f.read()).substitute(
            seed=SEED, sink="$sink", event_rate=0,
            inter_event_micros=INTER, first_event_micros=0)
    text = text.replace("seed = %d" % SEED, "seed = %d,\n  event_count = %d" % (SEED, EVENTS))
    sink = "connector = 'single_file', path = '%s', format = 'json', type = 'sink'" % out_path
    assert "connector = '$sink', type = 'sink'" in text
    return text.replace("connector = '$sink', type = 'sink'", sink)


def the_stream() -> dict:
    """Every event's columns from the connector itself, batch by batch."""
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    src = NexmarkSource({"inter_event_micros": INTER, "first_event_micros": 0, "seed": SEED,
                         "columns": ["person", "person.id", "auction", "auction.seller"]})
    batches = [src._generate(np.arange(lo, min(lo + 512, EVENTS)))
               for lo in range(0, EVENTS, 512)]
    return {c: np.concatenate([np.asarray(b[c]) for b in batches])
            for c in ("person", "person.id", "auction", "auction.seller", TIMESTAMP_FIELD)}


def counts_by_window(stream: dict, kind: str, column: str) -> dict:
    """window start -> {id: how often among the window's events of ``kind``}."""
    out: dict = {}
    ws = stream[TIMESTAMP_FIELD] // WIDTH * WIDTH
    for w, i in zip(ws[stream[kind]].tolist(), stream[column][stream[kind]].tolist()):
        per = out.setdefault(w, {})
        per[i] = per.get(i, 0) + 1
    return out


def micros(iso: str) -> int:
    dt = datetime.fromisoformat(iso).replace(tzinfo=timezone.utc)
    return int(dt.timestamp()) * 1_000_000 + dt.microsecond


def tapped_rows(batches: list, key: str, value: str = "__agg_0") -> dict:
    """window start -> {key: count}, as one aggregate emitted them (the
    plan's names: the key's source column, the first aggregate)."""
    out: dict = {}
    for b in batches:
        for w, k, v in zip(np.asarray(b["window_start"]).tolist(),
                           np.asarray(b[key]).tolist(), np.asarray(b[value]).tolist()):
            per = out.setdefault(w, {})
            assert k not in per, (w, k)
            per[k] = v
    return out


@pytest.mark.parametrize("probe", ["host", "device"])
def test_q8_end_to_end_equals_numpy_over_the_connectors_batches(probe, tmp_path):
    from arroyo_tpu import config as cfg
    from arroyo_tpu.engine import Engine
    from arroyo_tpu.obs import trace
    from arroyo_tpu.sql import plan_query

    if probe == "device":
        cfg.update({"device.join-min-rows": 0, "device.force-device-join": True})
    out = str(tmp_path / "new_sellers.json")
    job = f"q8-{probe}"
    engine = Engine(plan_query(q8_sql(out)).graph, job_id=job)
    if not engine.tasks:
        engine.build()
    taps: dict = {}
    for (nid, _sub), task in engine.tasks.items():
        keys = engine.graph.nodes[nid].config.get("key_fields")
        if engine.graph.nodes[nid].op.value == "tumbling_aggregate":
            into = taps.setdefault(tuple(keys), [])
            collect = task.collector.collect

            def tapped(batch, *a, _collect=collect, _into=into, **kw):
                _into.append(batch)
                return _collect(batch, *a, **kw)

            task.collector.collect = tapped
    assert set(taps) == {("person.id",), ("auction.seller",)}
    engine.run_to_completion(timeout=180)

    stream = the_stream()
    persons = counts_by_window(stream, "person", "person.id")
    sellers = counts_by_window(stream, "auction", "auction.seller")
    assert len(persons) == EVENTS * INTER // WIDTH == 6
    assert all(len(p) == 40 and set(p.values()) == {1} for p in persons.values())
    # each aggregate's own output, window by window
    assert tapped_rows(taps[("person.id",)], "person.id") == persons
    assert tapped_rows(taps[("auction.seller",)], "auction.seller") == sellers
    # the sink: who registered in a window and opened an auction in it
    want = sorted((w, i, 1, sellers[w][i]) for w, per in persons.items()
                  for i in per if i in sellers.get(w, {}))
    with open(out) as f:
        got = [json.loads(line) for line in f if line.strip()]
    assert sorted((micros(r["ws"]), r["id"], r["registered"], r["opened"]) for r in got) == want
    assert len(want) > 20
    # and the join said where it probed: every window once (the windows the
    # end of the stream closes together are one fused probe on the host)
    spans = trace.spans("join.probe", job=job)
    assert {s.args["on"] for s in spans} == {probe}
    assert sum(s.args.get("windows", 1) for s in spans) == len(persons)
    assert sum(s.args["pairs"] for s in spans) == len(want)
