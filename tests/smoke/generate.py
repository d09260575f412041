#!/usr/bin/env python
"""Generate smoke-test inputs and golden outputs.

The golden outputs are computed by independent plain-Python oracles (dict
loops, no engine code), mirroring how the reference pins behavior with
golden files (crates/arroyo-sql-testing/golden_outputs). Re-run after
changing inputs or adding queries:  python tests/smoke/generate.py
"""

from __future__ import annotations

import json
import os
import sys
from collections import defaultdict
from datetime import datetime, timezone

HERE = os.path.dirname(os.path.abspath(__file__))
INPUTS = os.path.join(HERE, "inputs")
GOLDEN = os.path.join(HERE, "golden")

BASE = 1696871600 * 1_000_000  # 2023-10-09T17:13:20Z
S = 1_000_000


def iso(us: int) -> str:
    dt = datetime.fromtimestamp(us // S, tz=timezone.utc)
    base = dt.strftime("%Y-%m-%dT%H:%M:%S")
    frac = us % S
    if frac == 0:
        return base
    if frac % 1000 == 0:
        return f"{base}.{frac // 1000:03d}"
    return f"{base}.{frac:06d}"


def iso_tz(us: int) -> str:
    return iso(us) + "+00:00"


# --------------------------------------------------------------------------
# inputs


def gen_impulse():
    rows = []
    for i in range(400):
        ts = BASE + i * 200_000
        rows.append({"timestamp": iso_tz(ts), "counter": i, "subtask_index": 0})
    return rows


def gen_cars():
    rows = []
    for i in range(300):
        ts = BASE + i * 250_000
        rows.append({
            "timestamp": iso_tz(ts),
            "driver_id": 100 + i % 7,
            "event_type": "pickup" if i % 2 == 0 else "dropoff",
            "location": f"loc_{i % 5}",
        })
    return rows


def gen_bids():
    rows = []
    for i in range(600):
        ts = BASE + i * 100_000
        rows.append({
            "datetime": iso_tz(ts),
            "auction": 1000 + ((i * 7) % 5) * 100,
            "price": (i * 13) % 1000 + 1,
            "bidder": f"b{i % 11}",
        })
    return rows


def gen_orders():
    rows = []
    for i in range(120):
        ts = BASE + i * 500_000
        rows.append({
            "timestamp": iso_tz(ts),
            "order_id": i,
            "customer_id": i % 10,
            "amount": (i * 37) % 500,
        })
    return rows


def gen_spill_users():
    """Wide-keyspace event stream for the tiered-state smoke family: ~1200
    distinct users over 4000 rows, sized so a few-tens-of-KB spill budget
    is ~10x smaller than the resident keyed state."""
    rows = []
    for i in range(4000):
        ts = BASE + i * 50_000
        rows.append({
            "timestamp": iso_tz(ts),
            "user_id": (i * 37) % 1200,
            "amount": (i * 13) % 500,
        })
    return rows


def gen_customers():
    rows = []
    for i in range(15):
        ts = BASE + i * 3_000_000
        rows.append({"timestamp": iso_tz(ts), "customer_id": i, "name": f"cust_{i}"})
    return rows


def gen_persons():
    """A person registers every half second for a minute (NEXmark q8)."""
    rows = []
    for i in range(120):
        ts = BASE + i * 500_000
        rows.append({"datetime": iso_tz(ts), "id": 2000 + i, "name": f"person-{i}"})
    return rows


def gen_auctions():
    """Four auctions a second for a minute; the seller is one of the persons
    registered so far, so some sellers registered in the auction's own 10 s
    window and most before it (NEXmark q8)."""
    rows = []
    for j in range(240):
        ts = BASE + j * 250_000 + 100_000
        registered = j // 2 + 1
        rows.append({"datetime": iso_tz(ts), "id": 5000 + j,
                     "seller": 2000 + (j * 13) % registered})
    return rows


def gen_aggregate_updates():
    """Debezium envelope stream over an orders table (id pk): creates,
    updates (quantity/status churn), deletes — deterministic."""
    envs = []
    state = {}
    products = ["widget", "gadget", "sprocket"]
    for i in range(60):
        row = {
            "id": i, "customer_name": f"cust_{i % 8}",
            "product_name": products[i % 3], "quantity": (i * 7) % 20 + 1,
            "price": round(9.99 + (i % 5) * 2.5, 2), "status": "new",
        }
        envs.append({"before": None, "after": row, "op": "c"})
        state[i] = row
    for i in range(0, 60, 4):  # update every 4th order
        before = dict(state[i])
        after = dict(before, quantity=before["quantity"] + 3, status="shipped")
        envs.append({"before": before, "after": after, "op": "u"})
        state[i] = after
    for i in range(0, 60, 10):  # delete every 10th
        envs.append({"before": dict(state[i]), "after": None, "op": "d"})
        del state[i]
    return envs, state


def input_ts(row, field):
    s = row[field].replace("+00:00", "")
    dt = datetime.fromisoformat(s).replace(tzinfo=timezone.utc)
    return int(dt.timestamp() * S)


# --------------------------------------------------------------------------
# window helpers


def tumble_start(ts: int, width: int) -> int:
    return (ts // width) * width


def hop_starts(ts: int, slide: int, width: int):
    first = ((ts - width) // slide + 1) * slide
    if first > ts:
        first -= slide
    starts = []
    s = max(first, ((ts - width) // slide + 1) * slide)
    # all starts s with s <= ts < s + width, s multiple of slide
    k = (ts - width) // slide + 1
    while k * slide <= ts:
        if ts < k * slide + width:
            starts.append(k * slide)
        k += 1
    return starts


def sessions(ts_list: list[int], gap: int):
    """Sorted event times -> list of (start, end, count-slice indices)."""
    out = []
    cur = None
    for t in sorted(ts_list):
        if cur is None or t - cur[1] > gap:
            if cur is not None:
                out.append(cur)
            cur = [t, t, 1]
        else:
            cur[1] = t
            cur[2] += 1
    if cur is not None:
        out.append(cur)
    return [(s, e + gap, n) for s, e, n in out]


# --------------------------------------------------------------------------
# oracles (one per query)


def o_select_star(ins):
    return [dict(r, timestamp=iso(input_ts(r, "timestamp"))) for r in ins["cars"]]


def o_expressions(ins):
    out = []
    for r in ins["impulse"]:
        c = r["counter"]
        if not (10 <= c < 60):
            continue
        if 30 <= c <= 39:
            continue
        out.append({
            "c": c,
            "doubled": c * 2,
            "parity": "even" if c % 2 == 0 else "odd",
            "clamped": c ** 0.5,
            "label": f"row_{c}",
        })
    return out


def o_tumbling_aggregates(ins):
    W = 10 * S
    byw = defaultdict(list)
    for r in ins["impulse"]:
        byw[tumble_start(input_ts(r, "timestamp"), W)].append(r["counter"])
    out = []
    for w, cs in sorted(byw.items()):
        out.append({
            "start": iso(w), "end": iso(w + W), "rows": len(cs),
            "total": sum(cs), "min_c": min(cs), "max_c": max(cs),
            "avg_c": sum(cs) / len(cs),
        })
    return out


def o_grouped_aggregates(ins):
    W = 10 * S
    byk = defaultdict(list)
    for r in ins["impulse"]:
        k = (tumble_start(input_ts(r, "timestamp"), W), r["counter"] % 3)
        byk[k].append(r["counter"])
    return [
        {"start": iso(w), "g": g, "rows": len(cs), "total": sum(cs)}
        for (w, g), cs in sorted(byk.items())
    ]


def o_sliding_window(ins):
    slide, width = 2 * S, 10 * S
    byk = defaultdict(list)
    for r in ins["bids"]:
        ts = input_ts(r, "datetime")
        for s in hop_starts(ts, slide, width):
            byk[(s, r["auction"])].append(r["price"])
    return [
        {"start": iso(s), "end": iso(s + width), "auction": a,
         "bids": len(ps), "top_price": max(ps)}
        for (s, a), ps in sorted(byk.items())
    ]


def o_session_window(ins):
    gap = 20 * S
    byu = defaultdict(list)
    for r in ins["impulse"]:
        u = 0 if r["counter"] % 10 == 0 else r["counter"]
        byu[u].append(input_ts(r, "timestamp"))
    out = []
    for u, ts_list in sorted(byu.items()):
        for s, e, n in sessions(ts_list, gap):
            out.append({"start": iso(s), "end": iso(e), "user_id": u, "rows": n})
    return out


def _hop_counts(bids):
    slide, width = 2 * S, 10 * S
    byk = defaultdict(int)
    for r in bids:
        ts = input_ts(r, "datetime")
        for s in hop_starts(ts, slide, width):
            byk[(s, r["auction"])] += 1
    return byk


def o_nexmark_q5(ins):
    byk = _hop_counts(ins["bids"])
    maxn = defaultdict(int)
    for (w, _a), n in byk.items():
        maxn[w] = max(maxn[w], n)
    return [
        {"auction": a, "count": n}
        for (w, a), n in sorted(byk.items())
        if n >= maxn[w]
    ]


def o_windowed_inner_join(ins):
    W = 20 * S
    pick = defaultdict(int)
    drop = defaultdict(int)
    for r in ins["cars"]:
        k = (tumble_start(input_ts(r, "timestamp"), W), r["driver_id"])
        if r["event_type"] == "pickup":
            pick[k] += 1
        else:
            drop[k] += 1
    out = []
    for (w, d), p in sorted(pick.items()):
        if (w, d) in drop:
            out.append({"start": iso(w), "driver_id": d, "pickups": p,
                        "dropoffs": drop[(w, d)]})
    return out


def o_windowed_full_join(ins):
    W = 20 * S
    pick = defaultdict(int)
    drop = defaultdict(int)
    for r in ins["cars"]:
        k = (tumble_start(input_ts(r, "timestamp"), W), r["driver_id"])
        if r["event_type"] == "pickup" and r["driver_id"] % 2 == 0:
            pick[k] += 1
        if r["event_type"] == "dropoff" and r["driver_id"] % 3 == 0:
            drop[k] += 1
    out = []
    for (w, d), p in sorted(pick.items()):
        if (w, d) in drop:
            out.append({"driver_id": d, "other_driver": d, "pickups": p,
                        "dropoffs": drop[(w, d)]})
        else:
            out.append({"driver_id": d, "other_driver": None, "pickups": p,
                        "dropoffs": None})
    for (w, d), dr in sorted(drop.items()):
        if (w, d) not in pick:
            out.append({"driver_id": None, "other_driver": d, "pickups": None,
                        "dropoffs": dr})
    return out


def o_updating_aggregate(ins):
    byg = defaultdict(list)
    for r in ins["impulse"]:
        byg[r["counter"] % 7].append(r["counter"])
    return [
        {"g": g, "c": len(cs), "total": sum(cs)} for g, cs in sorted(byg.items())
    ]


def o_spill_keyspace(ins):
    byu = defaultdict(list)
    for r in ins["spill_users"]:
        byu[r["user_id"]].append(r["amount"])
    return [
        {"u": u, "c": len(a), "total": sum(a)} for u, a in sorted(byu.items())
    ]


def o_filter_updating_aggregates(ins):
    byg = defaultdict(int)
    for r in ins["impulse"]:
        byg[r["counter"] % 7] += 1
    return [{"g": g, "c": c} for g, c in sorted(byg.items()) if c % 2 == 0]


def o_updating_inner_join(ins):
    names = {c["customer_id"]: c["name"] for c in ins["customers"]}
    out = []
    for o in ins["orders"]:
        if o["customer_id"] in names:
            out.append({
                "order_id": o["order_id"], "customer_id": o["customer_id"],
                "name": names[o["customer_id"]], "amount": o["amount"],
            })
    return out


def o_updating_left_join(ins):
    orders_by_cust = defaultdict(list)
    for o in ins["orders"]:
        orders_by_cust[o["customer_id"]].append(o["order_id"])
    out = []
    for c in ins["customers"]:
        oids = orders_by_cust.get(c["customer_id"])
        if oids:
            for oid in oids:
                out.append({"customer_id": c["customer_id"], "name": c["name"],
                            "order_id": oid})
        else:
            out.append({"customer_id": c["customer_id"], "name": c["name"],
                        "order_id": None})
    return out


def _final_right_sub(ins):
    """Final state of the updating subquery: count(*) per counter%2 over
    impulse counters < 3 -> [(counter_mod_2, right_count)]."""
    byg = defaultdict(int)
    for r in ins["impulse"]:
        if r["counter"] < 3:
            byg[r["counter"] % 2] += 1
    return sorted(byg.items())


def o_updating_right_join(ins):
    """impulse RIGHT JOIN updating-agg subquery ON counter = right_count
    WHERE counter < 3 (reference rejects updating right sides; we run it).
    The WHERE on the nullable left column drops null-padded rows."""
    counters = {r["counter"] for r in ins["impulse"]}
    out = []
    for cm2, rc in _final_right_sub(ins):
        if rc in counters and rc < 3:
            out.append({"left_counter": rc, "counter_mod_2": cm2, "right_count": rc})
    return out


def o_updating_full_join(ins):
    """(impulse counters < 5) FULL JOIN updating-agg subquery ON
    counter = right_count: matches plus null-padded rows from BOTH sides."""
    left = sorted({r["counter"] for r in ins["impulse"] if r["counter"] < 5})
    sub = _final_right_sub(ins)
    matched_rc = set()
    out = []
    for cm2, rc in sub:
        if rc in left:
            out.append({"left_counter": rc, "counter_mod_2": cm2, "right_count": rc})
            matched_rc.add(rc)
    for c in left:
        if c not in matched_rc:
            out.append({"left_counter": c, "counter_mod_2": None, "right_count": None})
    for cm2, rc in sub:
        if rc not in left:
            out.append({"left_counter": None, "counter_mod_2": cm2, "right_count": rc})
    return out


def o_updating_inner_join_with_updating(ins):
    counters = {r["counter"] for r in ins["impulse"]}
    return [
        {"left_counter": rc, "counter_mod_2": cm2, "right_count": rc}
        for cm2, rc in _final_right_sub(ins)
        if rc in counters and rc < 3
    ]


def o_debezium_pass_through(ins):
    _envs, final = gen_aggregate_updates()
    return [
        {"id": r["id"], "customer_name": r["customer_name"],
         "product_name": r["product_name"], "quantity": r["quantity"],
         "price": r["price"], "status": r["status"]}
        for r in final.values()
    ]


def o_debezium_coercion(ins):
    return [{"counter": r["counter"]} for r in ins["impulse"]]


def o_debezium_agg(ins):
    _envs, final = gen_aggregate_updates()
    byp = defaultdict(lambda: [0, set(), 0])
    for r in final.values():
        acc = byp[f"p_{r['product_name']}"]
        acc[0] += 1
        acc[1].add(r["customer_name"])
        acc[2] += r["quantity"] + 5
    return [{"p": p, "c": c, "d": len(d), "q": q + 10}
            for p, (c, d, q) in sorted(byp.items())]


def o_json_operators(ins):
    return [
        {"a": "test", "b": json.dumps(r["driver_id"]),
         "c": json.dumps(r["event_type"]), "d": "null"}
        for r in ins["cars"]
    ]


def o_unnest_in_view(ins):
    return [{"counter": r["counter"]} for r in ins["impulse"]]


def o_offset_impulse_join(ins):
    W = 1 * S
    out = []
    for r in ins["impulse"]:
        ts = input_ts(r, "timestamp")
        out.append({"start": iso(tumble_start(ts, W)), "counter": r["counter"]})
    return out


def o_async_udf(ins):
    return [{"counter": -2 * r["counter"]} for r in ins["impulse"]]


def o_most_active_driver(ins):
    SLIDE, W = 20 * S, 60 * S
    byw = defaultdict(lambda: defaultdict(int))
    for r in ins["cars"]:
        ts = input_ts(r, "timestamp")
        sb = (ts // SLIDE) * SLIDE
        for k in range(W // SLIDE):
            start = sb - k * SLIDE
            byw[start][r["driver_id"]] += 1
    out = []
    for w, drivers in sorted(byw.items()):
        # ORDER BY c DESC, driver_id DESC, take row 1
        d, c = max(drivers.items(), key=lambda kv: (kv[1], kv[0]))
        out.append({"start": iso(w), "driver_id": d, "cnt": c, "rn": 1})
    return out


def o_count_distinct(ins):
    W = 20 * S
    groups = defaultdict(lambda: (set(), 0))
    for r in ins["cars"]:
        w = tumble_start(input_ts(r, "timestamp"), W)
        drivers, n = groups[(w, r["event_type"])]
        drivers.add(r["driver_id"])
        groups[(w, r["event_type"])] = (drivers, n + 1)
    return [
        {"start": iso(w), "et": et, "drivers": len(d), "events": n}
        for (w, et), (d, n) in sorted(groups.items())
    ]


def o_memory_table(ins):
    return [{"driver_id": r["driver_id"], "event_type": r["event_type"]}
            for r in ins["cars"]]


def o_window_function(ins):
    W = 10 * S
    byk = defaultdict(int)
    for r in ins["bids"]:
        byk[(tumble_start(input_ts(r, "datetime"), W), r["auction"])] += 1
    byw = defaultdict(list)
    for (w, a), n in byk.items():
        byw[w].append((a, n))
    out = []
    for w, pairs in sorted(byw.items()):
        ranked = sorted(pairs, key=lambda p: (-p[1], p[0]))
        for i, (a, n) in enumerate(ranked[:2]):
            out.append({"start": iso(w), "auction": a, "bids": n, "row_num": i + 1})
    return out


def o_union_all(ins):
    out = []
    for r in ins["cars"]:
        if r["event_type"] == "pickup":
            out.append({"driver_id": r["driver_id"], "tag": "pick"})
    for r in ins["cars"]:
        if r["event_type"] == "dropoff":
            out.append({"driver_id": r["driver_id"], "tag": "drop"})
    return out


def o_having_filter(ins):
    W = 10 * S
    byk = defaultdict(list)
    for r in ins["bids"]:
        byk[(tumble_start(input_ts(r, "datetime"), W), r["auction"])].append(r["price"])
    return [
        {"start": iso(w), "auction": a, "bids": len(ps),
         "avg_price": sum(ps) / len(ps)}
        for (w, a), ps in sorted(byk.items())
        if len(ps) > 18
    ]


def o_nexmark_q1(ins):
    return [
        {"auction": r["auction"], "price_eur": r["price"] * 89 // 100,
         "bidder": r["bidder"]}
        for r in ins["bids"]
    ]


def o_nexmark_q2(ins):
    return [
        {"auction": r["auction"], "price": r["price"]}
        for r in ins["bids"]
        if r["auction"] in (1000, 1200, 1400)
    ]


def o_nexmark_q7(ins, W=10 * S):
    per = defaultdict(int)
    glob = defaultdict(int)
    for r in ins["bids"]:
        w = tumble_start(input_ts(r, "datetime"), W)
        per[(w, r["auction"])] = max(per[(w, r["auction"])], r["price"])
        glob[w] = max(glob[w], r["price"])
    return [
        {"auction": a, "price": p}
        for (w, a), p in sorted(per.items())
        if p == glob[w]
    ]


def o_nexmark_q7_minute(ins):
    # the NEXMark specification's own window: [RANGE 1 MINUTE SLIDE 1 MINUTE]
    return o_nexmark_q7(ins, W=60 * S)


def o_nexmark_q8(ins):
    # persons who opened an auction in the 10 s window they registered in
    W = 10 * S
    registered = {(tumble_start(input_ts(r, "datetime"), W), r["id"]): r["name"]
                  for r in ins["persons"]}
    opened = defaultdict(int)
    for r in ins["auctions"]:
        opened[(tumble_start(input_ts(r, "datetime"), W), r["seller"])] += 1
    return [
        {"id": i, "name": name, "starttime": iso(w), "opened": opened[(w, i)]}
        for (w, i), name in sorted(registered.items())
        if (w, i) in opened
    ]


def o_every_aggregate(ins):
    W = 20 * S
    byw = defaultdict(list)
    for r in ins["orders"]:
        byw[tumble_start(input_ts(r, "timestamp"), W)].append(r["amount"])
    return [
        {"start": iso(w), "n": len(a), "total": sum(a), "lo": min(a),
         "hi": max(a), "mean": sum(a) / len(a),
         "dbl_total": sum(x * 2 for x in a),
         "shifted_lo": min(a) + 100}
        for w, a in sorted(byw.items())
    ]


def o_session_udaf(ins):
    gap = 5 * S
    byc = defaultdict(list)
    for r in ins["orders"]:
        byc[r["customer_id"]].append((input_ts(r, "timestamp"), r["amount"]))
    out = []
    for c, rows in sorted(byc.items()):
        rows.sort()
        # split into sessions by gap, mirroring sessions()
        cur: list = []
        groups = []
        last = None
        for t, amt in rows:
            if last is not None and t - last > gap:
                groups.append(cur)
                cur = []
            cur.append((t, amt))
            last = t
        if cur:
            groups.append(cur)
        for g in groups:
            amts = [a for _t, a in g]
            # p90 mirrors numpy.percentile(linear interpolation)
            import numpy as _np

            out.append({
                "start": iso(g[0][0]), "customer_id": c, "n": len(g),
                "p90_amount": float(_np.percentile(_np.array(amts, dtype=float), 90)),
                "spread": max(amts) - min(amts),
            })
    return out


def o_windowed_left_join(ins):
    W = 20 * S
    pick = defaultdict(int)
    drop = defaultdict(int)
    for r in ins["cars"]:
        k = (tumble_start(input_ts(r, "timestamp"), W), r["driver_id"])
        if r["event_type"] == "pickup":
            pick[k] += 1
        if r["event_type"] == "dropoff" and r["driver_id"] % 3 == 0:
            drop[k] += 1
    return [
        {"driver_id": d, "pickups": p, "dropoffs": drop.get((w, d))}
        for (w, d), p in sorted(pick.items())
    ]


def o_string_keys(ins):
    W = 20 * S
    byk = defaultdict(int)
    for r in ins["cars"]:
        byk[(tumble_start(input_ts(r, "timestamp"), W), r["location"], r["event_type"])] += 1
    return [
        {"start": iso(w), "location": loc, "event_type": et, "events": n}
        for (w, loc, et), n in sorted(byk.items())
    ]


def o_nested_subquery(ins):
    W = 10 * S
    byk = defaultdict(int)
    for r in ins["cars"]:
        byk[(tumble_start(input_ts(r, "timestamp"), W), r["driver_id"])] += 1
    byw = defaultdict(list)
    for (w, _d), n in byk.items():
        byw[w].append(n)
    return [
        {"busiest_driver_events": max(ns), "drivers": len(ns)}
        for w, ns in sorted(byw.items())
    ]


def o_cast_to_sink_type(ins):
    return [
        {"counter_text": str(r["counter"]),
         "counter_float": float(r["counter"]),
         "counter_small": r["counter"]}
        for r in ins["impulse"]
    ]


def o_null_comparisons(ins):
    out = []
    for r in ins["impulse"]:
        c = r["counter"]
        if c < 5:
            out.append({"counter": c, "small": c, "is_gt": c > 2})
        else:
            # no right-side match: padding is NULL and the projected
            # comparison propagates NULL (three-valued logic), not False
            out.append({"counter": c, "small": None, "is_gt": None})
    return out


ORACLES = {
    "select_star": o_select_star,
    "nexmark_q1": o_nexmark_q1,
    "nexmark_q2": o_nexmark_q2,
    "nexmark_q7": o_nexmark_q7,
    "nexmark_q7_minute": o_nexmark_q7_minute,
    "nexmark_q8": o_nexmark_q8,
    "every_aggregate": o_every_aggregate,
    "session_udaf": o_session_udaf,
    "windowed_left_join": o_windowed_left_join,
    "string_keys": o_string_keys,
    "nested_subquery": o_nested_subquery,
    "expressions": o_expressions,
    "tumbling_aggregates": o_tumbling_aggregates,
    "grouped_aggregates": o_grouped_aggregates,
    "sliding_window": o_sliding_window,
    "session_window": o_session_window,
    "nexmark_q5": o_nexmark_q5,
    "windowed_inner_join": o_windowed_inner_join,
    "windowed_full_join": o_windowed_full_join,
    "updating_aggregate": o_updating_aggregate,
    "spill_keyspace": o_spill_keyspace,
    "filter_updating_aggregates": o_filter_updating_aggregates,
    "updating_inner_join": o_updating_inner_join,
    "updating_left_join": o_updating_left_join,
    "updating_right_join": o_updating_right_join,
    "updating_full_join": o_updating_full_join,
    "updating_inner_join_with_updating": o_updating_inner_join_with_updating,
    "async_udf": o_async_udf,
    "memory_table": o_memory_table,
    "count_distinct": o_count_distinct,
    "most_active_driver": o_most_active_driver,
    "offset_impulse_join": o_offset_impulse_join,
    "unnest_in_view": o_unnest_in_view,
    "json_operators": o_json_operators,
    "debezium_pass_through": o_debezium_pass_through,
    "debezium_coercion": o_debezium_coercion,
    "debezium_agg": o_debezium_agg,
    "window_function": o_window_function,
    "union_all": o_union_all,
    "having_filter": o_having_filter,
    "cast_to_sink_type": o_cast_to_sink_type,
    "null_comparisons": o_null_comparisons,
}

# queries whose sinks receive an updating stream (harness debezium-merges
# engine output before diffing; goldens hold the final merged rows)
UPDATING = {
    "updating_aggregate",
    "spill_keyspace",
    "filter_updating_aggregates",
    "updating_inner_join",
    "updating_left_join",
    "updating_right_join",
    "updating_full_join",
    "updating_inner_join_with_updating",
    "debezium_pass_through",
    "debezium_agg",
    "null_comparisons",
}


def main():
    os.makedirs(INPUTS, exist_ok=True)
    os.makedirs(GOLDEN, exist_ok=True)
    ins = {
        "impulse": gen_impulse(),
        "cars": gen_cars(),
        "bids": gen_bids(),
        "orders": gen_orders(),
        "customers": gen_customers(),
        "spill_users": gen_spill_users(),
        "persons": gen_persons(),
        "auctions": gen_auctions(),
    }
    for name, rows in ins.items():
        with open(os.path.join(INPUTS, f"{name}.json"), "w") as f:
            for r in rows:
                f.write(json.dumps(r, separators=(",", ":")) + "\n")
        print(f"inputs/{name}.json: {len(rows)} rows")
    envs, _final = gen_aggregate_updates()
    with open(os.path.join(INPUTS, "aggregate_updates.json"), "w") as f:
        for e in envs:
            f.write(json.dumps(e, separators=(",", ":")) + "\n")
    print(f"inputs/aggregate_updates.json: {len(envs)} envelopes")
    for qname, oracle in ORACLES.items():
        rows = oracle(ins)
        with open(os.path.join(GOLDEN, f"{qname}.json"), "w") as f:
            for r in rows:
                f.write(json.dumps(r, separators=(",", ":")) + "\n")
        print(f"golden/{qname}.json: {len(rows)} rows")


if __name__ == "__main__":
    sys.exit(main())
