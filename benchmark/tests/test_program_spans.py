"""The twelve metrics that read the program's own spans and counters
(arroyo_tpu/obs/trace.py), in a rehearsal on the CPU: one saturated and one
paced cell report theirs, shares stay shares, and the trail of stamps of
every close adds up to the latency the harness measured from outside."""

import json
import time

import pytest

from harness import cells, runner, stats

SAT = {"prefix_cpu_us_per_event", "agg_cpu_us_per_event", "agg_directory_us_per_event",
       "agg_dispatch_us_per_event", "agg_starved_share", "src_blocked_share",
       "gil_wait_share"}
PACED = {"src_late_ms", "wm_reach_ms", "wm_hold_aggregate_ms", "wm_hold_join_ms",
         "close_read_ms"}


def rehearse(workload, seconds, tmp_path, monkeypatch, capsys):
    """run.py --rehearse --trace 1, in this process so that the span ring
    can be read afterwards: -> (the result line, the run's records)."""
    monkeypatch.setattr(runner, "_report_dir", lambda *_a: str(tmp_path))
    assert runner.main(workload, 2147483659, seconds, True, True, time.monotonic()) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}, line
    with open(tmp_path / "report.json") as f:
        records = json.load(f)["records"]
    return line, dict(records, config=cells.Cell(workload).config)


def test_a_saturated_cell_reports_its_seven(tmp_path, monkeypatch, capsys):
    line, run = rehearse("q7-sat", 2.0, tmp_path, monkeypatch, capsys)
    got = {k: v["value"] for k, v in line["rehearsal_metrics"].items()}
    assert SAT <= set(got) and not PACED & set(got), sorted(got)
    for share in ("agg_starved_share", "src_blocked_share", "gil_wait_share"):
        assert 0.0 <= got[share] <= 100.0, (share, got[share])
    assert got["agg_starved_share"] + got["agg_busy_share"] <= 101.0
    # what the spans time lies inside what the hooks time, CPU inside wall
    assert 0 < got["agg_directory_us_per_event"] + got["agg_dispatch_us_per_event"] \
        <= got["agg_us_per_event"] * 1.05
    assert 0 < got["agg_cpu_us_per_event"] <= got["agg_us_per_event"]
    assert 0 < got["prefix_cpu_us_per_event"] <= got["prefix_us_per_event"]
    # per stage, the account adds up: nothing is counted twice
    from arroyo_tpu.obs import trace

    w = run["window"]
    for t in run["tasks"]:
        a = trace.account_over(t["node"], int(w["opened"] * 1e9), int(w["closed"] * 1e9))
        rest = a["wall"] - a["cpu"] - a["inbox_wait"] - a["put_wait"] - a["device_wait"]
        assert rest >= -0.01 * a["wall"] - 1e-3, (t["node"], a)


def test_a_paced_cell_reports_its_five_and_every_close_adds_up(tmp_path, monkeypatch, capsys):
    from arroyo_tpu.obs import trace

    # the same operators as the saturated test ran in this process, and the
    # same window ends: the readers cut the ring by the run's own window
    line, run = rehearse("q7-paced", 3.0, tmp_path, monkeypatch, capsys)
    got = {k: v["value"] for k, v in line["rehearsal_metrics"].items()}
    assert PACED <= set(got) and not SAT & set(got), sorted(got)
    # the twins from outside read the same
    assert got["src_late_ms"] == pytest.approx(got["gen_late_ms"], abs=1.0)
    assert got["close_read_ms"] == pytest.approx(got["close_fetch_ms"], abs=1.0)
    first = [t["node"] for t in run["tasks"] if t["first_level"]]
    join = next(t["node"] for t in run["tasks"] if t["stage"] == "join")
    width, opened = run["config"]["window"]["width_micros"], int(run["window"]["opened"] * 1e9)
    left = {tid: at for tid, at, _ in trace.stamps("rows.out", join, t0=opened)}
    terms = {"reach": [], "aggregate": [], "join": [], "sink": []}
    assert len(run["closes"]) >= 10
    for c in run["closes"]:
        reach = trace.crossings("wm.in", first, [c["ws"] + width], t0=opened)[0]
        at_join = trace.crossings("wm.in", join, [c["ws"] + 1], t0=opened)[0]
        hops = [reach / 1e6 - c["due"] * 1e3, (at_join - reach) / 1e6,
                (left[c["ws"]] - at_join) / 1e6, c["arrived"] * 1e3 - left[c["ws"]] / 1e6]
        # no hop runs backwards — but the first: a source emits a whole batch
        # when its first event is due, the window's last event among them
        assert all(h >= -0.01 for h in hops[1:]), (c, hops)
        assert abs(c["latency_ms"] - sum(hops)) <= 10.0, (c, hops)
        for k, h in zip(terms, hops):
            terms[k].append(h)
    assert got["wm_reach_ms"] == pytest.approx(stats.median(terms["reach"]), abs=1e-6)
    assert got["wm_hold_aggregate_ms"] == pytest.approx(stats.median(terms["aggregate"]), abs=1e-6)
    assert got["wm_hold_join_ms"] == pytest.approx(stats.median(terms["join"]), abs=1e-6)
    assert stats.median(terms["sink"]) < 50.0
