"""Connector suite: filesystem, http family, websocket, redis, preview.

All network connectors are driven against local in-test servers (the
reference similarly unit-tests kafka/mqtt against local brokers, §4.4).
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import arroyo_tpu
from arroyo_tpu import config as cfg
from arroyo_tpu.batch import TIMESTAMP_FIELD, Schema
from arroyo_tpu.engine.engine import Engine, run_graph
from arroyo_tpu.graph import EdgeType, Graph, Node, OpName
from arroyo_tpu.sql import plan_query


def _graph_src_sink(src_cfg, sink_cfg, schema):
    g = Graph()
    g.add_node(Node("src", OpName.SOURCE, src_cfg, 1))
    g.add_node(Node("sink", OpName.SINK, sink_cfg, 1))
    g.add_edge("src", "sink", EdgeType.FORWARD, schema)
    return g


SCHEMA = Schema.of([("x", "int64"), ("name", "string"), (TIMESTAMP_FIELD, "int64")])


def _write_json_input(path, n=50):
    with open(path, "w") as f:
        for i in range(n):
            f.write(json.dumps({"x": i, "name": f"n{i}", "_timestamp": 1000 + i}) + "\n")


# --------------------------------------------------------------------- files


@pytest.mark.parametrize("fmt", ["json", "parquet", "avro"])
def test_filesystem_roundtrip(fmt, tmp_path, _storage):
    arroyo_tpu._load_operators()
    src_dir = tmp_path / "in"
    os.makedirs(src_dir)
    _write_json_input(src_dir / "a.json")
    out_dir = str(tmp_path / f"out_{fmt}")
    # stage 1: json -> fmt
    g = _graph_src_sink(
        {"connector": "filesystem", "path": str(src_dir), "format": "json",
         "schema": SCHEMA},
        {"connector": "filesystem", "path": out_dir, "format": fmt, "schema": SCHEMA},
        SCHEMA,
    )
    run_graph(g, job_id=f"fs1-{fmt}", timeout=60)
    files = os.listdir(out_dir)
    assert files, "sink wrote no part files"
    # stage 2: read the fmt back
    rows = []
    g2 = _graph_src_sink(
        {"connector": "filesystem", "path": out_dir, "format": fmt, "schema": SCHEMA},
        {"connector": "vec", "rows": rows},
        SCHEMA,
    )
    run_graph(g2, job_id=f"fs2-{fmt}", timeout=60)
    assert sorted(r["x"] for r in rows) == list(range(50))
    assert sorted(r["name"] for r in rows) == sorted(f"n{i}" for i in range(50))


def test_filesystem_sink_partitioning_and_commit(tmp_path, _storage):
    """Partitioned part files only appear after the epoch's commit phase."""
    arroyo_tpu._load_operators()
    src = tmp_path / "in.json"
    _write_json_input(src, 40)
    out_dir = str(tmp_path / "parts")
    g = _graph_src_sink(
        {"connector": "filesystem", "path": str(src), "format": "json",
         "schema": SCHEMA},
        {"connector": "filesystem", "path": out_dir, "format": "json",
         "schema": SCHEMA, "partition_fields": ["x_mod"]},
        SCHEMA,
    )
    # add partition column via a VALUE node
    from arroyo_tpu.expr import BinOp, Col, Lit

    g.nodes.pop("sink")
    g.edges.clear()
    g.add_node(Node("proj", OpName.VALUE, {"projections": [
        ("x", Col("x")), ("name", Col("name")),
        ("x_mod", BinOp("%", Col("x"), Lit(2))),
    ]}, 1))
    g.add_node(Node("sink", OpName.SINK, {
        "connector": "filesystem", "path": out_dir, "format": "json",
        "schema": SCHEMA, "partition_fields": ["x_mod"]}, 1))
    g.add_edge("src", "proj", EdgeType.FORWARD, SCHEMA)
    g.add_edge("proj", "sink", EdgeType.FORWARD, SCHEMA)
    run_graph(g, job_id="fs-part", timeout=60)
    assert sorted(os.listdir(out_dir)) == ["x_mod=0", "x_mod=1"]
    n = 0
    for d in ("x_mod=0", "x_mod=1"):
        for fn in os.listdir(os.path.join(out_dir, d)):
            with open(os.path.join(out_dir, d, fn)) as f:
                n += sum(1 for _ in f)
    assert n == 40


def test_filesystem_exactly_once_across_restore(tmp_path, _storage):
    """Checkpoint mid-stream, stop, restore: no duplicate part rows."""
    arroyo_tpu._load_operators()
    src = tmp_path / "in.json"
    _write_json_input(src, 60)
    out_dir = str(tmp_path / "eo")
    cfg.update({"testing.source-read-delay-micros": 3000})

    def build():
        return Engine(_graph_src_sink(
            {"connector": "filesystem", "path": str(src), "format": "json",
             "schema": SCHEMA},
            {"connector": "filesystem", "path": out_dir, "format": "json",
             "schema": SCHEMA},
            SCHEMA,
        ), job_id="fs-eo")

    try:
        eng = build()
        eng.start()
        time.sleep(0.05)
        assert eng.checkpoint_and_wait(1, timeout=60)
        time.sleep(0.05)
        stopped = eng.checkpoint_and_wait(2, timeout=60, then_stop=True)
        eng.join(timeout=60)
    finally:
        cfg.update({"testing.source-read-delay-micros": 0})
    if stopped:
        eng2 = Engine(_graph_src_sink(
            {"connector": "filesystem", "path": str(src), "format": "json",
             "schema": SCHEMA},
            {"connector": "filesystem", "path": out_dir, "format": "json",
             "schema": SCHEMA},
            SCHEMA,
        ), job_id="fs-eo", restore_epoch=2)
        eng2.run_to_completion(timeout=60)
    rows = []
    for fn in sorted(os.listdir(out_dir)):
        with open(os.path.join(out_dir, fn)) as f:
            rows.extend(json.loads(l)["x"] for l in f if l.strip())
    assert sorted(rows) == list(range(60))


# ----------------------------------------------------------------- http/sse


def _http_server(handler_cls):
    srv = ThreadingHTTPServer(("127.0.0.1", 0), handler_cls)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv


def test_polling_http_source(_storage):
    arroyo_tpu._load_operators()
    calls = []

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            calls.append(1)
            body = "\n".join(
                json.dumps({"x": len(calls) * 10 + i, "name": "p"}) for i in range(2)
            ).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    srv = _http_server(H)
    rows = []
    g = _graph_src_sink(
        {"connector": "polling_http", "endpoint": f"http://127.0.0.1:{srv.server_port}/",
         "poll_interval_ms": 10, "schema": SCHEMA, "testing.max_polls": 3},
        {"connector": "vec", "rows": rows},
        SCHEMA,
    )
    run_graph(g, job_id="poll", timeout=60)
    srv.shutdown()
    assert len(rows) == 6
    assert {r["x"] for r in rows} == {10, 11, 20, 21, 30, 31}


def test_webhook_sink(tmp_path, _storage):
    arroyo_tpu._load_operators()
    received = []

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            n = int(self.headers["Content-Length"])
            received.append(json.loads(self.rfile.read(n)))
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

    srv = _http_server(H)
    src = tmp_path / "in.json"
    _write_json_input(src, 5)
    g = _graph_src_sink(
        {"connector": "single_file", "path": str(src), "schema": SCHEMA},
        {"connector": "webhook", "endpoint": f"http://127.0.0.1:{srv.server_port}/",
         "schema": SCHEMA},
        SCHEMA,
    )
    run_graph(g, job_id="hook", timeout=60)
    srv.shutdown()
    assert sorted(r["x"] for r in received) == list(range(5))


def test_sse_source(_storage):
    arroyo_tpu._load_operators()

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.end_headers()
            for i in range(4):
                payload = json.dumps({"x": i, "name": f"e{i}"})
                self.wfile.write(f"id: {i}\ndata: {payload}\n\n".encode())
            self.wfile.write(b"event: other\ndata: {}\n\n")  # filtered out
            # close the stream -> source finishes gracefully

    srv = _http_server(H)
    rows = []
    g = _graph_src_sink(
        {"connector": "sse", "endpoint": f"http://127.0.0.1:{srv.server_port}/",
         "events": "message", "schema": SCHEMA},
        {"connector": "vec", "rows": rows},
        SCHEMA,
    )
    run_graph(g, job_id="sse", timeout=60)
    srv.shutdown()
    assert sorted(r["x"] for r in rows) == [0, 1, 2, 3]


# ---------------------------------------------------------------- websocket


def test_websocket_source(_storage):
    arroyo_tpu._load_operators()
    from arroyo_tpu.connectors.websocket import (
        OP_CLOSE,
        OP_TEXT,
        FrameReader,
        accept_handshake,
        encode_frame,
    )

    server = socket.create_server(("127.0.0.1", 0))
    port = server.getsockname()[1]
    got_subscription = []

    def serve():
        conn, _ = server.accept()
        accept_handshake(conn)
        reader = FrameReader()
        # read the subscription message
        while not got_subscription:
            for op, payload in reader.feed(conn.recv(4096)):
                if op == OP_TEXT:
                    got_subscription.append(payload.decode())
        for i in range(3):
            msg = json.dumps({"x": i, "name": f"w{i}"}).encode()
            conn.sendall(encode_frame(OP_TEXT, msg, mask=False))
        conn.sendall(encode_frame(OP_CLOSE, b"", mask=False))
        conn.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    rows = []
    g = _graph_src_sink(
        {"connector": "websocket", "endpoint": f"ws://127.0.0.1:{port}/feed",
         "subscription_message": '{"subscribe": "all"}', "schema": SCHEMA},
        {"connector": "vec", "rows": rows},
        SCHEMA,
    )
    run_graph(g, job_id="ws", timeout=60)
    server.close()
    assert got_subscription == ['{"subscribe": "all"}']
    assert sorted(r["x"] for r in rows) == [0, 1, 2]


# -------------------------------------------------------------------- redis


class _FakeRedis:
    """RESP2 server speaking SET/RPUSH/HSET/GET for tests."""

    def __init__(self):
        self.server = socket.create_server(("127.0.0.1", 0))
        self.port = self.server.getsockname()[1]
        self.data: dict = {}
        self.lists: dict = {}
        threading.Thread(target=self._serve, daemon=True).start()

    def _serve(self):
        while True:
            try:
                conn, _ = self.server.accept()
            except OSError:
                return
            threading.Thread(target=self._client, args=(conn,), daemon=True).start()

    def _client(self, conn):
        buf = b""
        while True:
            try:
                chunk = conn.recv(65536)
            except OSError:
                return
            if not chunk:
                return
            buf += chunk
            while True:
                cmd, buf2 = self._parse(buf)
                if cmd is None:
                    break
                buf = buf2
                conn.sendall(self._exec(cmd))

    def _parse(self, buf):
        if not buf.startswith(b"*") or b"\r\n" not in buf:
            return None, buf
        head, rest = buf.split(b"\r\n", 1)
        n = int(head[1:])
        args = []
        for _ in range(n):
            if not rest.startswith(b"$") or b"\r\n" not in rest:
                return None, buf
            lhead, rest2 = rest.split(b"\r\n", 1)
            ln = int(lhead[1:])
            if len(rest2) < ln + 2:
                return None, buf
            args.append(rest2[:ln])
            rest = rest2[ln + 2 :]
        return args, rest

    def _exec(self, args):
        cmd = args[0].upper()
        if cmd == b"SET":
            self.data[args[1]] = args[2]
            return b"+OK\r\n"
        if cmd == b"RPUSH":
            self.lists.setdefault(args[1], []).append(args[2])
            return f":{len(self.lists[args[1]])}\r\n".encode()
        if cmd == b"HSET":
            self.data[(args[1], args[2])] = args[3]
            return b":1\r\n"
        if cmd == b"GET":
            v = self.data.get(args[1])
            if v is None:
                return b"$-1\r\n"
            return f"${len(v)}\r\n".encode() + v + b"\r\n"
        return b"-ERR unknown\r\n"


def test_redis_sink_and_lookup(tmp_path, _storage):
    arroyo_tpu._load_operators()
    fake = _FakeRedis()
    src = tmp_path / "in.json"
    _write_json_input(src, 4)
    g = _graph_src_sink(
        {"connector": "single_file", "path": str(src), "schema": SCHEMA},
        {"connector": "redis", "host": "127.0.0.1", "port": fake.port,
         "target": "string", "key_prefix": "row:", "key_field": "x",
         "schema": SCHEMA},
        SCHEMA,
    )
    run_graph(g, job_id="redis", timeout=60)
    assert json.loads(fake.data[b"row:2"])["name"] == "n2"
    # lookup side
    from arroyo_tpu.connectors.redis import RedisLookup

    lk = RedisLookup({"host": "127.0.0.1", "port": fake.port, "key_prefix": "row:"})
    res = lk.lookup([1, 3, 99])
    assert res[1]["name"] == "n1" and res[3]["name"] == "n3" and res[99] is None
    fake.server.close()


# ------------------------------------------------------------------ preview


def test_preview_rows_via_rest(tmp_path, _storage):
    import urllib.request

    from arroyo_tpu.api import ApiServer
    from arroyo_tpu.controller import ControllerServer, Database
    from arroyo_tpu.controller.scheduler import EmbeddedScheduler

    arroyo_tpu._load_operators()
    src = tmp_path / "in.json"
    _write_json_input(src, 8)
    sql = f"""
    CREATE TABLE t (x BIGINT, name TEXT) WITH (
      connector = 'single_file', path = '{src}', format = 'json', type = 'source');
    SELECT x * 2 AS двух FROM t WHERE x < 4;
    """
    # non-ascii alias exercises ident handling too; rename for clarity:
    sql = sql.replace("двух", "doubled")
    db = Database()
    api = ApiServer(db, port=0).start()
    ctl = ControllerServer(db, EmbeddedScheduler()).start()
    try:
        body = json.dumps({"name": "preview", "query": sql}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{api.port}/api/v1/pipelines", data=body, method="POST",
            headers={"Content-Type": "application/json"},
        )
        jid = json.loads(urllib.request.urlopen(req).read())["job_id"]
        ctl.wait_for_state(jid, "Finished", timeout=60)
        deadline = time.monotonic() + 10
        rows = []
        while time.monotonic() < deadline and len(rows) < 4:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{api.port}/api/v1/jobs/{jid}/output"
            ) as resp:
                rows = json.loads(resp.read())["data"]
            time.sleep(0.05)
        vals = sorted(json.loads(r["line"])["doubled"] for r in rows)
        assert vals == [0, 2, 4, 6]
    finally:
        ctl.stop()
        api.stop()


def test_gated_connector_raises_helpfully(_storage):
    # mqtt/nats/rabbitmq/kinesis grew real from-scratch implementations;
    # fluvio remains gated on its client package (no public wire spec)
    arroyo_tpu._load_operators()
    from arroyo_tpu.connectors import _SOURCES

    with pytest.raises(ImportError, match="fluvio"):
        _SOURCES["fluvio"]({"endpoint": "x"})


def test_connector_registry_lists_all(_storage):
    from arroyo_tpu.connectors import connectors

    c = connectors()
    for name in ("kafka", "filesystem", "sse", "websocket", "polling_http",
                 "single_file", "impulse", "nexmark", "kinesis", "mqtt", "nats",
                 "rabbitmq", "fluvio"):
        assert name in c["sources"], name
    for name in ("kafka", "filesystem", "webhook", "redis", "preview",
                 "single_file", "stdout", "blackhole"):
        assert name in c["sinks"], name


def test_filesystem_commit_on_checkpoint_stop(tmp_path, _storage):
    """then_stop must finalize the stopping epoch's part files: the commit
    phase runs before the sink task exits (regression: stop-with-checkpoint
    used to leave the output directory empty)."""
    arroyo_tpu._load_operators()
    src = tmp_path / "in.json"
    _write_json_input(src, 30)
    out_dir = str(tmp_path / "cs")
    cfg.update({"testing.source-read-delay-micros": 3000})
    try:
        eng = Engine(_graph_src_sink(
            {"connector": "filesystem", "path": str(src), "format": "json",
             "schema": SCHEMA},
            {"connector": "filesystem", "path": out_dir, "format": "json",
             "schema": SCHEMA},
            SCHEMA,
        ), job_id="fs-cs")
        eng.start()
        time.sleep(0.05)
        stopped = eng.checkpoint_and_wait(1, timeout=60, then_stop=True)
        eng.join(timeout=60)
    finally:
        cfg.update({"testing.source-read-delay-micros": 0})
    if stopped:
        rows = []
        for fn in sorted(os.listdir(out_dir)):
            with open(os.path.join(out_dir, fn)) as f:
                rows.extend(json.loads(l)["x"] for l in f if l.strip())
        assert rows, "stopping epoch was never committed"
        assert len(rows) == len(set(rows))
        # restore finishes the stream with no duplicates
        eng2 = Engine(_graph_src_sink(
            {"connector": "filesystem", "path": str(src), "format": "json",
             "schema": SCHEMA},
            {"connector": "filesystem", "path": out_dir, "format": "json",
             "schema": SCHEMA},
            SCHEMA,
        ), job_id="fs-cs", restore_epoch=1)
        eng2.run_to_completion(timeout=60)
        rows = []
        for fn in sorted(os.listdir(out_dir)):
            with open(os.path.join(out_dir, fn)) as f:
                rows.extend(json.loads(l)["x"] for l in f if l.strip())
        assert sorted(rows) == list(range(30))


def test_kafka_offset_tracker_rescale():
    from arroyo_tpu.connectors.kafka import _OffsetTracker

    t = _OffsetTracker()
    t.merge({0: 100, 2: 50})   # old subtask 0 (p=2)
    t.merge({1: 70, 3: 90})    # old subtask 1 (p=2)
    assert t.resume_position(1) == 70 and t.resume_position(3) == 90
    assert t.partitions_for(0, 1, 4) == [0, 1, 2, 3]
    t.observe(1, 75)
    assert t.resume_position(1) == 76


def test_kafka_auth_options_pass_through():
    """security./sasl./ssl. options (a Confluent Cloud profile) and
    librdkafka.-prefixed options reach the client config verbatim; format
    options do not leak in."""
    from arroyo_tpu.connectors.kafka import _auth_conf

    c = _auth_conf({
        "bootstrap_servers": "b:9092", "format": "json", "topic": "t",
        "security.protocol": "SASL_SSL", "sasl.mechanisms": "PLAIN",
        "sasl.username": "API_KEY", "sasl.password": "API_SECRET",
        "ssl.ca.location": "/etc/ssl/ca.pem",
        "librdkafka.client.id": "arroyo-tpu",
    })
    assert c == {
        "security.protocol": "SASL_SSL", "sasl.mechanisms": "PLAIN",
        "sasl.username": "API_KEY", "sasl.password": "API_SECRET",
        "ssl.ca.location": "/etc/ssl/ca.pem", "client.id": "arroyo-tpu",
    }


def _nexmark_digest(cfg: dict, n: int = 5000) -> str:
    import hashlib

    import numpy as np

    from arroyo_tpu.connectors.nexmark import NexmarkSource

    b = NexmarkSource(cfg)._generate(np.arange(n, dtype=np.uint64))
    h = hashlib.sha256()
    for name in sorted(b.columns):
        col = np.asarray(b[name])
        if col.dtype == object:
            h.update("\x00".join("" if v is None else str(v) for v in col).encode())
        else:
            h.update(np.ascontiguousarray(col).tobytes())
    return h.hexdigest()


def test_nexmark_seed_default_leaves_the_stream_as_it_was():
    """The `seed` option (chip_smoke.py's --seed) must not move the stream
    every existing golden and oracle was computed from: the digest below was
    taken from the generator before it had seeds."""
    import numpy as np

    from arroyo_tpu.batch import TIMESTAMP_FIELD
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    base = {"event_count": 5000, "inter_event_micros": 1000, "first_event_micros": 0}
    before_seeds = "a861453f7b7754498896592baaeb83ce1674b197a420d5bf38d43266d7469839"
    assert _nexmark_digest(base) == before_seeds
    assert _nexmark_digest({**base, "seed": 0}) == before_seeds
    assert _nexmark_digest({**base, "seed": 7}) != before_seeds
    assert _nexmark_digest({**base, "seed": 7}) == _nexmark_digest({**base, "seed": "7"})
    # a seed varies the random draws only: kinds and event times stay put
    n = np.arange(5000, dtype=np.uint64)
    a = NexmarkSource(base)._generate(n)
    b = NexmarkSource({**base, "seed": 7})._generate(n)
    for fixed in ("bid", "auction", "person", TIMESTAMP_FIELD, "auction.id"):
        assert np.array_equal(a[fixed], b[fixed]), fixed
    assert not np.array_equal(a["bid.price"], b["bid.price"])


def test_nexmark_numeric_options_given_as_strings_are_coerced():
    """SQL WITH values arrive quoted as often as not (event_count = '1000'):
    they used to reach the run loop's arithmetic as str and raise."""
    from arroyo_tpu.connectors.nexmark import NexmarkSource

    s = NexmarkSource({"event_count": "1000", "inter_event_micros": "100",
                       "first_event_micros": "0", "event_rate": "2500.5"})
    assert (s.event_count, s.inter_event_micros, s.first_event_micros) == (1000, 100, 0)
    assert s.event_rate == 2500.5
    assert _nexmark_digest({"event_count": "1000", "inter_event_micros": "100",
                            "first_event_micros": "0"}, 1000) == _nexmark_digest(
        {"event_count": 1000, "inter_event_micros": 100, "first_event_micros": 0}, 1000)
