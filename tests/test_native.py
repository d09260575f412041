"""C++ host runtime: differential tests against the NumPy reference paths.

Covers cpp/arroyo_host.cc via arroyo_tpu.native: hashing, repartition
permutation, JSON-lines parsing, the framed TCP data plane, and the
columnar wire codec.
"""

from __future__ import annotations

import json
import os
import threading

import numpy as np
import pytest

from arroyo_tpu import native
from arroyo_tpu.batch import TIMESTAMP_FIELD, Batch, Schema
from arroyo_tpu.hashing import hash_columns, servers_for_hashes, splitmix64
from arroyo_tpu.native.wire import (
    decode_batch,
    decode_signal,
    encode_batch,
    encode_signal,
)
from arroyo_tpu.types import CheckpointBarrier, Signal, Watermark

# Lazily skip at setup time, NOT at collection time: native.available()
# builds+loads the .so, and a native-layer fault at import poisoned the
# whole suite in round 3. A fixture keeps collection pure.
@pytest.fixture(autouse=True)
def _require_native(request):
    if request.node.get_closest_marker("no_native_required"):
        return
    if not native.available():
        pytest.skip("native library unavailable (no g++?)")

rng = np.random.default_rng(7)


def _loader_probe(cpp_dir, code: str):
    """Run ``code`` in a fresh interpreter whose native loader looks at
    ``cpp_dir`` instead of the checkout's cpp/ (the loader's state is
    process-wide, and these tests plant broken libraries)."""
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run(
        [sys.executable, "-c",
         "import arroyo_tpu.native as n\n"
         f"n._CPP_DIR = {str(cpp_dir)!r}\n" + code],
        capture_output=True, text=True, cwd=repo_root, timeout=180,
    )


def _copy_sources(tmp_path):
    import shutil

    cpp = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "cpp")
    for name in ("arroyo_host.cc", "Makefile"):
        shutil.copy(os.path.join(cpp, name), tmp_path / name)
    return tmp_path


def _bogus_so(path):
    """A library that loads but has none of the API (a stale or half-built
    .so — the failure that shipped in round 3)."""
    import subprocess

    src = path.with_suffix(".cc")
    src.write_text('extern "C" { void ah_not_the_api(void) {} }\n')
    subprocess.run(["g++", "-shared", "-fPIC", "-o", str(path), str(src)],
                   check=True)
    src.unlink()


@pytest.mark.no_native_required
def test_incompatible_so_degrades_or_raises_as_asked(tmp_path, monkeypatch):
    """A library under the RIGHT name that is missing symbols: lib() keeps
    the module contract (NumPy fallbacks, never a crash) and says so once;
    require() raises with the reason instead of degrading."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("g++ unavailable")
    import pathlib

    cpp = _copy_sources(tmp_path)
    monkeypatch.setattr(native, "_CPP_DIR", str(cpp))
    _bogus_so(pathlib.Path(native.lib_path()))
    r = _loader_probe(cpp, (
        "assert n.lib() is None and not n.available()\n"
        "import numpy as np\n"
        "from arroyo_tpu.hashing import hash_columns\n"
        "assert hash_columns([np.arange(10, dtype=np.int64)]).shape == (10,)\n"
        "try:\n"
        "    n.require()\n"
        "except n.NativeUnavailable as e:\n"
        "    assert 'does not load' in str(e), e\n"
        "    print('RAISED_OK')\n"
    ))
    assert r.returncode == 0, r.stderr
    assert "RAISED_OK" in r.stdout
    assert "native host library unavailable" in r.stderr  # said, not silent


@pytest.mark.no_native_required
def test_library_from_other_sources_is_never_loaded(tmp_path):
    """The built library is keyed on the content of its sources: one built
    from anything else — the old fixed name, another key — is not loaded
    whatever its file time says; the loader builds the keyed one, loads
    that, and clears the strays."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("g++ unavailable")
    cpp = _copy_sources(tmp_path)
    _bogus_so(cpp / "libarroyo_host.so")
    _bogus_so(cpp / "libarroyo_host-0123456789abcdef.so")
    r = _loader_probe(cpp, (
        "import os\n"
        "l = n.require()\n"
        "assert l._name == n.lib_path(), l._name\n"
        "assert sorted(f for f in os.listdir(n._CPP_DIR) if f.endswith('.so')) "
        "== [os.path.basename(n.lib_path())]\n"
        "key = n.lib_path()\n"
        "open(os.path.join(n._CPP_DIR, 'arroyo_host.cc'), 'a').write('// edit\\n')\n"
        "assert n.lib_path() != key\n"  # a source edit moves the key
        "print('KEYED_OK')\n"
    ))
    assert r.returncode == 0, r.stderr
    assert "KEYED_OK" in r.stdout


@pytest.mark.no_native_required
def test_unbuildable_library_raises_when_required(tmp_path):
    """No way to build (broken source): require() raises with make's own
    words; lib() still degrades."""
    import shutil

    if shutil.which("make") is None:
        pytest.skip("make unavailable")
    cpp = _copy_sources(tmp_path)
    (cpp / "arroyo_host.cc").write_text("this is not C++\n")
    r = _loader_probe(cpp, (
        "assert n.lib() is None\n"
        "try:\n"
        "    n.require()\n"
        "except n.NativeUnavailable as e:\n"
        "    assert 'make' in str(e) and 'failed' in str(e), e\n"
        "    print('RAISED_OK')\n"
    ))
    assert r.returncode == 0, r.stderr
    assert "RAISED_OK" in r.stdout


def test_hash_u64_matches_numpy():
    x = rng.integers(0, 1 << 63, size=10_000, dtype=np.uint64)
    assert np.array_equal(native.hash_u64(x), splitmix64(x))


def test_hash_f64_matches_numpy():
    x = rng.normal(size=5000)
    x[::100] = 0.0
    x[1::100] = -0.0
    want = splitmix64(np.where(x == 0.0, 0.0, x).astype(np.float64).view(np.uint64))
    assert np.array_equal(native.hash_f64(x), want)


def test_hash_combine_matches_numpy():
    a = rng.integers(0, 1 << 63, size=1000, dtype=np.uint64)
    b = rng.integers(0, 1 << 63, size=1000, dtype=np.uint64)
    want = splitmix64(a ^ (b + np.uint64(0x9E3779B97F4A7C15)))
    assert np.array_equal(native.hash_combine(a, b), want)


def test_hash_columns_uses_native_consistently():
    """hash_columns output must be identical with and without the native
    path (checkpoint rescale depends on hash stability)."""
    from arroyo_tpu import config as cfg

    cols = [
        rng.integers(0, 1000, size=2000).astype(np.int64),
        rng.normal(size=2000),
        np.array([f"s{i % 17}" for i in range(2000)], dtype=object),
    ]
    with_native = hash_columns(cols)
    import arroyo_tpu.native as nat

    saved = nat._lib, nat._lib_failed
    nat._lib, nat._lib_failed = None, True  # force the numpy fallback
    try:
        without = hash_columns(cols)
    finally:
        nat._lib, nat._lib_failed = saved
    assert np.array_equal(with_native, without)


def test_partition_matches_argsort():
    h = rng.integers(0, (1 << 64) - 1, size=20_000, dtype=np.uint64)
    for n in (1, 2, 3, 7, 16):
        out = native.partition(h, n)
        assert out is not None
        perm, offsets = out
        dests = servers_for_hashes(h, n)
        order = np.argsort(dests, kind="stable")
        bounds = np.searchsorted(dests[order], np.arange(n + 1))
        assert np.array_equal(perm, order), f"n={n}"
        assert np.array_equal(offsets, bounds), f"n={n}"


def test_parse_json_lines_matches_python():
    rows = []
    for i in range(500):
        rows.append({
            "a": i, "b": i * 0.5, "ok": i % 3 == 0,
            "s": f"val_{i}" if i % 10 else None,
            "extra": {"nested": [1, 2, {"x": "y"}]},
        })
    data = "\n".join(json.dumps(r) for r in rows).encode()
    fields = [("a", "int64"), ("b", "float64"), ("ok", "bool"), ("s", "string")]
    cols = native.parse_json_lines(data, fields, max_rows=1000)
    assert cols is not None
    assert list(cols["a"]) == [r["a"] for r in rows]
    assert np.allclose(cols["b"], [r["b"] for r in rows])
    assert list(cols["ok"]) == [r["ok"] for r in rows]
    # python side maps None -> empty string in native parser
    assert [s for s in cols["s"][:20]] == [
        (r["s"] if r["s"] is not None else "") for r in rows[:20]
    ]


def test_parse_json_lines_escapes_and_unicode():
    data = json.dumps({"s": 'he said "hi"\n\tümlaut ☃', "a": -42}).encode()
    cols = native.parse_json_lines(data, [("s", "string"), ("a", "int64")], 10)
    assert cols is not None
    assert cols["s"][0] == 'he said "hi"\n\tümlaut ☃'
    assert cols["a"][0] == -42


def test_parse_json_lines_malformed_returns_none():
    assert native.parse_json_lines(b"not json", [("a", "int64")], 10) is None


def test_data_plane_roundtrip():
    from arroyo_tpu.native import DataPlaneConn, DataPlaneListener, MSG_DATA, MSG_SIGNAL

    listener = DataPlaneListener()
    received = []

    def server():
        conn = listener.accept()
        while True:
            got = conn.recv()
            if got is None:
                break
            received.append(got)
        conn.close()

    t = threading.Thread(target=server, daemon=True)
    t.start()
    client = DataPlaneConn.connect("127.0.0.1", listener.port)
    batch = Batch({
        "x": np.arange(1000, dtype=np.int64),
        "name": np.array([f"n{i}" if i % 7 else None for i in range(1000)], dtype=object),
        TIMESTAMP_FIELD: np.arange(1000, dtype=np.int64) * 1000,
    })
    client.send((1, 0, 2, 3), MSG_DATA, encode_batch(batch))
    client.send((1, 0, 2, 3), MSG_SIGNAL,
                encode_signal(Signal.barrier_of(CheckpointBarrier(5, 1, 99, True))))
    client.send((1, 0, 2, 3), MSG_SIGNAL,
                encode_signal(Signal.watermark_of(Watermark.event_time(123456))))
    client.close()
    t.join(timeout=10)
    listener.close()
    assert len(received) == 3
    quad, mtype, payload = received[0]
    assert quad == (1, 0, 2, 3) and mtype == MSG_DATA
    out = decode_batch(payload)
    assert np.array_equal(out["x"], batch["x"])
    assert out["name"][0] is None and out["name"][1] == "n1"
    sig = decode_signal(received[1][2])
    assert sig.barrier.epoch == 5 and sig.barrier.then_stop
    sig2 = decode_signal(received[2][2])
    assert sig2.watermark.value == 123456


def test_wire_codec_dtypes():
    b = Batch({
        "i32": np.arange(10, dtype=np.int32),
        "u64": np.arange(10, dtype=np.uint64),
        "f32": np.linspace(0, 1, 10, dtype=np.float32),
        "bools": np.array([True, False] * 5),
        TIMESTAMP_FIELD: np.arange(10, dtype=np.int64),
    })
    out = decode_batch(encode_batch(b))
    for name in b.columns:
        assert out[name].dtype == b[name].dtype
        assert np.array_equal(out[name], b[name])


def test_two_worker_engine_over_data_plane(tmp_path, _storage):
    """Split one dataflow across two Engine instances ('workers') connected
    by the C++ data plane: worker 0 runs the source, worker 1 runs the keyed
    aggregate + sink; shuffle and barriers/watermarks cross the wire."""
    import arroyo_tpu
    from arroyo_tpu.engine.engine import Engine
    from arroyo_tpu.engine.network import NetworkManager
    from arroyo_tpu.expr import Col
    from arroyo_tpu.graph import EdgeType, Graph, Node, OpName

    arroyo_tpu._load_operators()
    S = Schema.of([("x", "int64"), (TIMESTAMP_FIELD, "int64")])
    rows: list = []

    def build_graph():
        g = Graph()
        g.add_node(Node("src", OpName.SOURCE, {
            "connector": "impulse", "message_count": 300,
            "interval_micros": 100_000, "start_time_micros": 0}, 1))
        g.add_node(Node("wm", OpName.WATERMARK, {"expr": Col(TIMESTAMP_FIELD)}, 1))
        g.add_node(Node("key", OpName.KEY, {
            "keys": [("g", __import__("arroyo_tpu.expr", fromlist=["BinOp"]).BinOp(
                "%", Col("counter"), __import__("arroyo_tpu.expr", fromlist=["Lit"]).Lit(3)))]}, 1))
        g.add_node(Node("agg", OpName.TUMBLING_AGGREGATE, {
            "width_micros": 10_000_000, "key_fields": ["g"],
            "aggregates": [("n", "count", None)],
            "backend": "numpy"}, 2))
        g.add_node(Node("sink", OpName.SINK, {"connector": "vec", "rows": rows}, 1))
        g.add_edge("src", "wm", EdgeType.FORWARD, S)
        g.add_edge("wm", "key", EdgeType.FORWARD, S)
        g.add_edge("key", "agg", EdgeType.SHUFFLE, S)
        g.add_edge("agg", "sink", EdgeType.FORWARD, S)
        return g

    assignment = {
        ("src", 0): 0, ("wm", 0): 0, ("key", 0): 0,
        ("agg", 0): 1, ("agg", 1): 1, ("sink", 0): 1,
    }
    nm0 = NetworkManager()
    nm1 = NetworkManager()
    peers = {0: ("127.0.0.1", nm0.port), 1: ("127.0.0.1", nm1.port)}
    nm0.set_peers(peers)
    nm1.set_peers(peers)
    w0 = Engine(build_graph(), job_id="dist", assignment=assignment,
                worker_index=0, network=nm0)
    w1 = Engine(build_graph(), job_id="dist", assignment=assignment,
                worker_index=1, network=nm1)
    w1.build(); w0.build()
    w1.start(); w0.start()
    w0.join(timeout=120)
    w1.join(timeout=120)
    nm0.close(); nm1.close()
    total = sum(r["n"] for r in rows)
    assert total == 300
    per_g = {}
    for r in rows:
        per_g[r["g"]] = per_g.get(r["g"], 0) + r["n"]
    assert per_g == {0: 100, 1: 100, 2: 100}


@pytest.mark.parametrize("target", ["asan-test", "tsan-test"])
def test_cpp_host_under_sanitizers(target):
    """The C++ host runtime passes its full-surface harness under ASan/
    UBSan and TSan (SURVEY §5: sanitizers stand in for the reference's
    Rust ownership guarantees; covers the threaded data plane)."""
    import subprocess

    cpp = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "cpp")
    r = subprocess.run(["make", "-C", cpp, target],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"{target} failed:\n{r.stdout}\n{r.stderr}"
    assert "host_test OK" in r.stdout
