"""Protocol front-end tests (VERDICT r4 Next #2).

The reference's whole shape is "plans arrive from an external driver
process" (Plugin.scala:44-51). These tests check that seam: the wire codec
round-trips plans exactly, and a SEPARATE server process (no shared Python
state) produces bit-identical results to in-process Session.collect.
"""

import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec.join import JoinType
from spark_rapids_tpu.exec.sort import asc, desc
from spark_rapids_tpu.expressions import col, lit
from spark_rapids_tpu.expressions.aggregates import Average, Count, Sum
from spark_rapids_tpu.plan import Session, table
from spark_rapids_tpu.plan.logical import DataFrame
from spark_rapids_tpu.server import PlanClient, PlanServer
from spark_rapids_tpu.server import plandoc
from spark_rapids_tpu.server.client import PlanServerError


def _orders_table():
    rng = np.random.default_rng(17)
    n = 500
    return pa.table({
        "o_id": np.arange(n, dtype=np.int64),
        "cust": rng.integers(0, 40, n).astype(np.int32),
        "amount": rng.uniform(1.0, 500.0, n),
        "flag": rng.integers(0, 2, n).astype(np.int32),
    })


def _cust_table():
    return pa.table({
        "c_id": np.arange(40, dtype=np.int32),
        "region": (np.arange(40, dtype=np.int32) % 5).astype(np.int32),
    })


def _query(orders_df, cust_df):
    return (orders_df
            .where((col("amount") > lit(50.0)) & (col("flag") == lit(1)))
            .join(cust_df, ["cust"], ["c_id"], JoinType.INNER)
            .group_by("region")
            .agg(Sum(col("amount")).alias("total"),
                 Average(col("amount")).alias("avg_amount"),
                 Count().alias("n"))
            .order_by(asc(col("region"))))


# ---------------------------------------------------------------------------
# codec round-trip (no sockets)
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_plandoc_roundtrip_identical_results():
    orders, cust = _orders_table(), _cust_table()
    df = _query(table(orders), table(cust))
    doc, tables = plandoc.plan_to_doc(df.plan)
    wire = json.dumps(doc)                 # must be pure JSON
    plan2 = plandoc.doc_to_plan(json.loads(wire), tables)
    ses = Session()
    expected = ses.collect(df)
    actual = Session().collect(DataFrame(plan2))
    assert actual.equals(expected)


def test_plandoc_expression_breadth():
    from spark_rapids_tpu import types as T
    t = pa.table({"s": ["ab", "xyz", None, "q"],
                  "x": pa.array([1, 2, None, 4], type=pa.int64()),
                  "d": pa.array([1.5, -3.25, 2.0, None],
                                type=pa.float64())})
    from spark_rapids_tpu.expressions.strings import Upper
    df = (table(t)
          .select(Upper(col("s")).alias("u"),
                  (col("x") * lit(3) + lit(1)).alias("y"),
                  col("d").cast(T.FLOAT32).alias("f"),
                  col("x").is_null().alias("isn")))
    doc, tables = plandoc.plan_to_doc(df.plan)
    plan2 = plandoc.doc_to_plan(json.loads(json.dumps(doc)), tables)
    assert Session().collect(DataFrame(plan2)).equals(Session().collect(df))


def test_plandoc_nonfinite_and_odd_scalars():
    import math
    for v in (math.nan, math.inf, -math.inf, b"\x00\xff", (1, "a"),
              {"k": 2}):
        enc = json.loads(json.dumps(plandoc.encode_value(v)))
        dec = plandoc.decode_value(enc)
        if isinstance(v, float) and math.isnan(v):
            assert math.isnan(dec)
        else:
            assert dec == v


def test_plandoc_sort_window_generate():
    t = pa.table({"k": pa.array([1, 1, 2, 2], type=pa.int32()),
                  "v": pa.array([3, 1, 4, 2], type=pa.int64()),
                  "arr": pa.array([[1, 2], [3], None, [4, 5]],
                                  type=pa.list_(pa.int64()))})
    df = table(t).explode(col("arr"), alias="e").order_by(
        desc(col("v")), asc(col("k")))
    doc, tables = plandoc.plan_to_doc(df.plan)
    plan2 = plandoc.doc_to_plan(json.loads(json.dumps(doc)), tables)
    assert Session().collect(DataFrame(plan2)).equals(Session().collect(df))


def test_plandoc_dedupes_shared_tables():
    orders = _orders_table()
    df = table(orders).join(table(orders), ["o_id"], ["o_id"],
                            JoinType.LEFT_SEMI)
    doc, tables = plandoc.plan_to_doc(df.plan)
    assert len(tables) == 1


# ---------------------------------------------------------------------------
# embedded server (same process, real sockets)
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_embedded_server_collect_and_capture():
    server = PlanServer().start()
    try:
        orders, cust = _orders_table(), _cust_table()
        df = _query(table(orders), table(cust))
        expected = Session().collect(df)
        with PlanClient("127.0.0.1", server.port) as client:
            got = client.collect(df)
            assert got.equals(expected)
            assert any("Agg" in n for n in client.last_execs)
            # operator metrics ride back (SQLMetrics-to-driver analogue)
            assert any("numOutputRows" in k for k in client.last_metrics)
            assert all(isinstance(v, int)
                       for v in client.last_metrics.values())
            # repeated query over the same table objects: no re-ship, and
            # the result is stable
            assert client.collect(df).equals(expected)
            text = client.explain(df)
            assert "Tpu" in text or "*" in text
    finally:
        server.stop()


def test_embedded_server_error_keeps_connection():
    server = PlanServer().start()
    try:
        t = pa.table({"x": [1, 2, 3]})
        with PlanClient("127.0.0.1", server.port) as client:
            bad = table(t).select(col("nope"))
            with pytest.raises(PlanServerError) as ei:
                client.collect(bad)
            assert "nope" in str(ei.value)
            good = table(t).select((col("x") + lit(1)).alias("y"))
            out = client.collect(good)
            assert out.column("y").to_pylist() == [2, 3, 4]
    finally:
        server.stop()


def test_embedded_server_session_conf():
    server = PlanServer().start()
    try:
        t = pa.table({"x": [1, 2, 3]})
        df = table(t).select((col("x") + lit(1)).alias("y"))
        with PlanClient("127.0.0.1", server.port,
                        conf={"spark.rapids.tpu.sql.enabled": False}) as c:
            out = c.collect(df)
            assert out.column("y").to_pylist() == [2, 3, 4]
            assert c.last_execs == []     # interpreter path: no exec plan
    finally:
        server.stop()


def test_file_source_plan_over_wire(tmp_path):
    import pyarrow.parquet as pq
    from spark_rapids_tpu.io.scan import read_parquet
    t = pa.table({"k": np.arange(100, dtype=np.int64),
                  "v": np.arange(100, dtype=np.float64)})
    pq.write_table(t.slice(0, 50), str(tmp_path / "a.parquet"))
    pq.write_table(t.slice(50, 50), str(tmp_path / "b.parquet"))
    df = read_parquet(str(tmp_path), predicate=col("k") >= lit(90))
    expected = Session().collect(df)
    server = PlanServer().start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            got = client.collect(df)
        assert got.equals(expected)
        assert expected.num_rows == 10
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# the VERDICT "done" criterion: a genuinely external server process
# ---------------------------------------------------------------------------

def test_external_process_server_bit_identical():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "spark_rapids_tpu.server", "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, text=True)
    try:
        line = proc.stdout.readline()
        m = re.search(r"listening on [\d.]+:(\d+)", line)
        assert m, f"no readiness line: {line!r}"
        port = int(m.group(1))
        orders, cust = _orders_table(), _cust_table()
        df = _query(table(orders), table(cust))
        expected = Session().collect(df)
        with PlanClient("127.0.0.1", port) as client:
            got = client.collect(df)
        assert got.equals(expected)       # bit-identical Arrow tables
    finally:
        proc.terminate()
        proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# serving-tier robustness (ISSUE 9): malformed input, deadlines, circuit
# breaker, bounded admission, stop() cancellation
# ---------------------------------------------------------------------------

import socket
import struct
import threading


def _poll(cond, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


def _tiny_df():
    t = pa.table({"x": [1, 2, 3]})
    from spark_rapids_tpu.expressions import col, lit
    return table(t).select((col("x") + lit(1)).alias("y"))


def _assert_server_alive(server):
    """The server must keep serving fresh connections and leak no
    session slots."""
    with PlanClient("127.0.0.1", server.port) as client:
        assert client.collect(_tiny_df()).column("y").to_pylist() == \
            [2, 3, 4]
    assert _poll(lambda: server.active_sessions == 0), \
        f"leaked sessions: {server.active_sessions}"


def test_malformed_truncated_preamble_keeps_server_alive():
    from spark_rapids_tpu.server import protocol
    server = PlanServer().start()
    try:
        with socket.create_connection(("127.0.0.1", server.port),
                                      timeout=5) as s:
            s.sendall(b"RT")              # truncated preamble, then EOF
        with socket.create_connection(("127.0.0.1", server.port),
                                      timeout=5) as s:
            s.sendall(b"XXXX" + struct.pack("<H", 1))   # bad magic
        _assert_server_alive(server)
    finally:
        server.stop()


def test_malformed_oversized_header_disconnects_cleanly():
    from spark_rapids_tpu.server import protocol
    server = PlanServer().start()
    try:
        with socket.create_connection(("127.0.0.1", server.port),
                                      timeout=5) as s:
            protocol.send_preamble(s)
            assert protocol.recv_preamble(s) == protocol.PROTOCOL_VERSION
            # claim a header bigger than _MAX_HEADER: the server must
            # refuse to buffer it and drop the connection
            s.sendall(struct.pack("<I", protocol._MAX_HEADER + 1))
            s.sendall(b"j" * 64)
            s.settimeout(5)
            assert s.recv(1) == b""       # clean disconnect, no reply
        _assert_server_alive(server)
    finally:
        server.stop()


def test_malformed_oversized_body_disconnects_cleanly():
    import json
    from spark_rapids_tpu.server import protocol
    server = PlanServer().start()
    try:
        with socket.create_connection(("127.0.0.1", server.port),
                                      timeout=5) as s:
            protocol.send_preamble(s)
            protocol.recv_preamble(s)
            h = json.dumps({"msg": "table", "name": "t"}).encode()
            s.sendall(struct.pack("<I", len(h)) + h
                      + struct.pack("<Q", protocol._MAX_BODY + 1))
            s.settimeout(5)
            assert s.recv(1) == b""       # refused before buffering 16G
        _assert_server_alive(server)
    finally:
        server.stop()


def test_invalid_plandoc_returns_error_and_keeps_session():
    server = PlanServer().start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            with pytest.raises(PlanServerError):
                client._request({"msg": "plan", "mode": "collect",
                                 "plan": {"node": "no-such-node"}})
            # same connection still serves queries
            out = client.collect(_tiny_df())
            assert out.column("y").to_pylist() == [2, 3, 4]
        _assert_server_alive(server)
    finally:
        server.stop()


def test_query_deadline_watchdog_returns_retryable_error():
    server = PlanServer(conf={
        "spark.rapids.tpu.server.test.collectDelayMs": 2000}).start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            t0 = time.monotonic()
            with pytest.raises(PlanServerError) as ei:
                client.collect(_tiny_df(), timeout_ms=150)
            assert time.monotonic() - t0 < 1.5     # watchdog, not delay
            assert ei.value.retryable and ei.value.timeout
            assert "deadline" in str(ei.value)
        # the cancelled worker drains (cooperative cancel at the delay
        # loop) and fresh sessions work
        assert _poll(lambda: server.active_query_count == 0)
        _assert_server_alive(server)
    finally:
        server.stop()


def test_watchdog_supervised_error_carries_worker_traceback():
    """The failure happens on the watchdog WORKER thread — the reply
    must carry that thread's traceback, not the handler's empty one
    (review finding: 'NoneType: None')."""
    server = PlanServer().start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            from spark_rapids_tpu.expressions import col
            t = pa.table({"x": [1, 2, 3]})
            bad = table(t).select(col("nope"))
            with pytest.raises(PlanServerError) as ei:
                client.collect(bad, timeout_ms=30000)   # watchdog path
            assert "nope" in str(ei.value)
            assert "Traceback" in ei.value.remote_traceback
            assert "NoneType: None" not in ei.value.remote_traceback
    finally:
        server.stop()


def test_default_query_timeout_conf():
    server = PlanServer(conf={
        "spark.rapids.tpu.server.test.collectDelayMs": 2000,
        "spark.rapids.tpu.server.queryTimeoutMs": 150}).start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            with pytest.raises(PlanServerError) as ei:
                client.collect(_tiny_df())      # no per-plan timeout
            assert ei.value.retryable and ei.value.timeout
    finally:
        server.stop()


def test_circuit_breaker_answers_unavailable():
    def sick():
        raise RuntimeError("executor poisoned by earlier fatal error")

    server = PlanServer(health_check=sick).start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            with pytest.raises(PlanServerError) as ei:
                client.collect(_tiny_df())
            assert ei.value.unavailable and ei.value.retryable
            assert ei.value.retry_after_ms == 1000    # conf default
            assert "unavailable" in str(ei.value)
            # non-plan traffic (table upload) still flows: the breaker
            # guards the device, not the control plane
            from spark_rapids_tpu.server import protocol
            client._request({"msg": "table", "name": "t"},
                            protocol.table_to_ipc(pa.table({"x": [1]})))
    finally:
        server.stop()


def test_fatal_device_error_opens_breaker_via_runtime():
    """A plan submitted AFTER an injected fatal device error gets a
    structured unavailable reply, not a dead connection (ISSUE 9
    acceptance)."""
    from spark_rapids_tpu.plugin import init

    runtime = init()
    server = PlanServer().start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            assert client.collect(_tiny_df()).num_rows == 3   # healthy
            runtime.on_task_failed(
                RuntimeError("device is in an invalid state"))
            with pytest.raises(PlanServerError) as ei:
                client.collect(_tiny_df())
            assert ei.value.unavailable
            assert ei.value.retry_after_ms is not None
            # recovery: a replaced/healthy runtime closes the breaker
            runtime.fatal_error = None
            assert client.collect(_tiny_df()).num_rows == 3
    finally:
        runtime.fatal_error = None
        server.stop()


def test_validation_error_with_fatal_marker_text_cannot_poison_runtime():
    """Fatal-marker classification is substring-based; a request whose
    ECHOED text contains a marker (e.g. an unknown mode named 'halted')
    must stay a per-request error — only execution-phase failures may
    open the breaker (review finding: one crafted message must not DoS
    every session)."""
    from spark_rapids_tpu.plugin import init

    runtime = init()
    assert runtime.fatal_error is None
    server = PlanServer().start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            doc = client._serialize(_tiny_df())
            with pytest.raises(PlanServerError, match="halted"):
                client._request({"msg": "plan", "mode": "halted",
                                 "plan": doc})
            assert runtime.fatal_error is None, \
                "validation error poisoned the executor"
            assert client.collect(_tiny_df()).num_rows == 3
    finally:
        runtime.fatal_error = None
        server.stop()


def test_binding_error_echoing_fatal_marker_cannot_poison_runtime():
    """Bind-phase failures echo client-chosen COLUMN NAMES; a column
    literally named after a fatal marker must stay a per-request error
    (review finding: binding happens inside collect, so the exec-phase
    tag needs planning to succeed first)."""
    from spark_rapids_tpu.plugin import init

    runtime = init()
    assert runtime.fatal_error is None
    server = PlanServer().start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            from spark_rapids_tpu.expressions import col
            t = pa.table({"x": [1, 2, 3]})
            bad = table(t).select(
                col("zz device is in an invalid state zz"))
            with pytest.raises(PlanServerError):
                client.collect(bad)
            assert runtime.fatal_error is None, \
                "binding error poisoned the executor"
            assert client.collect(_tiny_df()).num_rows == 3
    finally:
        runtime.fatal_error = None
        server.stop()


def test_abandoned_worker_still_counts_against_max_sessions(monkeypatch):
    """On deadline overrun the admission slot transfers to the worker:
    an abandoned, still-collecting query keeps counting against
    maxSessions until it actually ends (review finding: otherwise a
    timeout loop runs unboundedly many concurrent collects)."""
    from spark_rapids_tpu.server import server as server_mod

    release = threading.Event()
    real_dispatch = server_mod._Handler._dispatch

    def stuck_dispatch(self, header, body, tables, conf, cancelled):
        if header.get("msg") == "plan":
            release.wait(20)        # uncancellable in-flight collect
        return real_dispatch(self, header, body, tables, conf, cancelled)

    monkeypatch.setattr(server_mod._Handler, "_dispatch", stuck_dispatch)
    server = PlanServer(conf={
        "spark.rapids.tpu.server.maxSessions": 1}).start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            with pytest.raises(PlanServerError) as ei:
                client.collect(_tiny_df(), timeout_ms=150)
            assert ei.value.timeout
        # the session closed, but its abandoned worker holds the slot
        with pytest.raises(PlanServerError) as ei2:
            PlanClient("127.0.0.1", server.port)
        assert ei2.value.unavailable
        release.set()               # the collect finally ends

        def admitted():
            try:
                with PlanClient("127.0.0.1", server.port):
                    return True
            except PlanServerError:
                return False

        assert _poll(admitted, timeout_s=10), \
            "slot never released after the worker finished"
    finally:
        release.set()
        server.stop()


def test_invalid_timeout_ms_gets_structured_error():
    server = PlanServer().start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            doc, tables = plandoc.plan_to_doc(_tiny_df().plan)
            with pytest.raises(PlanServerError, match="timeout_ms"):
                client._request({"msg": "plan", "mode": "collect",
                                 "plan": doc, "timeout_ms": "soon"})
            # per-request isolation: the session survives
            assert client.collect(_tiny_df()).num_rows == 3
    finally:
        server.stop()


def test_explicit_timeout_ms_zero_means_unbounded():
    """timeout_ms=0 must override the server default (the conf documents
    0 = unbounded), not silently coalesce into it."""
    server = PlanServer(conf={
        "spark.rapids.tpu.server.test.collectDelayMs": 400,
        "spark.rapids.tpu.server.queryTimeoutMs": 150}).start()
    try:
        with PlanClient("127.0.0.1", server.port) as client:
            out = client.collect(_tiny_df(), timeout_ms=0)   # no watchdog
            assert out.column("y").to_pylist() == [2, 3, 4]
    finally:
        server.stop()


def test_silent_connection_does_not_hold_admission_slot():
    """A connect that never sends its preamble (slowloris) must not pin
    a maxSessions slot for the idle timeout (review finding)."""
    server = PlanServer(conf={
        "spark.rapids.tpu.server.maxSessions": 1}).start()
    silent = socket.create_connection(("127.0.0.1", server.port),
                                      timeout=5)
    try:
        time.sleep(0.1)               # handler is parked in recv_preamble
        _assert_server_alive(server)  # the one slot is still free
    finally:
        silent.close()
        server.stop()


def test_max_sessions_admission_bound():
    server = PlanServer(conf={
        "spark.rapids.tpu.server.maxSessions": 1}).start()
    try:
        with PlanClient("127.0.0.1", server.port) as c1:
            assert _poll(lambda: server.active_sessions == 1)
            with pytest.raises(PlanServerError) as ei:
                PlanClient("127.0.0.1", server.port)
            assert ei.value.unavailable and ei.value.retryable
            assert "maxSessions" in str(ei.value)
            assert c1.collect(_tiny_df()).num_rows == 3   # c1 unaffected
        # slot released: a new session is admitted
        assert _poll(lambda: server.active_sessions == 0)
        _assert_server_alive(server)
    finally:
        server.stop()


def test_rejected_handshake_closes_client_socket(monkeypatch):
    """The maxSessions retry dance must not leak a socket per rejected
    PlanClient construction (review finding)."""
    server = PlanServer(conf={
        "spark.rapids.tpu.server.maxSessions": 1}).start()
    created = []
    real_create = socket.create_connection

    def spy(*a, **kw):
        s = real_create(*a, **kw)
        created.append(s)
        return s

    monkeypatch.setattr(socket, "create_connection", spy)
    try:
        with PlanClient("127.0.0.1", server.port):
            with pytest.raises(PlanServerError):
                PlanClient("127.0.0.1", server.port)   # over the bound
        assert all(s.fileno() == -1 for s in created), \
            "rejected handshake leaked an open socket"
    finally:
        server.stop()


def test_stop_cancels_in_flight_query():
    """An in-flight query must not hold its thread past stop(): the
    cancel flag + connection close unblock the handler and the worker
    joins within the grace period (ISSUE 9 satellite)."""
    server = PlanServer(conf={
        "spark.rapids.tpu.server.test.collectDelayMs": 30000}).start()
    errs = []

    def submit():
        try:
            with PlanClient("127.0.0.1", server.port) as client:
                client.collect(_tiny_df(), timeout_ms=60000)
        except Exception as e:    # noqa: BLE001 — recorded for assert
            errs.append(e)

    t = threading.Thread(target=submit, daemon=True)
    t.start()
    try:
        assert _poll(lambda: server.active_query_count == 1,
                     timeout_s=10.0), "query never started"
        t0 = time.monotonic()
        server.stop(grace_s=5.0)
        assert time.monotonic() - t0 < 8.0, "stop() blocked on the query"
        assert server.active_query_count == 0, "query thread leaked"
        t.join(timeout=10)
        assert not t.is_alive()
        assert errs, "client should observe the cancelled session"
    finally:
        if t.is_alive():
            t.join(timeout=1)


def test_readiness_line_reports_bound_port():
    from spark_rapids_tpu.server.server import readiness_line
    server = PlanServer().start()
    try:
        line = readiness_line(server)
        m = re.search(r"listening on ([\d.]+):(\d+)$", line)
        assert m, line
        assert m.group(1) == "127.0.0.1"
        assert int(m.group(2)) == server.port != 0
    finally:
        server.stop()


def test_plandoc_window_expression():
    """Window specs (plain dataclasses riding the expression tree) must
    cross the wire; VERDICT's front-end must cover the full dialect."""
    from spark_rapids_tpu.exec.sort import asc
    from spark_rapids_tpu.expressions.window import (RowNumber,
                                                     WindowExpression,
                                                     WindowFrame,
                                                     WindowSpec)
    t = pa.table({"k": pa.array([1, 1, 2, 2], type=pa.int32()),
                  "v": pa.array([3.0, 1.0, 4.0, 2.0])})
    spec = WindowSpec(partition_keys=(col("k"),),
                      orders=(asc(col("v")),),
                      frame=WindowFrame(is_rows=True, start=None, end=0))
    df = table(t).window(WindowExpression(RowNumber(), spec).alias("rn"))
    doc, tables = plandoc.plan_to_doc(df.plan)
    plan2 = plandoc.doc_to_plan(json.loads(json.dumps(doc)), tables)
    assert Session().collect(DataFrame(plan2)).equals(Session().collect(df))


# ---------------------------------------------------------------------------
# serving tier (ISSUE 10): result-cache serving, invalidation acks,
# per-query admission
# ---------------------------------------------------------------------------

_SERVING_CONF = {
    "spark.rapids.tpu.server.planCache.enabled": "true",
    "spark.rapids.tpu.server.resultCache.enabled": "true",
}


@pytest.mark.serving
def test_server_result_cache_serves_repeat_bit_for_bit():
    server = PlanServer(conf=_SERVING_CONF).start()
    try:
        orders, cust = _orders_table(), _cust_table()
        df = _query(table(orders), table(cust))
        with PlanClient("127.0.0.1", server.port) as c:
            first = c.collect(df)
            assert not c.last_cached
            execs1, fell1 = c.last_execs, c.last_fell_back
            again = c.collect(df)
            assert c.last_cached
            assert c.last_cache.get("result") == "hit"
            assert again.equals(first)
            # the cached serve reports the stored run's plan capture
            assert c.last_execs == execs1
            assert c.last_fell_back == fell1
            # cache counters ride the metrics roll-up
            assert c.last_metrics.get("cache.resultCacheHitCount") == 1
        stats = server.serving_stats()
        assert stats["resultCache"]["entries"] >= 1
    finally:
        server.stop()


@pytest.mark.serving
def test_server_drop_table_invalidates_and_acks_count():
    server = PlanServer(conf=_SERVING_CONF).start()
    try:
        t = pa.table({"x": np.arange(100, dtype=np.int64)})
        with PlanClient("127.0.0.1", server.port) as c:
            ack = c.register_table("t", t)
            assert ack["rows"] == 100 and ack["digest"]
            df = table(t).select((col("x") * lit(2)).alias("y"))
            c.collect(df)
            c.collect(df)
            assert c.last_cached
            dropped = c.drop_table("t")
            assert dropped["invalidated"] == 1
            # re-registering + re-querying recomputes (miss, not stale)
            c.register_table("t", t)
            c.collect(df)
            assert not c.last_cached
    finally:
        server.stop()


@pytest.mark.serving
def test_server_table_replacement_never_serves_stale():
    """Re-uploading a name with NEW content must invalidate dependents
    (acked) and queries against the new table must see the new rows."""
    server = PlanServer(conf=_SERVING_CONF).start()
    try:
        v1 = pa.table({"x": np.arange(50, dtype=np.int64)})
        v2 = pa.table({"x": np.arange(50, 150, dtype=np.int64)})
        with PlanClient("127.0.0.1", server.port) as c:
            ack1 = c.register_table("t", v1)
            r1 = c.collect(table(v1).agg(Sum(col("x")).alias("s")))
            assert r1.column("s").to_pylist() == [sum(range(50))]
            ack2 = c.register_table("t", v2)      # REPLACE with new bytes
            assert ack2["invalidated"] == 1
            assert ack2["digest"] != ack1["digest"]
            r2 = c.collect(table(v2).agg(Sum(col("x")).alias("s")))
            assert r2.column("s").to_pylist() == [sum(range(50, 150))]
            # same-content re-upload invalidates nothing
            ack3 = c.register_table("t", v2)
            assert ack3["invalidated"] == 0
    finally:
        server.stop()


@pytest.mark.serving
def test_server_cache_off_reports_off():
    server = PlanServer(conf={
        "spark.rapids.tpu.server.planCache.enabled": "false"}).start()
    try:
        t = pa.table({"x": [1, 2, 3]})
        with PlanClient("127.0.0.1", server.port) as c:
            c.collect(table(t).select((col("x") + lit(1)).alias("y")))
            assert not c.last_cached
            assert c.last_cache.get("result") == "off"
            assert "plan" not in c.last_cache    # fingerprinting skipped
    finally:
        server.stop()


@pytest.mark.serving
def test_server_admission_watchdog_cancels_queued_query():
    """A query that cannot admit before its deadline gets the structured
    retryable timeout, and the abandoned worker releases its slot."""
    server = PlanServer(conf={
        "spark.rapids.tpu.server.concurrentCollects": "1",
        "spark.rapids.tpu.server.test.collectDelayMs": "700",
    }).start()
    try:
        t = pa.table({"x": np.arange(10, dtype=np.int64)})
        df = table(t).select((col("x") + lit(1)).alias("y"))
        import threading as _th
        done = []

        def slow():
            with PlanClient("127.0.0.1", server.port) as c1:
                done.append(c1.collect(df))

        holder = _th.Thread(target=slow)
        holder.start()
        time.sleep(0.15)        # the slot is now held by the delay query
        with PlanClient("127.0.0.1", server.port) as c2:
            with pytest.raises(PlanServerError) as ei:
                c2.collect(df, timeout_ms=300)
            assert ei.value.timeout and ei.value.retryable
        holder.join(timeout=10)
        assert len(done) == 1
        deadline = time.monotonic() + 5
        while server.active_query_count and time.monotonic() < deadline:
            time.sleep(0.02)
        assert server.active_query_count == 0
        # the freed slot admits new queries normally
        with PlanClient("127.0.0.1", server.port) as c3:
            assert c3.collect(df).num_rows == 10
    finally:
        server.stop()


@pytest.mark.serving
def test_register_table_name_never_collides_with_auto_names():
    """A client-chosen registry name (register_table) must never capture
    a plan's auto-named scan: the query below would silently bind to the
    registered table if plan_to_doc reused its name."""
    server = PlanServer(conf=_SERVING_CONF).start()
    try:
        registered = pa.table({"x": np.arange(1000, dtype=np.int64)})
        local = pa.table({"x": np.arange(5, dtype=np.int64)})
        with PlanClient("127.0.0.1", server.port) as c:
            # occupy the exact name plan_to_doc would generate next
            c.register_table("t1", registered)
            out = c.collect(table(local).agg(Sum(col("x")).alias("s")))
            assert out.column("s").to_pylist() == [10], \
                "query bound to the registered table, not its own scan"
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# fleet seams on the single server (ISSUE 12): the stats wire op + stable
# schema, the shutdown wire op, and the PlanClient unavailable-retry budget
# ---------------------------------------------------------------------------


@pytest.mark.serving
def test_stats_wire_op_and_stable_schema():
    """serving_stats() is a wire op now, with a schema the router (and
    any ops tooling) can rely on: versioned, with the server block the
    readiness line formats from."""
    server = PlanServer(conf=_SERVING_CONF).start()
    try:
        t = pa.table({"x": np.arange(20, dtype=np.int64)})
        df = table(t).select((col("x") * lit(3)).alias("y"))
        with PlanClient("127.0.0.1", server.port) as c:
            c.collect(df)
            c.collect(df)
            st = c.stats()
        # v2: the trace block (flight-recorder occupancy, slow-query
        # count, dropped spans, cost-store size) joined the schema;
        # v3: the adaptive block (cost-fed plans + runtime re-plan
        # counters) joined it; v4: the sharing block (in-flight dedup,
        # subplan cache, scan-share registry, affinity batching); v5: the
        # programs block (the process's program table)
        assert st["schemaVersion"] == 5
        assert set(st["programs"]) == {"entries", "hits", "misses",
                                       "unkeyed", "evictions"}
        assert set(st["adaptive"]) == {
            "costFedPlanCount", "explorationRunCount", "replanCount",
            "coalescedPartitionCount", "skewSplitCount",
            "broadcastSwitchCount"}
        sh = st["sharing"]
        for k in ("inflightLeaderCount", "inflightServedCount",
                  "subplanHitCount", "scanShareHitCount",
                  "admissionAffinityBatchedCount"):
            assert k in sh, k
        assert set(sh["inflight"]) == {"inFlight", "pendingDone"}
        assert set(sh["subplanCache"]) == {"entries", "usedBytes",
                                           "maxBytes"}
        assert set(sh["scanShare"]) == {"entries", "usedBytes",
                                        "maxBytes", "pinnedRefs"}
        tr = st["trace"]
        assert set(tr) == {"recorder", "costFingerprints"}
        assert set(tr["recorder"]) == {
            "entries", "capacity", "recorded", "slowQueries",
            "slowQueryMs", "droppedSpans"}
        info = st["server"]
        assert info["host"] == "127.0.0.1"
        assert info["port"] == server.port
        assert info["maxSessions"] >= 1 and not info["shuttingDown"]
        # the device the server computes on, as JAX reports it — what a
        # client (chip_smoke.py) tells a chip run from a CPU run by
        import jax
        dev = info["device"]
        assert (dev["platform"], dev["kind"], dev["count"]) == (
            jax.devices()[0].platform, jax.devices()[0].device_kind,
            len(jax.devices()))
        assert st["counters"]["resultCacheHitCount"] >= 1
        assert set(st["admission"]) == {"concurrentCollects", "admitted",
                                        "inFlight", "waitTimeNs"}
        # every counter the fleet aggregates exists, including the
        # persistent-tier ones
        for k in ("resultStoreHitCount", "resultStoreWriteCount",
                  "resultStoreInvalidationCount",
                  "resultStoreEvictionCount"):
            assert k in st["counters"], k
        # readiness_line is a projection OF the stats schema
        from spark_rapids_tpu.server.server import readiness_line
        line = readiness_line(server)
        assert f"{info['host']}:{info['port']}" in line
    finally:
        server.stop()


@pytest.mark.serving
def test_shutdown_wire_op_stops_server():
    """The rolling restart's drain seam: a ``shutdown`` op acks, then
    the server stops via the PR-9 stop() contract (in-flight cancel +
    bounded join) without the caller holding a process handle."""
    server = PlanServer().start()
    port = server.port
    import socket as _socket
    from spark_rapids_tpu.server import protocol as _proto
    with _socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        _proto.send_preamble(s)
        _proto.recv_preamble(s)
        _proto.send_msg(s, {"msg": "hello", "conf": {}})
        _proto.recv_msg(s)
        _proto.send_msg(s, {"msg": "shutdown", "grace_s": 5})
        reply, _ = _proto.recv_msg(s)
        assert reply["msg"] == "shutdown_ack"
    assert _poll(lambda: server._server.shutting_down.is_set(),
                 timeout_s=10)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            _socket.create_connection(("127.0.0.1", port),
                                      timeout=0.2).close()
            time.sleep(0.05)
        except OSError:
            break
    else:
        raise AssertionError("server still accepting after shutdown op")


@pytest.mark.serving
def test_client_retry_honors_retry_after_with_jitter_and_budget():
    """The PlanClient retry loop: sleeps ride the server's
    retry_after_ms hint (jittered within [1x, 2x]), attempts are
    bounded, and a budget too small to honor the hint raises instead of
    sleeping past it."""
    server = PlanServer(
        health_check=lambda: (_ for _ in ()).throw(
            RuntimeError("poisoned")),
        conf={"spark.rapids.tpu.server.retryAfterMs": "40"}).start()
    try:
        t = pa.table({"x": np.arange(5, dtype=np.int64)})
        df = table(t).select((col("x") + lit(1)).alias("y"))
        sleeps = []
        with PlanClient("127.0.0.1", server.port,
                        unavailable_retries=3,
                        _sleep=sleeps.append) as c:
            with pytest.raises(PlanServerError) as ei:
                c.collect(df)
            assert ei.value.unavailable and ei.value.retry_after_ms == 40
        assert len(sleeps) == 3                  # bounded attempts
        assert c.retried_unavailable == 3
        for s in sleeps:
            assert 0.04 <= s <= 0.08 + 1e-9      # hint x [1, 2) jitter
        # a budget smaller than one hint raises WITHOUT sleeping
        sleeps2 = []
        with PlanClient("127.0.0.1", server.port,
                        unavailable_retries=3, retry_budget_ms=10,
                        _sleep=sleeps2.append) as c2:
            with pytest.raises(PlanServerError):
                c2.collect(df)
        assert sleeps2 == []
    finally:
        server.stop()


@pytest.mark.serving
def test_client_retry_succeeds_after_breaker_closes():
    """Transient unavailability is absorbed: the breaker opens for the
    first attempts and closes before the budget runs out; the collect
    completes without the caller hand-rolling a loop."""
    calls = []

    def flaky_health():
        calls.append(1)
        if len(calls) <= 2:
            raise RuntimeError("transient device sickness")

    server = PlanServer(
        health_check=flaky_health,
        conf={"spark.rapids.tpu.server.retryAfterMs": "20"}).start()
    try:
        t = pa.table({"x": np.arange(7, dtype=np.int64)})
        df = table(t).select((col("x") * lit(2)).alias("y"))
        with PlanClient("127.0.0.1", server.port,
                        unavailable_retries=5) as c:
            out = c.collect(df)
            assert out.column("y").to_pylist() == \
                [x * 2 for x in range(7)]
            assert c.retried_unavailable == 2
    finally:
        server.stop()


@pytest.mark.serving
def test_client_heals_after_abrupt_connection_drop():
    """An abrupt transport drop (server restart, no fatal reply)
    surfaces ONE error and closes the client's socket; the next call
    reconnects, re-ships the session's tables, and succeeds — the
    client must never be permanently wedged on a dead fd."""
    server = PlanServer(conf=_SERVING_CONF).start()
    try:
        t = pa.table({"x": np.arange(30, dtype=np.int64)})
        with PlanClient("127.0.0.1", server.port) as c:
            c.register_table("t", t)
            df = table(t).agg(Sum(col("x")).alias("s"))
            first = c.collect(df)
            c._sock.close()                  # simulate the abrupt drop
            with pytest.raises(OSError):
                c.collect(df)
            assert c._sock is None           # _request cleaned it up
            healed = c.collect(df)           # reconnect + table replay
            assert healed.equals(first)
    finally:
        server.stop()
