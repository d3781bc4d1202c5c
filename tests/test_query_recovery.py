"""Query recovery (ISSUE 11 acceptance): shuffle lineage, deterministic
lost-partition recompute, map-output replication, suspect/registry
rehabilitation, and the chaos soak wrappers.

The contract: killing the only peer serving a shuffle's map outputs
mid-query must NOT abort the query —

- at ``replicas=0`` the reduce side recomputes exactly the lost map
  partitions from lineage (nonzero ``recomputeCount``), bit-for-bit;
- at ``replicas=1`` the blocks are served from the replica peer (zero
  recompute, nonzero ``replicaBytes``), bit-for-bit;
- either way: zero leaked sockets, catalog pins, or threads.

Plus the satellites: a suspect peer is rehabilitated by one successful
fetch (not a TTL); a dead executor needs a fresh ``register`` handshake
(a stray heartbeat cannot resurrect it); plan-server ``stop()`` landing
during an active recompute is observed by the recompute loop and leaks
nothing; and the unified robustness lint (tools/lint_robustness.py)
keeps the tree clean.
"""

import importlib
import json
import os
import socket
import sys
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from harness.threads import warm_reader_pool
from spark_rapids_tpu.memory.catalog import device_budget
from spark_rapids_tpu.memory.retry import oom_injection
from spark_rapids_tpu.shuffle.lineage import (LineageMissError,
                                              LineageRegistry,
                                              LineageVerificationError,
                                              metrics as lineage_metrics)
from spark_rapids_tpu.shuffle.transport import (BlockMissingError,
                                                TcpTransport)

pytestmark = pytest.mark.net_inject


def _load_tool(name):
    tools = os.path.join(os.path.dirname(__file__), os.pardir, "tools")
    sys.path.insert(0, tools)
    try:
        mod = importlib.import_module(name)
        return mod
    finally:
        sys.path.pop(0)


@pytest.fixture(scope="module")
def soak():
    """tools/chaos_soak.py — the harness IS the differential runner."""
    return _load_tool("chaos_soak")


@pytest.fixture(scope="module")
def shapes(soak):
    return soak.make_tables(3000)


@pytest.fixture(scope="module")
def baselines(soak, shapes):
    """Clean per-shape runs (no kill, no injection), computed once."""
    return {name: soak.run_query(t) for name, t in shapes.items()}


def _threads_settle(baseline, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if threading.active_count() <= baseline:
            return
        time.sleep(0.02)
    assert threading.active_count() <= baseline, \
        f"leaked threads: {sorted(t.name for t in threading.enumerate())}"


# ---------------------------------------------------------------------------
# the acceptance matrix: kill-one-peer-mid-query on all five bench
# shapes, replicas=0 (pure lineage recompute) and replicas=1 (replica
# serve), bit-for-bit with zero leaks
# ---------------------------------------------------------------------------

SHAPE_NAMES = ("q1_stage", "hash_agg", "join_sort", "parquet_scan",
               "exchange")


@pytest.mark.parametrize("shape", SHAPE_NAMES)
def test_kill_peer_mid_query_recomputes_bit_for_bit(shape, soak, shapes,
                                                    baselines):
    """replicas=0: the dead primary's blocks exist NOWHERE else — every
    one the reduce side still needs is recomputed from lineage."""
    cat = device_budget()
    warm_reader_pool()
    baseline_threads = threading.active_count()
    m0 = lineage_metrics().snapshot()
    parts = soak.run_query(shapes[shape], replicas=0, kill="mid_read")
    m1 = lineage_metrics().snapshot()
    assert soak.same(parts, baselines[shape]), \
        f"{shape}: recovered result differs from the clean run"
    assert m1["recomputeCount"] > m0["recomputeCount"], \
        f"{shape}: peer death at replicas=0 must recompute"
    assert m1["replicaBytes"] == m0["replicaBytes"]
    assert cat.total_pinned() == 0, cat.dump_state()
    _threads_settle(baseline_threads)


@pytest.mark.parametrize("shape", SHAPE_NAMES)
def test_kill_peer_mid_query_replica_serves(shape, soak, shapes,
                                            baselines):
    """replicas=1: every block was replicated at publish — the replica
    serves them all and recompute never fires."""
    cat = device_budget()
    warm_reader_pool()
    baseline_threads = threading.active_count()
    m0 = lineage_metrics().snapshot()
    parts = soak.run_query(shapes[shape], replicas=1, kill="mid_read")
    m1 = lineage_metrics().snapshot()
    assert soak.same(parts, baselines[shape]), \
        f"{shape}: replica-served result differs from the clean run"
    assert m1["recomputeCount"] == m0["recomputeCount"], \
        f"{shape}: replica serve must not recompute"
    assert m1["replicaBytes"] > m0["replicaBytes"], \
        f"{shape}: replication never happened"
    assert cat.total_pinned() == 0, cat.dump_state()
    _threads_settle(baseline_threads)


def test_kill_peer_before_any_read_recovers(soak, shapes, baselines):
    """The primary dies before the FIRST reduce fetch: even the block
    listing comes from lineage (the transport listing raises)."""
    m0 = lineage_metrics().snapshot()
    parts = soak.run_query(shapes["exchange"], replicas=0,
                           kill="before_read")
    assert soak.same(parts, baselines["exchange"])
    assert lineage_metrics().snapshot()["recomputeCount"] > \
        m0["recomputeCount"]


def test_nested_recovery_of_chained_shuffles_does_not_deadlock():
    """Shuffle B's recompute re-executes a child containing shuffle A;
    when BOTH primaries are dead, A's recovery runs NESTED inside B's —
    it must skip the recover lock B's recovery holds (and fetch serially
    off the shared pool) instead of deadlocking, and stay bit-for-bit."""
    from spark_rapids_tpu.exec import InMemoryScanExec
    from spark_rapids_tpu.expressions import col
    from spark_rapids_tpu.shuffle import HashPartitioning
    from spark_rapids_tpu.shuffle.multithreaded import \
        MultithreadedShuffleExchangeExec
    from spark_rapids_tpu.batch import to_arrow
    rng = np.random.default_rng(21)
    t = pa.table({"k": rng.integers(0, 16, 1500).astype(np.int64),
                  "v": rng.integers(-50, 50, 1500).astype(np.int64)})

    def run(kill):
        reg = LineageRegistry()          # ONE registry for both shuffles
        prim_a, prim_b = TcpTransport(), TcpTransport()
        cli_a = TcpTransport(peers={1: prim_a.address}, retries=2,
                             connect_timeout_s=2.0, io_timeout_s=2.0,
                             backoff_base_ms=1.0)
        cli_b = TcpTransport(peers={1: prim_b.address}, retries=2,
                             connect_timeout_s=2.0, io_timeout_s=2.0,
                             backoff_base_ms=1.0)
        ex_a = MultithreadedShuffleExchangeExec(
            HashPartitioning([col("k")], 3),
            InMemoryScanExec(t, batch_rows=400),
            transport=prim_a, read_transport=cli_a, lineage_registry=reg)
        ex_b = MultithreadedShuffleExchangeExec(
            HashPartitioning([col("v")], 3), ex_a,
            transport=prim_b, read_transport=cli_b, lineage_registry=reg)
        try:
            ex_b._write_all()            # clean write: A read over wire
            if kill:
                prim_a.close()           # BOTH primaries die before the
                prim_b.close()           # first reduce read of B
            return [[to_arrow(b, ex_b.output_schema)
                     for b in ex_b.execute_partition(p)]
                    for p in range(3)]
        finally:
            ex_a.cleanup()
            ex_b.cleanup()
            cli_a.close()
            cli_b.close()
            prim_a.close()
            prim_b.close()

    clean = run(False)
    box = {}

    def faulted():
        box["parts"] = run(True)

    m0 = lineage_metrics().snapshot()
    th = threading.Thread(target=faulted, daemon=True)
    th.start()
    th.join(timeout=120.0)
    assert not th.is_alive(), \
        "nested recovery deadlocked on the recover lock"
    m1 = lineage_metrics().snapshot()
    assert m1["recomputeCount"] > m0["recomputeCount"]
    for a, b in zip(clean, box["parts"]):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert x.equals(y)           # bit-for-bit through BOTH hops


def test_recompute_survives_oom_injection(soak, shapes, baselines):
    """The recompute re-run rides the PR-7 with_retry state machine:
    injected OOM during recovery spills/retries and stays bit-for-bit."""
    from spark_rapids_tpu.memory.retry import metrics as retry_metrics
    m0 = lineage_metrics().snapshot()
    r0 = retry_metrics().snapshot()
    with oom_injection("every-3", seed=7):
        parts = soak.run_query(shapes["hash_agg"], replicas=0,
                               kill="mid_read")
    assert soak.same(parts, baselines["hash_agg"])
    assert lineage_metrics().snapshot()["recomputeCount"] > \
        m0["recomputeCount"]
    assert retry_metrics().snapshot()["retryCount"] > r0["retryCount"], \
        "OOM injection never exercised the retry machine"
    assert device_budget().total_pinned() == 0


# ---------------------------------------------------------------------------
# lineage registry unit contracts
# ---------------------------------------------------------------------------

def test_lineage_miss_is_typed_and_counted():
    reg = LineageRegistry()
    m0 = lineage_metrics().snapshot()
    cause = BlockMissingError("nobody holds it")
    with pytest.raises(LineageMissError) as ei:
        reg.recover(1, 0, 0, cause=cause)
    assert ei.value.__cause__ is cause
    assert lineage_metrics().snapshot()["lineageMissCount"] == \
        m0["lineageMissCount"] + 1


def test_lineage_verification_rejects_nondeterministic_fragment():
    """A fragment whose re-run produces DIFFERENT bytes than it
    published must fail loudly — never resume with different rows —
    and the report names the fragment's input digest."""
    reg = LineageRegistry()
    reg.register_fragment(
        2, 0, lambda rs: {r: b"different-bytes" for r in rs}, "frag-sig")
    reg.note_block(2, 0, 0, b"published-bytes")
    with pytest.raises(LineageVerificationError,
                       match="deterministic") as ei:
        reg.recover(2, 0, 0)
    assert "frag-sig" in str(ei.value)


def test_one_fragment_rerun_recovers_all_sibling_blocks():
    """A dead peer usually loses a whole map output: recovering ONE of
    its blocks re-runs the fragment ONCE, and the verified siblings are
    served from the stash without re-executing the child."""
    reg = LineageRegistry()
    runs = []

    def recompute(rs):
        runs.append(tuple(rs))
        return {r: b"block-%d" % r for r in rs}

    reg.register_fragment(4, 0, recompute, "d")
    for r in (0, 1, 2):
        reg.note_block(4, 0, r, b"block-%d" % r)
    m0 = lineage_metrics().snapshot()
    assert reg.recover(4, 0, 1) == b"block-1"
    assert reg.recover(4, 0, 0) == b"block-0"
    assert reg.recover(4, 0, 2) == b"block-2"
    assert runs == [(0, 1, 2)], "fragment re-ran more than once"
    m1 = lineage_metrics().snapshot()
    assert m1["recomputeCount"] - m0["recomputeCount"] == 3
    assert m1["recomputedPartitions"] - m0["recomputedPartitions"] == 3


def test_empty_shuffle_reads_empty_past_dead_listing():
    """A shuffle whose child yielded ZERO batches is still lineage-known:
    with the only serving peer dead, every reducer reads as provably
    empty instead of failing the listing."""
    from spark_rapids_tpu.exec import InMemoryScanExec
    from spark_rapids_tpu.expressions import col
    from spark_rapids_tpu.shuffle import HashPartitioning
    from spark_rapids_tpu.shuffle.multithreaded import \
        MultithreadedShuffleExchangeExec
    empty = pa.table({"k": pa.array([], pa.int64())})
    primary = TcpTransport()
    client = TcpTransport(peers={1: primary.address}, retries=2,
                          connect_timeout_s=2.0, io_timeout_s=2.0,
                          backoff_base_ms=1.0)
    ex = MultithreadedShuffleExchangeExec(
        HashPartitioning([col("k")], 3), InMemoryScanExec(empty),
        transport=primary, read_transport=client,
        lineage_registry=LineageRegistry())
    try:
        ex._write_all()
        primary.close()
        assert all(list(ex.execute_partition(p)) == [] for p in range(3))
    finally:
        ex.cleanup()
        client.close()
        primary.close()


def test_lineage_listing_and_cleanup():
    reg = LineageRegistry()
    reg.register_fragment(3, 0, lambda r: b"x", "d")
    reg.register_fragment(3, 1, lambda r: b"x", "d")
    reg.note_block(3, 0, 0, b"x")
    reg.note_block(3, 1, 0, b"x")
    reg.note_block(3, 1, 2, b"x")
    assert reg.blocks(3, 0) == [(3, 0, 0), (3, 1, 0)]
    assert reg.blocks(3, 2) == [(3, 1, 2)]
    assert reg.blocks(3, 1) == []          # empty reducer, still known
    assert reg.knows_shuffle(3)
    reg.remove_shuffle(3)
    assert not reg.knows_shuffle(3)
    assert reg.blocks(3, 0) == []


def test_transport_put_replicates_blocks():
    """The _PUT wire op lands a published block on a peer, and the peer
    serves it back; replicaBytes counts the replicated payload."""
    peer = TcpTransport()
    src = TcpTransport(peers={2: peer.address}, retries=2,
                       connect_timeout_s=2.0, io_timeout_s=2.0,
                       backoff_base_ms=1.0)
    try:
        payload = b"replica-me" * 100
        m0 = lineage_metrics().snapshot()
        assert src.replicate(5, 1, 2, payload, 1) == 1
        assert peer.fetch(5, 1, 2) == payload
        assert lineage_metrics().snapshot()["replicaBytes"] == \
            m0["replicaBytes"] + len(payload)
        # asking for more replicas than peers writes what it can
        assert src.replicate(5, 1, 3, payload, 3) == 1
        # end-of-query cleanup reaches the replica holders too: the
        # copies must not outlive the shuffle in peer processes
        src.remove_shuffle(5)
        assert peer.local_blocks(5, 2) == []
        assert peer.local_blocks(5, 3) == []
        with pytest.raises(BlockMissingError):
            peer.fetch(5, 1, 2)
    finally:
        src.close()
        peer.close()


# ---------------------------------------------------------------------------
# suspect rehabilitation (satellite): one successful fetch clears the
# suspect flag — not a suspect_ttl_s wait
# ---------------------------------------------------------------------------

def test_successful_fetch_rehabilitates_suspect_immediately():
    live = TcpTransport()
    live.publish(11, 0, 0, b"block")
    other = TcpTransport()
    client = TcpTransport(peers={1: live.address, 2: other.address},
                          retries=2, connect_timeout_s=2.0,
                          io_timeout_s=2.0, backoff_base_ms=1.0,
                          suspect_ttl_s=3600.0)   # TTL can NOT be the fix
    try:
        # a transient blip marked the live peer suspect: ordered last
        client._suspects[live.address] = time.time()
        assert client._ordered_peers()[-1][0] == 1
        assert client.fetch(11, 0, 0) == b"block"
        # the fetch succeeded against the suspect — rehabilitated NOW,
        # long before the 1-hour TTL would have aged it out
        assert live.address not in client._suspects
        assert [pid for pid, _ in client._ordered_peers()] == [1, 2]
    finally:
        client.close()
        live.close()
        other.close()


def test_missing_answer_also_rehabilitates_suspect():
    """A MISSING reply is a completed round trip — the peer is alive.
    Nobody holds the block, so the fetch walks EVERY peer (suspects
    last) and each answered transaction clears its suspect flag."""
    live = TcpTransport()           # holds nothing
    other = TcpTransport()          # holds nothing either
    client = TcpTransport(peers={1: live.address, 2: other.address},
                          retries=2, connect_timeout_s=2.0,
                          io_timeout_s=2.0, backoff_base_ms=1.0,
                          suspect_ttl_s=3600.0)
    try:
        client._suspects[live.address] = time.time()
        with pytest.raises(BlockMissingError):
            client.fetch(12, 0, 0)
        assert live.address not in client._suspects
    finally:
        client.close()
        live.close()
        other.close()


# ---------------------------------------------------------------------------
# registry resurrection (satellite): dead needs a fresh register — a
# stray heartbeat must not resurrect it
# ---------------------------------------------------------------------------

def _registry_rpc(addr, msg: dict) -> dict:
    with socket.create_connection(addr, timeout=10) as s:
        s.sendall((json.dumps(msg) + "\n").encode())
        line = s.makefile().readline()
    return json.loads(line) if line else {}


def test_peer_registry_heartbeat_cannot_resurrect_dead():
    from spark_rapids_tpu.shuffle.discovery import PeerRegistry
    reg = PeerRegistry(timeout_s=60.0)
    try:
        _registry_rpc(reg.address, {"op": "register", "id": 7,
                                    "host": "h", "port": 1234})
        assert "7" in reg.live_table()
        # a transport reported executor 7's block server dead
        _registry_rpc(reg.address, {"op": "unreachable", "id": 7})
        assert "7" not in reg.live_table()
        # the zombie's heartbeat loop keeps pinging: REFUSED, not stamped
        resp = _registry_rpc(reg.address, {"op": "heartbeat", "id": 7})
        assert resp == {"ok": False, "dead": True}
        assert "7" not in reg.live_table()
        # rehabilitation is the explicit re-register handshake
        _registry_rpc(reg.address, {"op": "register", "id": 7,
                                    "host": "h", "port": 1234})
        assert "7" in reg.live_table()
        resp = _registry_rpc(reg.address, {"op": "heartbeat", "id": 7})
        assert resp == {"ok": True}
    finally:
        reg.close()


def test_registry_client_reregisters_after_dead_promotion():
    """The executor-side beat loop sees the 'dead' refusal and performs
    the fresh register handshake itself — rehabilitation for a peer
    that was only transiently unreachable."""
    from spark_rapids_tpu.shuffle.discovery import (PeerRegistry,
                                                    RegistryClient)
    reg = PeerRegistry(timeout_s=60.0)
    client = None
    try:
        client = RegistryClient(reg.address, 9, ("h", 42),
                                heartbeat_interval_s=0.05)
        assert "9" in reg.live_table()
        reg.mark_unreachable(9)
        assert "9" not in reg.live_table()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and "9" not in reg.live_table():
            time.sleep(0.02)
        assert "9" in reg.live_table(), \
            "beat loop never re-registered after the dead refusal"
    finally:
        if client is not None:
            client.close()
        reg.close()


def test_registry_client_reregisters_after_table_loss():
    """A registry that lost its table (restart) answers an address-less
    heartbeat with `unknown` instead of a hollow ok — and the beat loop
    re-registers with its address, so the executor returns to listings
    instead of heartbeating into the void forever."""
    from spark_rapids_tpu.shuffle.discovery import (PeerRegistry,
                                                    RegistryClient)
    reg = PeerRegistry(timeout_s=60.0)
    client = None
    try:
        client = RegistryClient(reg.address, 13, ("h", 99),
                                heartbeat_interval_s=0.05)
        assert "13" in reg.live_table()
        with reg._lock:                 # simulate a restart: table gone
            reg._table.clear()
        assert "13" not in reg.live_table()
        resp = _registry_rpc(reg.address, {"op": "heartbeat", "id": 77})
        assert resp == {"ok": False, "unknown": True}
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                "13" not in reg.live_table():
            time.sleep(0.02)
        assert "13" in reg.live_table(), \
            "beat loop never re-registered after the table loss"
    finally:
        if client is not None:
            client.close()
        reg.close()


def test_runtime_heartbeat_cannot_resurrect_dead_executor():
    """The in-process twin (ExecutorRuntime): mark_unreachable is a
    PROMOTION; a stray heartbeat is REFUSED (returns False); only
    register() brings the executor back."""
    from spark_rapids_tpu.plugin import init
    runtime = init()
    assert runtime.heartbeat("exec-zombie")
    assert "exec-zombie" in runtime.live_executors(timeout_s=60.0)
    runtime.mark_unreachable("exec-zombie")
    assert "exec-zombie" not in runtime.live_executors(timeout_s=60.0)
    assert not runtime.heartbeat("exec-zombie")   # stray late heartbeat
    assert "exec-zombie" not in runtime.live_executors(timeout_s=60.0)
    runtime.register("exec-zombie")           # the explicit handshake
    assert "exec-zombie" in runtime.live_executors(timeout_s=60.0)
    runtime.mark_unreachable("exec-zombie")   # leave no state behind


def test_runtime_sender_loop_rehabilitates_after_dead_promotion():
    """An executor whose OWN heartbeat sender is demonstrably alive was
    only transiently unreachable: the sender sees its beat refused and
    performs the register() handshake itself — the in-process twin of
    RegistryClient._beat's rehabilitation (a dead executor has no
    sender, so stray beats from elsewhere still cannot resurrect)."""
    from spark_rapids_tpu.plugin import init
    runtime = init()
    stop = runtime.start_heartbeat("exec-flappy", interval_s=0.05)
    try:
        assert "exec-flappy" in runtime.live_executors(timeout_s=60.0)
        runtime.mark_unreachable("exec-flappy")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                "exec-flappy" not in runtime.live_executors(timeout_s=60.0):
            time.sleep(0.02)
        assert "exec-flappy" in runtime.live_executors(timeout_s=60.0), \
            "live sender never re-registered after the dead promotion"
    finally:
        stop.set()
        time.sleep(0.15)          # let a mid-flight beat drain first
        runtime.mark_unreachable("exec-flappy")   # leave no state behind


# ---------------------------------------------------------------------------
# metrics surfaces
# ---------------------------------------------------------------------------

def test_lineage_metrics_roll_into_session_metrics():
    from spark_rapids_tpu.expressions import col
    from spark_rapids_tpu.plan import Session, table
    ses = Session()
    t = pa.table({"x": np.arange(16, dtype=np.int64)})
    ses.collect(table(t).select(col("x")))   # watermarks lineage counters
    reg = LineageRegistry()
    payload = b"the-block"
    reg.register_fragment(21, 0, lambda rs: {r: payload for r in rs}, "d")
    reg.note_block(21, 0, 0, payload)
    assert reg.recover(21, 0, 0) == payload
    m = ses.metrics()
    assert m.get("lineage.recomputeCount", 0) > 0, m
    assert m.get("lineage.recomputedPartitions", 0) > 0, m


def test_serving_stats_exposes_lineage_counters():
    from spark_rapids_tpu.server import PlanServer
    server = PlanServer().start()
    try:
        stats = server.serving_stats()
        assert set(stats["lineage"]) == {
            "recomputeCount", "recomputedPartitions", "replicaBytes",
            "lineageMissCount"}
    finally:
        server.stop(grace_s=2.0)


# ---------------------------------------------------------------------------
# plan-server stop() during an active recompute (satellite): the
# recompute loop observes the cancel flag, the admission slot frees,
# nothing leaks
# ---------------------------------------------------------------------------

def test_plan_server_stop_cancels_active_recompute(monkeypatch):
    from spark_rapids_tpu.expressions import col
    from spark_rapids_tpu.plan import table
    from spark_rapids_tpu.plan.session import Session
    from spark_rapids_tpu.server import PlanClient, PlanServer
    from spark_rapids_tpu.shuffle import lineage as lineage_mod

    reg = LineageRegistry()
    payload = b"recomputed-block"
    started = threading.Event()

    def slow_recompute(rs):
        started.set()
        time.sleep(0.3)
        return {r: payload for r in rs}

    # two LOST MAP OUTPUTS = two fragment re-runs; the cancel must be
    # observed between them
    reg.register_fragment(91, 0, slow_recompute, "d")
    reg.register_fragment(91, 1, slow_recompute, "d")
    reg.note_block(91, 0, 0, payload)
    reg.note_block(91, 1, 0, payload)

    recovered = []
    orig_collect = Session.collect

    def fake_collect(self, df, _prepared=None):
        # stand-in for an exchange read whose every serving peer died
        # mid-collect: the recompute loop runs INSIDE the admitted
        # region with the server's cancel scope installed on this
        # worker thread — exactly how the real read captures it
        cancel = lineage_mod.current_cancel()
        assert cancel is not None, \
            "server did not install the lineage cancel scope"
        for m in (0, 1):
            recovered.append(reg.recover(91, m, 0, cancel=cancel))
        return orig_collect(self, df, _prepared=_prepared)

    monkeypatch.setattr(Session, "collect", fake_collect)
    cat = device_budget()
    baseline_threads = threading.active_count()
    server = PlanServer().start()
    t = pa.table({"x": np.arange(8, dtype=np.int64)})
    client_errors = []

    def run_client():
        try:
            with PlanClient("127.0.0.1", server.port) as c:
                c.collect(table(t).select(col("x")), timeout_ms=30000)
        except Exception as e:          # stop() kills the connection
            client_errors.append(e)

    th = threading.Thread(target=run_client, daemon=True)
    th.start()
    assert started.wait(15.0), "the recompute never started"
    # stop() lands while block 0's recompute is running: the loop must
    # finish that recompute, then OBSERVE the cancel flag before block 1
    server.stop(grace_s=10.0)
    th.join(timeout=10.0)
    assert not th.is_alive()
    assert recovered == [payload], \
        f"cancel not observed between recomputes: {len(recovered)}"
    assert server.active_query_count == 0
    adm = server._server.query_admission
    assert adm.in_flight == 0, "admission slot leaked across the cancel"
    assert cat.total_pinned() == 0, cat.dump_state()
    _threads_settle(baseline_threads)


def test_retry_loop_observes_cancel_between_attempts():
    """with_retry's cancelled hook: a retry storm stops at the next
    attempt boundary instead of riding out its backoff budget."""
    from spark_rapids_tpu.memory.catalog import OutOfBudgetError
    from spark_rapids_tpu.memory.retry import (RetryCancelledError,
                                               with_retry_no_split)
    calls = []

    def body():
        calls.append(1)
        raise OutOfBudgetError("synthetic pressure")

    with pytest.raises(RetryCancelledError):
        with_retry_no_split(body, name="test",
                            cancelled=lambda: len(calls) >= 2)
    assert len(calls) == 2


# ---------------------------------------------------------------------------
# CI/tooling: unified robustness lint + chaos wrappers
# ---------------------------------------------------------------------------

def test_lint_robustness_clean():
    """The tree passes retry + net + swallow — this IS the tier-1 job
    (supersedes the separate lint_retry/lint_net invocations)."""
    assert _load_tool("lint_robustness").lint_all() == []


def test_lint_robustness_catches_silent_swallow(tmp_path):
    lint = _load_tool("lint_robustness")
    bad = tmp_path / "shuffle"
    bad.mkdir()
    (bad / "bad.py").write_text(
        "try:\n    x = 1\nexcept Exception:\n    pass\n")
    (bad / "ok.py").write_text(
        "try:\n    x = 1\nexcept Exception:\n"
        "    pass  # robust-ok: reason\n")
    (bad / "handled.py").write_text(
        "try:\n    x = 1\nexcept Exception:\n    raise\n")
    problems = lint.lint_swallows(str(tmp_path))
    assert len(problems) == 1 and "bad.py:3" in problems[0]


def test_chaos_marker_registered_and_implies_slow(request):
    """The conftest adds `slow` to every chaos-marked test, so the
    tier-1 `-m 'not slow'` command and the smoke gate exclude soaks."""
    assert any(m.startswith("chaos:")
               for m in request.config.getini("markers"))
    items = [i for i in request.session.items
             if i.name == "test_chaos_soak_nightly"]
    if items:        # present unless deselected by -k/-m
        assert items[0].get_closest_marker("chaos") is not None
        assert items[0].get_closest_marker("slow") is not None


def test_chaos_soak_short(soak):
    """A couple of soak rounds in tier-1: the harness itself stays
    green (the ≥5-minute acceptance soak is the chaos-marked job)."""
    stats = soak.soak(duration_s=8.0, seed=11, rows=1200, verbose=False)
    assert stats["rounds"] >= 1
    assert stats["ok"], stats["failures"]
    assert stats["wrong_results"] == 0
    assert stats["leaked_pins"] == 0


@pytest.mark.chaos
def test_chaos_soak_nightly(soak):
    """ISSUE 11 acceptance: a ≥5-minute mixed kill/net/OOM soak with
    zero wrong results and zero leaks (nightly; `pytest -m chaos`)."""
    stats = soak.soak(duration_s=300.0, seed=1, rows=3000, verbose=False)
    assert stats["ok"], stats["failures"]
    assert stats["rounds"] >= 20
    assert stats["kills"] > 0 and stats["recomputeCount"] > 0
    assert stats["wrong_results"] == 0
