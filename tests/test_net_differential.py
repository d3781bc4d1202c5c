"""Network fault-injection differential suite (ISSUE 9 acceptance).

The contract for the fault-tolerant distributed plane: the five bench
shapes (bench.py: q1_stage, hash_agg, join_sort, parquet_scan,
exchange), pushed through a REAL TcpTransport exchange (map side
publishes into its block server; the reduce side pulls every block over
the wire through a separate fetching client), must under injected
drop/delay/truncate/corrupt schedules

  1. complete — retries, reconnects and failover recover every fault,
  2. produce results bit-for-bit identical to the clean run,
  3. report nonzero fetch-retry metrics (the recovery actually ran), and
  4. leak nothing: no cached client connections, no catalog pins, and
     the server handler threads drain at close.

Plus the peer-death criteria: killing a peer mid-``fetch_many`` either
recovers via failover (blocks replicated elsewhere) or raises the typed
``PeerUnreachableError`` within the configured deadline — never hangs.
"""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from harness.threads import warm_reader_pool
from spark_rapids_tpu.batch import to_arrow
from spark_rapids_tpu.exec import InMemoryScanExec
from spark_rapids_tpu.expressions import col
from spark_rapids_tpu.memory.catalog import device_budget
from spark_rapids_tpu.shuffle import HashPartitioning
from spark_rapids_tpu.shuffle.multithreaded import \
    MultithreadedShuffleExchangeExec
from spark_rapids_tpu.shuffle.netfault import net_injection, net_injector
from spark_rapids_tpu.shuffle.transport import (PeerUnreachableError,
                                                TcpTransport,
                                                transport_metrics)

pytestmark = pytest.mark.net_inject

N = 3000


@pytest.fixture(autouse=True)
def _net_injection_off_after():
    yield
    net_injector().configure("")
    assert not net_injector().enabled


def _rng(seed):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# the five bench shapes' tables, keyed for the exchange
# ---------------------------------------------------------------------------

def _q1_stage():
    rng = _rng(3)
    return pa.table({
        "k": rng.integers(0, 3, N).astype(np.int32),       # l_returnflag
        "l_quantity": rng.integers(1, 51, N).astype(np.int64),
        "l_extendedprice": rng.uniform(1.0, 1e5, N),
    })


def _hash_agg():
    rng = _rng(5)
    return pa.table({
        "k": rng.integers(0, 256, N).astype(np.int64),     # ss_item_sk
        "ss_quantity": rng.integers(1, 100, N).astype(np.int64),
    })


def _join_sort():
    rng = _rng(9)
    return pa.table({
        "k": rng.integers(0, 64, N).astype(np.int64),
        "v": rng.integers(-1000, 1000, N).astype(np.int64),
        "cls": rng.integers(0, 7, N).astype(np.int64),
    })


def _parquet_scan(tmp_path):
    import pyarrow.parquet as pq
    rng = _rng(13)
    t = pa.table({"k": rng.integers(0, 1000, N).astype(np.int64),
                  "v": rng.uniform(-10.0, 10.0, N)})
    pq.write_table(t, str(tmp_path / "part-0.parquet"))
    return pq.read_table(str(tmp_path / "part-0.parquet"))


def _exchange_shape():
    rng = _rng(11)
    return pa.table({
        "k": rng.integers(0, 64, N).astype(np.int32),      # g
        "v": rng.integers(-1000, 1000, N).astype(np.int64),
    })


SHAPES = {
    "q1_stage": _q1_stage,
    "hash_agg": _hash_agg,
    "join_sort": _join_sort,
    "exchange": _exchange_shape,
}


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

def _wire_exchange(t: pa.Table, n_parts: int = 4, batch_rows: int = 700,
                   window_bytes: int = 64 << 10, retries: int = 6):
    """Push ``t`` through a TcpTransport exchange: the map side
    publishes into ``server_t``'s block server; the reduce side lists
    and fetches EVERY block over the wire through ``client_t``. Returns
    (per-partition arrow tables, leak report)."""
    server_t = TcpTransport(window_bytes=window_bytes)
    client_t = TcpTransport(peers={1: server_t.address}, retries=retries,
                            connect_timeout_s=5.0, io_timeout_s=5.0,
                            backoff_base_ms=1.0,
                            window_bytes=window_bytes)
    ex = MultithreadedShuffleExchangeExec(
        HashPartitioning([col("k")], n_parts),
        InMemoryScanExec(t, batch_rows=batch_rows),
        transport=server_t, read_transport=client_t)
    try:
        parts = []
        for p in range(n_parts):
            got = [to_arrow(b, ex.output_schema)
                   for b in ex.execute_partition(p)]
            parts.append(got)
        return parts
    finally:
        ex.cleanup()
        client_t.close()
        server_t.close()
        assert not client_t._conns, "leaked client connections"


def _assert_same(parts_a, parts_b):
    assert len(parts_a) == len(parts_b)
    for pa_, pb_ in zip(parts_a, parts_b):
        assert len(pa_) == len(pb_)
        for ta, tb in zip(pa_, pb_):
            assert ta.equals(tb)        # bit-for-bit


def _wait_threads(baseline: int, timeout_s: float = 5.0) -> None:
    """Server handler threads must drain once their connections close."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if threading.active_count() <= baseline:
            return
        time.sleep(0.02)
    assert threading.active_count() <= baseline, \
        f"leaked threads: {[t.name for t in threading.enumerate()]}"


def _differential(t: pa.Table, mode: str, kind: str,
                  expect_retries: bool = True, **inj_kw):
    cat = device_budget()
    clean = _wire_exchange(t)
    assert cat.total_pinned() == 0
    warm_reader_pool()
    baseline_threads = threading.active_count()
    m0 = transport_metrics().snapshot()
    with net_injection(mode, fault_kind=kind, delay_ms=5, **inj_kw):
        faulted = _wire_exchange(t)
    m1 = transport_metrics().snapshot()
    _assert_same(clean, faulted)
    if expect_retries:
        assert m1["fetchRetryCount"] > m0["fetchRetryCount"], \
            f"no fetch retries recorded under {mode}/{kind}: {m1}"
    if kind == "corrupt":
        assert m1["corruptFrameCount"] > m0["corruptFrameCount"]
    assert cat.total_pinned() == 0, cat.dump_state()
    _wait_threads(baseline_threads)


# ---------------------------------------------------------------------------
# per-kind schedules on the q1 shape (tier-1), full matrix nightly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["drop", "truncate", "corrupt"])
def test_net_differential_q1_kinds(kind):
    _differential(_q1_stage(), "every-2", kind)


def test_net_differential_q1_delay():
    # delay faults nothing — deadlines absorb the stall, zero retries
    _differential(_q1_stage(), "every-4", "delay", expect_retries=False)


@pytest.mark.slow
def test_net_differential_q1_random_schedule():
    _differential(_q1_stage(), "random-0.3", "mix", seed=42)


# ---------------------------------------------------------------------------
# every bench shape under the mixed schedule (tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_net_differential_shapes_mixed(shape):
    _differential(SHAPES[shape](), "every-2", "mix")


def test_net_differential_parquet_scan_shape(tmp_path):
    _differential(_parquet_scan(tmp_path), "every-2", "mix")


@pytest.mark.slow
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kind", ["drop", "truncate", "corrupt"])
def test_net_differential_full_matrix(shape, kind):
    _differential(SHAPES[shape](), "every-2", kind)


# ---------------------------------------------------------------------------
# peer death mid-read (ISSUE 9 acceptance)
# ---------------------------------------------------------------------------

def test_kill_peer_mid_fetch_many_fails_over():
    """Blocks replicated on a second peer: killing the first peer
    mid-``fetch_many`` degrades latency, not correctness."""
    peer1 = TcpTransport()
    peer2 = TcpTransport()
    blocks = {}
    ids = []
    for m in range(8):
        payload = bytes([m]) * 2048
        blocks[m] = payload
        peer2.publish(21, m, 0, payload)      # every block lives here
        if m < 4:
            peer1.publish(21, m, 0, payload)  # first half also on peer1
        ids.append((21, m, 0))
    client = TcpTransport(peers={1: peer1.address, 2: peer2.address},
                          retries=2, connect_timeout_s=2.0,
                          io_timeout_s=1.0, backoff_base_ms=1.0)
    try:
        it = client.fetch_many(ids, max_in_flight=2)
        first_id, first = next(it)
        assert first == blocks[first_id[1]]
        peer1.close()                         # killed mid-read
        t0 = time.monotonic()
        rest = list(it)
        assert time.monotonic() - t0 < 30.0
        for (s, m, r), data in rest:
            assert data == blocks[m], f"block m{m} corrupt after failover"
    finally:
        client.close()
        peer2.close()
        peer1.close()


def test_kill_peer_exclusive_block_raises_typed_within_deadline():
    """A block ONLY the dead peer held: fetch_many must raise the typed
    PeerUnreachableError within the configured deadline — never hang."""
    peer1 = TcpTransport()
    peer1.publish(22, 0, 0, b"only-here")
    ids = [(22, 0, 0)]
    client = TcpTransport(peers={1: peer1.address}, retries=2,
                          connect_timeout_s=1.0, io_timeout_s=0.5,
                          backoff_base_ms=1.0)
    try:
        peer1.close()
        t0 = time.monotonic()
        with pytest.raises(PeerUnreachableError):
            list(client.fetch_many(ids))
        # retries * (connect + io deadline) plus slack
        assert time.monotonic() - t0 < 10.0
    finally:
        client.close()


# ---------------------------------------------------------------------------
# metrics ride Session.metrics() (the SQLMetrics roll-up twin)
# ---------------------------------------------------------------------------

def test_transport_metrics_roll_into_session_metrics():
    from spark_rapids_tpu.plan import Session, table
    ses = Session()
    t = pa.table({"x": np.arange(32, dtype=np.int64)})
    ses.collect(table(t).select(col("x")))    # watermarks net counters
    # transport traffic attributed to this session's window: a fetch
    # that retries through an injected drop
    server = TcpTransport()
    server.publish(30, 0, 0, b"z" * 512)
    client = TcpTransport(peers={1: server.address}, retries=3,
                          connect_timeout_s=5.0, io_timeout_s=5.0,
                          backoff_base_ms=1.0)
    try:
        with net_injection("every-1", fault_kind="drop"):
            assert client.fetch(30, 0, 0) == b"z" * 512
    finally:
        client.close()
        server.close()
    m = ses.metrics()
    assert m.get("net.fetchRetryCount", 0) > 0, m
    assert "net.fetchBackoffTime" in m
