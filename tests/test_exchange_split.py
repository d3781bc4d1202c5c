"""The exchange's splitter (``PartitioningExchangeExec.split``): one ordering
of the row index by partition id, one host read and one gather a piece must
hand out exactly the pieces that ``compact(batch, pids == p)`` shrunk to its
row-count bucket did, for every partitioning, partition count, batch shape
and column layout the three host exchanges see."""

import decimal

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import trace as qtrace
from spark_rapids_tpu.batch import bucket_capacity, from_arrow, to_arrow
from spark_rapids_tpu.dictenc import dictionary_encode_arrow
from spark_rapids_tpu.exec import InMemoryScanExec
from spark_rapids_tpu.exec.common import compact
from spark_rapids_tpu.exec.sort import asc
from spark_rapids_tpu.expressions import col
from spark_rapids_tpu.memory.catalog import BufferCatalog
from spark_rapids_tpu.memory import retry
from spark_rapids_tpu.memory.retry import (SpillableInput,
                                           split_input_halves)
from spark_rapids_tpu.shuffle import (HashPartitioning,
                                      MultithreadedShuffleExchangeExec,
                                      RangePartitioning,
                                      RoundRobinPartitioning,
                                      ShuffleExchangeExec)
from spark_rapids_tpu.shuffle.exchange import CachedShuffleExchangeExec

ROWS = 700


def _table(n=ROWS, seed=34):
    """Nullable int64, double, dictionary string, decimal128 limbs (2-D
    data) and a struct with a null-carrying child."""
    rng = np.random.default_rng(seed)
    k = rng.integers(-40, 40, n)
    words = np.array([f"w{i:02d}" for i in range(23)])
    money = [decimal.Decimal(int(v)) / 100
             for v in rng.integers(-10**12, 10**12, n)]
    st = pa.StructArray.from_arrays(
        [pa.array(rng.integers(0, 9, n).astype(np.int32)),
         pa.array(rng.uniform(-1, 1, n), mask=rng.random(n) < 0.2)],
        names=["a", "b"], mask=pa.array(np.arange(n) % 11 == 5))
    return dictionary_encode_arrow(pa.table({
        "k": pa.array(k, mask=rng.random(n) < 0.1),
        "d": pa.array(rng.normal(size=n)),
        "s": pa.array(words[rng.integers(0, 23, n)]),
        "m": pa.array(money, pa.decimal128(25, 2)),
        "st": st}))


@pytest.fixture(scope="module")
def batches():
    """name -> batch: full (no dead row), dead rows, empty; one schema."""
    t = _table()
    full, schema = from_arrow(t.slice(0, 512))
    assert full.capacity == 512 and full.columns[2].dict_data is not None
    assert full.columns[3].data.ndim == 2 and full.columns[4].is_struct
    dead, _ = from_arrow(t, schema=schema, capacity=1024)
    empty, _ = from_arrow(t.slice(0, 0), schema=schema, capacity=128)
    return {"full": full, "dead": dead, "empty": empty}, schema


def _partitioning(kind, n):
    return {"hash": lambda: HashPartitioning([col("k"), col("s")], n),
            "roundrobin": lambda: RoundRobinPartitioning(n, start=3),
            "range": lambda: RangePartitioning([asc(col("k"))], n)}[kind]()


def _exchange(kind, n, bs, schema, cls=ShuffleExchangeExec, **kw):
    ex = cls(_partitioning(kind, n), InMemoryScanExec(list(bs), schema),
             **kw)
    if kind == "range" and n > 1:
        ex._sample_range_bounds(list(bs))
    return ex


# (the partition index traced: one reference program whatever ``n``)
_compacted = jax.jit(lambda b, pids, p: compact(b, pids == p))


@pytest.mark.parametrize("shape", ["full", "dead", "empty"])
@pytest.mark.parametrize("n", [1, 2, 8, 200, 300])
@pytest.mark.parametrize("kind", ["hash", "roundrobin", "range"])
def test_pieces_equal_the_compacted_slices(batches, kind, n, shape):
    bs, schema = batches
    b = bs[shape]
    ex = _exchange(kind, n, [bs["dead"]], schema)
    pids = ex.partitioning.partition_ids(b, ex.ctx) if n > 1 else \
        jnp.zeros(b.capacity, jnp.int32)
    got = {p: (piece, rows) for p, piece, rows in ex.split(b)}
    assert list(got) == sorted(got)             # in partition order
    total = 0
    for p in range(n):
        want = _compacted(b, pids, p)
        rows = int(want.num_rows)
        total += rows
        if rows == 0:
            assert p not in got                 # empty pieces skipped
            continue
        piece, said = got[p]
        assert said == rows == int(piece.num_rows)
        assert piece.capacity == min(bucket_capacity(rows), b.capacity)
        # cell for cell, in row order (nulls, limbs, struct children)
        assert to_arrow(piece, schema).equals(to_arrow(want, schema)), p
        # the dictionary rides along: the input's own object
        assert piece.columns[2].dict_data is b.columns[2].dict_data
        assert piece.columns[2].dict_lengths is b.columns[2].dict_lengths
    assert total == int(b.num_rows)
    if n >= 200 and kind != "roundrobin" and shape != "empty":
        assert len(got) < n                     # some receive nothing


@pytest.fixture
def host_reads(monkeypatch):
    """Counts device-to-host reads (every ``int()``, ``bool()``,
    ``np.asarray`` of a device array goes through ``ArrayImpl._value``)."""
    from jax._src.array import ArrayImpl
    reads = []
    inner = ArrayImpl._value

    def counted(self):
        if self._npy_value is None:
            reads.append(self.shape)
        return inner.fget(self)

    monkeypatch.setattr(ArrayImpl, "_value", property(counted))
    return reads


@pytest.mark.parametrize("n", [1, 8, 300])
def test_one_host_read_a_batch_and_the_counters(batches, host_reads, n):
    bs, schema = batches
    b = bs["dead"]
    ex = _exchange("hash", n, [b], schema)
    list(ex.split(b))                           # programs built, warm
    del host_reads[:]
    with qtrace.query_trace():
        with qtrace.span("ShuffleExchangeExec.write",
                         kind="shuffle") as write:
            pieces = list(ex.split(b))
    # one read: the n counts together (n == 1: the batch's row count)
    assert host_reads == [(n,) if n > 1 else ()]
    attrs = write.attrs
    if n == 1:
        assert "splitBatches" not in attrs      # nothing to split
        return
    assert attrs["splitBatches"] == 1
    assert attrs["splitPieces"] == len(pieces)
    assert attrs["splitRowsSorted"] == b.capacity       # not n x capacity
    assert attrs["splitRowsGathered"] == \
        sum(piece.capacity for _, piece, _ in pieces)
    assert attrs["splitRowsGathered"] < 2 * b.capacity + n * 128


def test_half_inputs_split_to_the_same_pieces_in_order(batches,
                                                       monkeypatch):
    bs, schema = batches
    b = bs["dead"]
    ex = _exchange("hash", 8, [b], schema)
    cat = BufferCatalog(device_limit=1 << 30)
    whole = {p: to_arrow(piece, schema).slice(0, rows)
             for p, piece, rows in ex.split(b)}
    monkeypatch.setattr(retry._POLICY, "split_floor_rows", 16)
    halves = split_input_halves(SpillableInput.from_batch(b, schema, cat))
    parts = {}
    for half in halves:
        hb = half.acquire()
        for p, piece, rows in ex.split(hb):
            parts.setdefault(p, []).append(
                to_arrow(piece, schema).slice(0, rows))
        half.release()
        half.close()
    assert sorted(parts) == sorted(whole)
    for p, want in whole.items():
        assert pa.concat_tables(parts[p]).equals(want), p


def test_recompute_gives_the_bytes_it_published(batches, tmp_path):
    bs, schema = batches
    n = 8
    ex = _exchange("hash", n, [bs["full"], bs["dead"]], schema,
                   cls=MultithreadedShuffleExchangeExec,
                   shuffle_dir=str(tmp_path / "shuf"), num_threads=2)
    try:
        ex._write_all()
        for m in range(2):
            again = ex._make_recompute(0, m)(list(range(n)))
            for r in range(n):
                listed = (ex.shuffle_id, m, r) in \
                    ex.transport.list_blocks(ex.shuffle_id, r)
                if again[r] is None:
                    assert not listed
                else:
                    assert again[r] == ex.transport.fetch(
                        ex.shuffle_id, m, r)
    finally:
        ex.cleanup()


@pytest.mark.parametrize("cls", [ShuffleExchangeExec,
                                 CachedShuffleExchangeExec,
                                 MultithreadedShuffleExchangeExec])
def test_every_host_exchange_reads_back_its_input(batches, cls, tmp_path):
    """The three modes through the one splitter: every row once, each key
    in one partition."""
    bs, schema = batches
    kw = {"shuffle_dir": str(tmp_path / "shuf")} \
        if cls is MultithreadedShuffleExchangeExec else {}
    ex = _exchange("hash", 8, [bs["full"], bs["dead"]], schema, cls=cls,
                   **kw)
    try:
        parts = [[to_arrow(b, schema).slice(0, int(b.num_rows))
                  for b in ex.execute_partition(p)] for p in range(8)]
        got = pa.concat_tables(t for part in parts for t in part)
        want = pa.concat_tables(
            [to_arrow(bs[s], schema).slice(0, int(bs[s].num_rows))
             for s in ("full", "dead")])
        key = [("d", "ascending")]
        assert got.sort_by(key).equals(want.sort_by(key))
        homes = {}
        for p, part in enumerate(parts):
            for t in part:
                for k, s in zip(t["k"].to_pylist(), t["s"].to_pylist()):
                    assert homes.setdefault((k, s), p) == p
    finally:
        ex.close()
