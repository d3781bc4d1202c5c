"""The pair expansion's slot map: each slot of a pair batch finds the probe
row whose candidates hold it in one pass (``exec/join._map_slot_rows``: every
row whose candidates start in the part marks its first slot, the row holding
the part's first slot marks slot 0, a running maximum carries the marks),
where a binary search of the offsets took ``ceil(log2(n + 1))`` rounds a
slot. Checked against numpy's ``searchsorted`` on every slot below
the total and against the clipped search on every slot, in every part of
the pair slots; then the shape rule that chooses between the two, the
joins' outputs under each, and the ``joinMappedSlots`` counter."""

import importlib
import os
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import compile_cache
from spark_rapids_tpu import trace as qtrace
from spark_rapids_tpu.batch import bucket_capacity
from spark_rapids_tpu.exec import (HashJoinExec, InMemoryScanExec, JoinType,
                                   collect)
from spark_rapids_tpu.exec import join as join_module
from spark_rapids_tpu.exec.join import (_map_slot_rows, _maps_slots,
                                        _search_slot_rows)
from spark_rapids_tpu.expressions import col
from spark_rapids_tpu.expressions.aggregates import _running_max


def _padded(counts, rows):
    c = np.zeros(rows, np.int32)
    c[:len(counts)] = counts
    return c


def _orders(rng, live, rows):
    """Orders of 8 to 16 lines, 4 % of probe rows without a key."""
    return _padded(rng.integers(8, 17, live) * (rng.random(live) >= 0.04),
                   rows)


# name -> (counts per probe row, slots of a part)
CASES = {
    "zero_runs_at_start_middle_and_end":
        lambda rng: (_padded([0, 0, 3, 0, 0, 2, 5, 0, 1, 0, 0], 16), 16),
    "rows_cross_the_parts": lambda rng: ([5, 7, 0, 9, 4, 0, 3, 11, 2], 8),
    "total_below_the_slots": lambda rng: (_padded([1, 2, 1], 8), 16),
    "total_equal_to_the_slots":
        lambda rng: (_padded([3, 0, 5, 4, 0, 4], 32), 16),
    "all_dead": lambda rng: (np.zeros(64, np.int32), 8),
    "counts_of_one": lambda rng: (
        _padded(rng.random(1800) < 0.3, 2048), 1024),
    "one_hot_row": lambda rng: (
        np.where(np.arange(64) == 37, 1000, 0).astype(np.int32), 256),
    "self_join_parts_of_the_probe_batch": lambda rng: (
        _orders(rng, 400, 512), 512),
    "self_join_blocked_ladder": lambda rng: (
        _orders(rng, 1500, 2048), 4096),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_map_equals_the_search(case):
    rng = np.random.default_rng(sum(map(ord, case)))
    counts, out_cap = CASES[case](rng)
    counts = np.asarray(counts, np.int32)
    offsets = np.cumsum(counts, dtype=np.int64).astype(np.int32)
    n, total = len(counts), int(offsets[-1])
    mapped = jax.jit(_map_slot_rows, static_argnums=3)
    search = jax.jit(_search_slot_rows)
    bases = range(0, max(total, 1), out_cap)
    if case == "rows_cross_the_parts":
        assert len(bases) > 3
    for base in bases:
        j = base + np.arange(out_cap, dtype=np.int32)
        live = j < total
        got = np.asarray(mapped(jnp.asarray(offsets), jnp.asarray(counts),
                                jnp.int32(base), out_cap))
        assert got.dtype == np.int32 and got.shape == (out_cap,)
        want = np.searchsorted(offsets, j, side="right")
        np.testing.assert_array_equal(got[live], want[live],
                                      err_msg=f"base {base}")
        # as _gather_pairs uses them: slots from the total on read the
        # last row, as the clipped search does
        clipped = np.clip(np.asarray(search(jnp.asarray(offsets),
                                            jnp.asarray(j))), 0, n - 1)
        np.testing.assert_array_equal(np.where(live, got, n - 1), clipped)
    if case == "total_equal_to_the_slots":
        assert total == out_cap
    if case == "total_below_the_slots":
        assert 0 < total < out_cap
    if case == "all_dead":
        assert total == 0


@pytest.mark.parametrize("n", [1, 7, 512, 1024, 4096, 1536])
def test_the_running_max_equals_numpy(n):
    """Flat below the block, blocked from 1024, flat again off a multiple
    of the block."""
    rng = np.random.default_rng(n)
    x = np.where(rng.random(n) < 0.1, rng.integers(0, 1 << 20, n),
                 0).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(jax.jit(_running_max)(x)),
                                  np.maximum.accumulate(x))


def test_the_cells_shapes_fall_on_the_sides_the_chip_chose():
    """(probe batch, pair slots) of the cells' pair-making joins: Q95's
    self-join parts and its returns join, DS Q3's date join map; DS Q3's
    item join, a handful of pairs from a whole probe batch, searches."""
    assert _maps_slots(1 << 19, 1 << 19)      # Q95's expandPart
    assert _maps_slots(1 << 19, 1 << 20)      # Q95's returns join
    assert _maps_slots(1 << 20, 1 << 18)      # DS Q3's date join
    assert not _maps_slots(1 << 18, 1 << 8)   # DS Q3's item join
    # the rule is one comparison, monotone in the pair slots
    for probe in (1 << 10, 1 << 18, 1 << 20):
        sides = [_maps_slots(probe, 1 << k) for k in range(3, 22)]
        assert sides == sorted(sides)


# ---------------------------------------------------------------------------
# The joins under either way of finding a slot's row


@pytest.fixture
def forced(monkeypatch):
    """Run a join with its slots' rows from the map, or from the search,
    whatever the shapes say; the program table forgets the programs made
    either way."""
    def force(use_map):
        compile_cache.program_table().clear()
        monkeypatch.setattr(join_module, "_maps_slots",
                            lambda probe, slots: use_map)
    yield force
    compile_cache.program_table().clear()


def _tables(seed=40):
    rng = np.random.default_rng(seed)
    per = rng.integers(1, 12, 120)
    order = np.repeat(np.arange(120, dtype=np.int64) * 3, per)
    n = len(order)
    left = pa.table({
        "o": pa.array(np.concatenate([order, [-3, 10 ** 6]]),
                      mask=np.concatenate([rng.random(n) < 0.05,
                                           [False, False]])),
        "w": pa.array(rng.integers(1, 6, n + 2).astype(np.int32)),
        "v": pa.array(np.arange(n + 2, dtype=np.int64))})
    keep = rng.random(n) < 0.6
    right = pa.table({
        "r_o": pa.array(np.concatenate([order[keep], [7, 10 ** 7]])),
        "r_w": pa.array(rng.integers(1, 6, keep.sum() + 2).astype(np.int32)),
        "r_v": pa.array(np.arange(keep.sum() + 2, dtype=np.int64))})
    return left, right


@pytest.mark.parametrize("jt,condition", [
    (JoinType.INNER, False), (JoinType.INNER, True),
    (JoinType.LEFT_OUTER, True), (JoinType.RIGHT_OUTER, False),
    (JoinType.FULL_OUTER, True), (JoinType.LEFT_SEMI, True),
    (JoinType.LEFT_ANTI, True), (JoinType.EXISTENCE, True)],
    ids=lambda p: p.value if isinstance(p, JoinType) else
    ("w_ne_r_w" if p else "keys_only"))
def test_the_joins_answer_alike_either_way(forced, monkeypatch, jt,
                                           condition):
    """Pair batches of many times the probe batch (cut to parts for the
    INNER join), dead and null-keyed probe rows, keys without partners."""
    monkeypatch.setattr(join_module, "PAIR_PART_MIN_SLOTS", 256)
    left, right = _tables()
    out = {}
    for use_map in (True, False):
        forced(use_map)
        join = HashJoinExec(
            [col("o")], [col("r_o")], jt,
            InMemoryScanExec(left, batch_rows=128), InMemoryScanExec(right),
            condition=(col("w") != col("r_w")) if condition else None)
        out[use_map] = collect(join)
    assert out[True].equals(out[False])
    assert out[True].num_rows > 0


# ---------------------------------------------------------------------------
# joinMappedSlots


def _profile(join):
    rec = qtrace.FlightRecorder()
    with qtrace.query_trace(recorder=rec):
        collect(join)
    return rec.profiles()[-1]


def _counters(join):
    got = Counter()
    for s in _profile(join)["spans"]:
        if s["name"] == "HashJoinExec":
            for k, v in (s.get("attrs") or {}).items():
                if k.startswith("join"):
                    got[k] += v
    return got


def test_a_self_join_counts_every_slot_mapped():
    """Each probe batch's pairs are many times its rows: every pair batch
    takes the map."""
    left, right = _tables()
    c = _counters(HashJoinExec(
        [col("o")], [col("r_o")], JoinType.INNER,
        InMemoryScanExec(left, batch_rows=128), InMemoryScanExec(right),
        condition=col("w") != col("r_w")))
    assert c["joinSlotsOut"] > 0
    assert c["joinMappedSlots"] == c["joinSlotsOut"]


def test_a_lookup_with_few_pairs_counts_none_mapped():
    """A probe batch of 2^14 rows of which eight find a partner: a pair
    batch of the least capacity is cheaper searched, and counts none."""
    rows = 1 << 14
    probe = pa.table({"a": pa.array(np.arange(rows, dtype=np.int64) * 5)})
    build = pa.table({"b": pa.array(np.arange(8, dtype=np.int64) * 5),
                      "bw": pa.array(np.arange(8, dtype=np.int32))})
    assert not _maps_slots(rows, bucket_capacity(8))
    c = _counters(HashJoinExec(
        [col("a")], [col("b")], JoinType.INNER,
        InMemoryScanExec(probe, batch_rows=rows), InMemoryScanExec(build),
        condition=col("a") != col("bw")))
    assert c["joinProbeBatches"] == 1 and c["joinCandidates"] == 8
    assert c["joinSlotsOut"] == bucket_capacity(8)
    assert c["joinMappedSlots"] == 0


def test_an_exact_semi_join_maps_nothing():
    """Counted matches, no pair batch: no slot at all."""
    probe = pa.table({"a": pa.array(np.arange(300, dtype=np.int64) % 40)})
    build = pa.table({"b": pa.array(np.repeat(np.arange(20,
                                                        dtype=np.int64), 5))})
    c = _counters(HashJoinExec(
        [col("a")], [col("b")], JoinType.LEFT_SEMI,
        InMemoryScanExec(probe, batch_rows=128), InMemoryScanExec(build)))
    assert c["joinProbeBatches"] == 3
    assert c["joinSlotsOut"] == 0 and c["joinMappedSlots"] == 0


def test_the_viewer_prints_the_mapped_slots():
    left, right = _tables()
    profile = _profile(HashJoinExec(
        [col("o")], [col("r_o")], JoinType.INNER,
        InMemoryScanExec(left, batch_rows=128), InMemoryScanExec(right)))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    try:
        viewer = importlib.import_module("trace_viewer")
    finally:
        sys.path.pop(0)
    row = {r["name"]: r for r in viewer.self_time_table(profile)}[
        "HashJoinExec"]
    assert row["joinMappedSlots"] == row["joinSlotsOut"] > 0
