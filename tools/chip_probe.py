"""What the attached device and its compiler do with 64-bit types, sorts and
scans — the facts docs/tpu_compat.md states, re-measured on this installation.

Run on the chip: ``python tools/chip_probe.py [log2_rows] [text]`` (one JSON
object per line; 2**20 rows, the served path's batch capacity, by default;
only the probes whose name holds ``text`` where it is given). Each probe is
independent; a probe that raises is reported, not fatal, and the exit code
is non-zero if any did.

The ``run ...`` probes time, at run time, each form the execs are built on
(``exec/common.lex_sort_permutation``, the prefix ladder, ``searchsorted``
by binary search, f64 key words) against the form it replaced for compile
seconds, the join probe's bucket search (``exec/join._table_search``)
against the whole-build search it replaced, and the pair slots' probe rows
by a one-pass map (``exec/join._map_slot_rows``) against the search it
replaced and the other forms of the map, on the same data, and check that
all give the same answer.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax
    jax.config.update("jax_enable_x64", True)
    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())},
                      "memory_stats": dev.memory_stats()}), flush=True)
    failed = 0
    only = sys.argv[2] if len(sys.argv) > 2 else ""

    def probe(name, fn):
        nonlocal failed
        if only not in name:
            return
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception as e:  # report every probe, fail at the end
            failed += 1
            out = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print(json.dumps({"probe": name, "seconds":
                          round(time.perf_counter() - t0, 3), **out}),
              flush=True)

    def f64_roundtrip():
        vals = np.array([1e200, 1e-310, 1.0 + 2.0 ** -52, 3.4e38 * 10, -0.0])
        back = np.asarray(jax.device_put(vals))
        return {"sent": [repr(v) for v in vals],
                "back": [repr(v) for v in back],
                "bit_exact": bool((vals.view(np.int64)
                                   == back.view(np.int64)).all())}

    def f64_arith():
        a = jnp.asarray(np.array([1.0, 1e200, 1e-300]))
        b = jnp.asarray(np.array([2.0 ** -52, 1e100, 1e-10]))
        got = np.asarray(jax.jit(lambda x, y: (x + y, x * y))(a, b))
        exp = np.stack([np.array([1.0, 1e200, 1e-300])
                        + np.array([2.0 ** -52, 1e100, 1e-10]),
                        np.array([1.0, 1e200, 1e-300])
                        * np.array([2.0 ** -52, 1e100, 1e-10])])
        return {"got": [repr(v) for v in got.ravel()],
                "numpy": [repr(v) for v in exp.ravel()]}

    def f64_sum_precision():
        rng = np.random.default_rng(0)
        x = rng.uniform(1.0, 1e5, 1 << 20)
        got = float(jax.jit(jnp.sum)(jnp.asarray(x)))
        exp = float(np.sum(x))
        return {"rel_err": abs(got - exp) / exp}

    def bitcast64():
        x = jnp.asarray(np.array([1.5, -2.0, 1e300]))
        got = np.asarray(jax.jit(
            lambda v: lax.bitcast_convert_type(v, jnp.int64))(x))
        exp = np.array([1.5, -2.0, 1e300]).view(np.int64)
        return {"equal": bool((got == exp).all()),
                "got": [int(v) for v in got]}

    def log_precision():
        x = np.array([3.0, 1e10, 7.123456789])
        got = np.asarray(jax.jit(jnp.log)(jnp.asarray(x)))
        return {"max_rel_err": float(np.max(np.abs(got - np.log(x))
                                            / np.abs(np.log(x))))}

    def blocks():
        n = 1 << 22
        x = jnp.asarray(np.random.default_rng(1).uniform(size=n))
        f = jax.jit(lambda v: jnp.sort(v))
        jax.block_until_ready(f(x))
        t0 = time.perf_counter()
        y = f(x)
        t_dispatch = time.perf_counter() - t0
        jax.block_until_ready(y)
        t_block = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(y[:1])
        t_after = time.perf_counter() - t0
        return {"dispatch_s": t_dispatch, "blocked_s": t_block,
                "fetch_after_block_s": t_after}

    def compile_time(fn, *shapes):
        def run():
            args = [jnp.zeros(s, d) for s, d in shapes]
            t0 = time.perf_counter()
            c = jax.jit(fn).lower(*args).compile()
            dt = time.perf_counter() - t0
            ma = c.memory_analysis()
            return {"compile_s": round(dt, 2),
                    "code_bytes": ma.generated_code_size_in_bytes}
        return run

    def same(a, b):
        if a.dtype.kind == "f":     # prefix sums differ by summation order
            return bool(np.allclose(a, b, rtol=1e-9, equal_nan=True))
        return bool(np.array_equal(a, b))

    def run_time(forms, make_args, reps=7):
        """Per form: compile seconds, then ``reps`` blocked calls on the same
        device arrays (ms, unrounded); ``equal`` says every form returned
        what the first did."""
        def run():
            args = [jnp.asarray(a) for a in make_args()]
            out, first = {}, None
            for label, fn in forms:
                t0 = time.perf_counter()
                c = jax.jit(fn).lower(*args).compile()
                compile_s = time.perf_counter() - t0
                got = jax.block_until_ready(c(*args))
                ms = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(c(*args))
                    ms.append((time.perf_counter() - t0) * 1e3)
                got = [np.asarray(g) for g in jax.tree.leaves(got)]
                if first is None:
                    first = got
                out[label] = {
                    "compile_s": round(compile_s, 2),
                    "code_bytes":
                        c.memory_analysis().generated_code_size_in_bytes,
                    "run_ms_min": min(ms),
                    "run_ms_median": sorted(ms)[reps // 2],
                    "run_ms": ms,
                    "equal": all(map(same, first, got))}
            return out
        return run

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.batch import DeviceColumn
    from spark_rapids_tpu.exec.common import (lex_sort_permutation,
                                              orderable_words)
    from spark_rapids_tpu.exec.join import (ProbeTable, _map_slot_rows,
                                            _probe_table, _search_slot_rows,
                                            _table_search)
    from spark_rapids_tpu.expressions.aggregates import _cumsum as ladder
    from spark_rapids_tpu.expressions.aggregates import _running_max

    n = 1 << (int(sys.argv[1]) if len(sys.argv) > 1 else 20)
    rng = np.random.default_rng(7)
    iota = lambda: jnp.arange(n, dtype=jnp.int32)  # noqa: E731

    def keys3():        # a dead/null-rank lane and two key words
        return [(rng.random(n) < 0.01).astype(np.uint8),
                rng.integers(0, 1 << 10, n).astype(np.uint32),
                rng.integers(0, 1 << 32, n, dtype=np.uint64)
                .astype(np.uint32)]

    def payload():      # one key word, three 64-bit payload columns
        return [rng.integers(0, 1 << 20, n).astype(np.uint32),
                rng.integers(-1 << 40, 1 << 40, n),
                rng.uniform(-1e6, 1e6, n), rng.uniform(0, 1, n)]

    def carried(k, a, b, c):
        return jax.lax.sort((k, a, b, c), num_keys=1, is_stable=True)[1:]

    def gathered(k, a, b, c):
        perm = lex_sort_permutation([k])
        return [jnp.take(x, perm) for x in (a, b, c)]

    def f64_words(x):
        return orderable_words(DeviceColumn(x, jnp.ones(x.shape, bool),
                                            None, T.FLOAT64))

    def sorted_and_probes():
        return [np.sort(rng.integers(0, 1 << 31, n).astype(np.uint32)),
                rng.integers(0, 1 << 31, n).astype(np.uint32)]

    def join_build(keys, cap, probes, sign):
        """A hash join's build of ``keys`` in ``cap`` slots as the join
        keeps it (orderable words sorted, dead slots the largest word) and
        its probe words: the whole-build search's arguments (sorted words,
        live count, per-position run lengths clamped at it, probes), then
        the fields of the directory ``exec/join._probe_table`` makes."""
        dtype = np.uint64 if sign == 63 else np.uint32
        flip = dtype(1) << dtype(sign)
        words = np.sort(keys.astype(dtype) ^ flip)
        nv = len(words)
        padded = np.full(cap, np.iinfo(dtype).max, dtype)
        padded[:nv] = words
        _, first, counts = np.unique(words, return_index=True,
                                     return_counts=True)
        runlen = np.zeros(cap, np.int32)
        runlen[first] = counts
        table = jax.jit(_probe_table)(jnp.asarray(padded), jnp.int32(nv))
        return [padded, np.int32(nv), runlen,
                probes.astype(dtype) ^ flip, *table]

    def whole_build_search(sorted_h, n_valid, runlen, h, *_):
        lo = jnp.minimum(jnp.searchsorted(sorted_h, h, side="left",
                                          method="scan").astype(jnp.int32),
                         n_valid)
        hit = (jnp.take(sorted_h, jnp.clip(lo, 0, sorted_h.shape[0] - 1))
               == h) & (lo < n_valid)
        return lo, jnp.where(hit, jnp.take(runlen, lo), 0)

    def directory_search(sorted_h, n_valid, runlen, h, *table):
        return _table_search(ProbeTable(*table), h)

    def returns_join():     # Q95: web_returns' order numbers, repeated
        return join_build(rng.integers(1, 60_001, 71_763), 1 << 17,
                          rng.integers(1, 60_001, 1 << 19), 63)

    def date_dim_join():    # DS Q3: one month of every year's date keys
        days = np.arange(2_415_022, 2_488_070)
        return join_build(days[(days // 30) % 12 == 10][:6_000], 1 << 17,
                          rng.integers(2_450_816, 2_452_642, 1 << 20), 31)

    def pair_slots(rows, counts, out_cap, part):
        """A probe batch of ``rows`` slots whose live rows have ``counts``
        candidates (dead rows none), and part ``part`` of its pair slots
        at ``out_cap`` a part: the offsets, the counts and the part's
        first slot."""
        c = np.zeros(rows, np.int32)
        c[:len(counts)] = counts
        offsets = np.cumsum(c, dtype=np.int64)
        assert offsets[-1] > part * out_cap and offsets[-1] < 1 << 31
        return [offsets.astype(np.int32), c, np.int32(part * out_cap)]

    def slot_forms(out_cap, sort_form=True):
        """Each pair slot's probe row five ways, slots past the total read
        the last row: the search (``_gather_pairs``' before the map), the
        map marked by a scatter of max marks (a row that starts before the
        part marks slot 0), by a scatter of ascending marks, by a scatter
        of unique marks (``exec/join._map_slot_rows``, the form in use),
        and by two sorts (the starts merged with the slots by one
        two-operand sort, a stable sort on the tag to bring the slots back
        into slot order)."""
        def tail(offsets, base, rows):
            j = jnp.arange(out_cap, dtype=jnp.int32) + base
            return jnp.where(j < offsets[-1], rows, offsets.shape[0] - 1)

        def search(offsets, counts, base):
            j = jnp.arange(out_cap, dtype=jnp.int32) + base
            return jnp.clip(_search_slot_rows(offsets, j), 0,
                            offsets.shape[0] - 1)

        def starts(offsets, counts, base):
            # each row with candidates at its first slot, one that starts
            # before the part at slot 0, the rest past the end
            return jnp.where(counts > 0,
                             jnp.maximum(offsets - counts - base, 0), out_cap)

        def scatter_max(offsets, counts, base):
            rows = jnp.arange(offsets.shape[0], dtype=jnp.int32)
            marks = jnp.zeros(out_cap, jnp.int32).at[
                starts(offsets, counts, base)].max(rows, mode="drop")
            return tail(offsets, base, _running_max(marks))

        def scatter_sorted(offsets, counts, base):
            # every row marks where its candidates would start, the empty
            # ones too: the marks' positions ascend with the rows, and an
            # empty row's mark is outbid by the next row's (but at the
            # total, which the tail masks)
            at = jnp.clip(offsets - counts - base, 0, out_cap)
            rows = jnp.arange(offsets.shape[0], dtype=jnp.int32)
            marks = jnp.zeros(out_cap, jnp.int32).at[at].max(
                rows, mode="drop", indices_are_sorted=True)
            return tail(offsets, base, _running_max(marks))

        def scatter_unique(offsets, counts, base):
            return tail(offsets, base,
                        _map_slot_rows(offsets, counts, base, out_cap))

        def sort(offsets, counts, base):
            n = offsets.shape[0]
            keys = jnp.concatenate([starts(offsets, counts, base),
                                    jnp.arange(out_cap, dtype=jnp.int32)])
            vals = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                                    jnp.full(out_cap, -1, jnp.int32)])
            _, v = lax.sort((keys, vals), num_keys=1, is_stable=True)
            tag = (v < 0).astype(jnp.int32)
            _, rows = lax.sort((tag, _running_max(jnp.maximum(v, 0))),
                               num_keys=1, is_stable=True)
            return tail(offsets, base, rows[n:])
        forms = [("old searchsorted", search),
                 ("map, scatter of max marks", scatter_max),
                 ("map, scatter of ascending marks", scatter_sorted),
                 ("map, scatter of unique marks (in use)", scatter_unique)]
        return forms + [("map, two sorts", sort)] if sort_form else forms

    # the cells' shapes: Q95's self-join parts (orders of 8-16 lines, 4 %
    # null keys), the returns join's expansion, DS Q3's date and item joins
    def self_join(part):
        live = 390_000
        c = rng.integers(8, 17, live) * (rng.random(live) >= 0.04)
        return pair_slots(1 << 19, c, 1 << 19, part)

    def lookups(rows, matched):     # counts of 0 and 1, ``matched`` ones
        return lambda: pair_slots(rows, rng.random(rows) < matched / rows,
                                  0, 0)

    slot_shapes = [
        ("2^19 rows -> 2^19 slots, part 0 (Q95's expandPart)", 1 << 19,
         lambda: self_join(0), True),
        ("2^19 rows -> 2^19 slots, part 5, from mid-row (Q95's expandPart)",
         1 << 19, lambda: self_join(5), True),
        ("2^19 rows -> 2^20 slots (Q95's returns join)", 1 << 20,
         lambda: pair_slots(1 << 19, rng.poisson(3, 314_573), 1 << 20, 0),
         True),
        ("2^20 rows -> 2^18 slots (DS Q3's date join)", 1 << 18,
         lookups(1 << 20, 209_715), True),
        ("2^18 rows -> 256 slots (DS Q3's item join)", 1 << 8,
         lookups(1 << 18, 200), True),
        ("2^18 rows -> 2^12 slots", 1 << 12, lookups(1 << 18, 3_000), False),
        ("2^18 rows -> 2^14 slots", 1 << 14, lookups(1 << 18, 12_000), False),
        ("2^20 rows -> 2^14 slots", 1 << 14, lookups(1 << 20, 12_000), False),
        ("2^20 rows -> 2^16 slots", 1 << 16, lookups(1 << 20, 50_000), False),
    ]

    probe("f64_roundtrip", f64_roundtrip)
    probe("f64_arith", f64_arith)
    probe("f64_sum_precision", f64_sum_precision)
    probe("bitcast64", bitcast64)
    probe("log_precision", log_precision)
    probe("block_until_ready", blocks)
    probe(f"run key sort, 3 words, {n} rows", run_time([
        ("old one 4-operand lax.sort", lambda a, b, c: lax.sort(
            (a, b, c, iota()), num_keys=3, is_stable=True)[-1]),
        ("new lex_sort_permutation", lambda a, b, c:
            lex_sort_permutation([a, b, c]))], keys3))
    probe(f"run key sort, 1 flag word (compaction), {n} rows", run_time([
        ("old one 2-operand lax.sort", lambda a, b, c: lax.sort(
            (a, iota()), num_keys=2)[-1]),
        ("new lex_sort_permutation", lambda a, b, c:
            lex_sort_permutation([a]))], keys3))
    probe(f"run key sort with i64,f64,f64 payload, {n} rows", run_time([
        ("old payload carried through the sort", carried),
        ("new permutation, then three gathers", gathered)], payload))
    probe(f"run prefix sum i32, {n} rows", run_time([
        ("old jnp.cumsum", jnp.cumsum), ("new ladder", ladder)],
        lambda: [rng.integers(0, 4, n).astype(np.int32)]))
    probe(f"run searchsorted u32, {n} in {n}", run_time([
        ("old method=sort", lambda s, q: jnp.searchsorted(
            s, q, side="left", method="sort")),
        ("new method=scan", lambda s, q: jnp.searchsorted(
            s, q, side="left", method="scan"))], sorted_and_probes))
    probe("run join probe, 2^19 u64 probes into 71,763 words with repeats "
          "in 2^17 (Q95's returns join)", run_time([
              ("old whole-build searchsorted", whole_build_search),
              ("new directory bucket search", directory_search)],
              returns_join))
    probe("run join probe, 2^20 u32 probes into 6,000 words in 2^17 "
          "(DS Q3's date_dim)", run_time([
              ("old whole-build searchsorted", whole_build_search),
              ("new directory bucket search", directory_search)],
              date_dim_join))
    for label, out_cap, make, sort_form in slot_shapes:
        probe(f"run pair slots' probe rows, {label}",
              run_time(slot_forms(out_cap, sort_form), make))
    probe(f"run f64 key words (_double_bits_words), {n} rows", run_time([
        ("new", f64_words)], lambda: [rng.uniform(-1e6, 1e6, n)]))
    # last: the old form's compile is the longest of all (minutes)
    probe(f"run prefix sum f64, {n} rows", run_time([
        ("new ladder", ladder), ("old jnp.cumsum", jnp.cumsum)],
        lambda: [rng.uniform(0, 1, n)]))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
