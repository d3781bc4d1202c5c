"""What the attached device and its compiler do with 64-bit types, sorts and
scans — the facts docs/tpu_compat.md states, re-measured on this installation.

Run on the chip: ``python tools/chip_probe.py [log2_rows]`` (one JSON object
per line; 2**20 rows, the served path's batch capacity, by default). Each
probe is independent; a probe that raises is reported, not fatal, and the
exit code is non-zero if any did.

The ``run ...`` probes time, at run time, each form the execs are built on
(``exec/common.lex_sort_permutation``, the prefix ladder, ``searchsorted``
by binary search, f64 key words) against the form it replaced for compile
seconds, on the same data, and check that both give the same answer.
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

    def probe(name, fn):
        nonlocal failed
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
    from spark_rapids_tpu.expressions.aggregates import _cumsum as ladder

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
    probe(f"run f64 key words (_double_bits_words), {n} rows", run_time([
        ("new", f64_words)], lambda: [rng.uniform(-1e6, 1e6, n)]))
    # last: the old form's compile is the longest of all (minutes)
    probe(f"run prefix sum f64, {n} rows", run_time([
        ("new ladder", ladder), ("old jnp.cumsum", jnp.cumsum)],
        lambda: [rng.uniform(0, 1, n)]))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
