"""What the attached device and its compiler do with 64-bit types, sorts and
scans — the facts docs/tpu_compat.md states, re-measured on this installation.

Run on the chip: ``python tools/chip_probe.py`` (one JSON object per line).
Each probe is independent; a probe that raises is reported, not fatal, and
the exit code is non-zero if any did.
"""

import json
import sys
import time

import numpy as np


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

    n = 1 << 20
    probe("f64_roundtrip", f64_roundtrip)
    probe("f64_arith", f64_arith)
    probe("f64_sum_precision", f64_sum_precision)
    probe("bitcast64", bitcast64)
    probe("log_precision", log_precision)
    probe("block_until_ready", blocks)
    probe("compile sort i32+i32 1M", compile_time(
        lambda k, p: lax.sort((k, p), num_keys=1),
        ((n,), jnp.int32), ((n,), jnp.int32)))
    probe("compile cumsum i32 1M", compile_time(
        jnp.cumsum, ((n,), jnp.int32)))
    probe("compile sort u8,i32,i32+i32 1M", compile_time(
        lambda a, b, c, p: lax.sort((a, b, c, p), num_keys=3),
        ((n,), jnp.uint8), ((n,), jnp.int32), ((n,), jnp.int32),
        ((n,), jnp.int32)))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
