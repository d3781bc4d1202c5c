"""Compile, for a described (not attached) v5e chip, the programs the three
``chip_smoke.py`` queries run — at the capacity the served path uses.

    JAX_PLATFORMS=cpu python tools/aot_compile.py [--cap 1048576] [name ...]

One JSON line per program: lowering and compile seconds, generated code and
temp bytes, and how many ``sort`` ops the compiled HLO holds. Nothing runs:
what the TPU compiler refuses here it would refuse on the chip, and what it
takes minutes over here it takes minutes over there (measured on the chip
machine: 0.4-0.55x of this sandbox's seconds, chip_probe.py). The compiles
that take a second or two are also tests (tests/test_chip_compile.py); the
whole list is too slow for that and lives here.

``--prims`` times the primitives instead — ``lax.sort`` by operand count and
width, cumulative sums, ``searchsorted`` methods — which is how the cost
model in ``exec/common.lex_sort_permutation`` was found.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import spark_rapids_tpu  # noqa: E402,F401  (x64 on)


def describe(tree, sharding):
    """Arrays (or shapes) -> shapes placed on the described device."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def at_capacity(tree, cap_from, cap_to):
    """The same batch structure at another row capacity."""
    def grow(a):
        shape = tuple(cap_to if d == cap_from else d for d in a.shape)
        return jax.ShapeDtypeStruct(shape, a.dtype)
    return jax.tree.map(grow, tree)


def compile_one(name, fn, args, sharding, static_argnums=()):
    """Lower + compile ``fn`` for the described device; one result dict."""
    args = [a if i in static_argnums else describe(a, sharding)
            for i, a in enumerate(args)]
    t0 = time.perf_counter()
    lowered = jax.jit(fn, static_argnums=static_argnums).lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    return {"program": name, "lower_s": round(t1 - t0, 2),
            "compile_s": round(t2 - t1, 2),
            "code_bytes": ma.generated_code_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "argument_bytes": ma.argument_size_in_bytes,
            "hlo_sorts": text.count(" sort("),
            "custom_calls": text.count("tpu_custom_call")}


# ---------------------------------------------------------------------------
# the served path's programs (exec constructors as the planner calls them)
# ---------------------------------------------------------------------------

def smoke_programs(cap):
    """name -> (fn, args[, static_argnums]) for the jitted programs of the
    three smoke queries at scan-batch capacity ``cap``. Built lazily: each
    entry is a thunk, so asking for one program builds only its execs."""
    import bench
    from spark_rapids_tpu.batch import from_arrow
    from spark_rapids_tpu.exec import (AggregateMode, HashAggregateExec,
                                       HashJoinExec, InMemoryScanExec,
                                       JoinType)
    from spark_rapids_tpu.exec.basic import FilterExec, ProjectExec
    from spark_rapids_tpu.exec.sort import SortExec, desc
    from spark_rapids_tpu.expressions import col, lit
    from spark_rapids_tpu.expressions.aggregates import Average, Count, Sum

    def batch_of(table):
        return from_arrow(table)[0]

    def q1_execs():
        t = bench.lineitem_table(cap)
        scan = InMemoryScanExec(t.slice(0, 16))
        filt = FilterExec(col("l_shipdate") <= lit(10471), scan)
        proj = ProjectExec(
            [col("l_returnflag"), col("l_linestatus"), col("l_quantity"),
             col("l_extendedprice"), col("l_discount"),
             (col("l_extendedprice") * (lit(1.0) - col("l_discount")))
             .alias("disc_price")], filt)
        aggs = [Sum(col("l_quantity")).alias("sum_qty"),
                Sum(col("l_extendedprice")).alias("sum_base_price"),
                Sum(col("disc_price")).alias("sum_disc_price"),
                Average(col("l_quantity")).alias("avg_qty"),
                Average(col("l_discount")).alias("avg_disc"),
                Count().alias("count_order")]
        keys = [col("l_returnflag"), col("l_linestatus")]
        partial = HashAggregateExec(keys, aggs, proj, AggregateMode.PARTIAL)
        final = HashAggregateExec(keys, aggs, partial, AggregateMode.FINAL)
        return batch_of(t), filt, proj, partial, final

    def q2_execs():
        t = bench.store_sales_table(cap, 1 << 20)
        scan = InMemoryScanExec(t.slice(0, 16))
        aggs = [Sum(col("ss_quantity")).alias("sq"),
                Sum(col("ss_net_profit")).alias("sp"),
                Average(col("ss_sales_price")).alias("ap"),
                Count().alias("c")]
        partial = HashAggregateExec([col("ss_item_sk")], aggs, scan,
                                    AggregateMode.PARTIAL)
        final = HashAggregateExec([col("ss_item_sk")], aggs, partial,
                                  AggregateMode.FINAL)
        return batch_of(t), partial, final

    def q3_execs():
        stream, build = bench.join_tables(cap, cap >> 1)
        join = HashJoinExec([col("l_orderkey")], [col("o_orderkey")],
                            JoinType.INNER,
                            InMemoryScanExec(stream.slice(0, 16)),
                            InMemoryScanExec(build.slice(0, 16)))
        sort = SortExec([desc(col("l_revenue"))], join)
        return batch_of(stream), batch_of(build), join, sort

    def agg_update(execs, which):
        b, *rest = execs()
        return rest[which]._update_kernel, [b]

    def agg_merge(execs, which, final, factor, rows=None):
        # ``factor`` partials of ``rows`` rows: cut to their groups'
        # bucket, or (default) as many groups as the scan batch has rows,
        # ``cap``, which the windowed pre-merge concatenates
        # max_result_rows (4M) at a time
        b, *rest = execs()
        partial = rest[-2]
        buf = jax.eval_shape(partial._update_kernel, b)
        big = at_capacity(buf, cap, (rows or cap) * factor)
        agg = rest[which]
        return (lambda x: agg._merge_kernel(x, final=final)), [big]

    def filter_kernel():
        b, filt, *_ = q1_execs()
        return filt._kernel, [b]

    def project_kernel():
        b, _, proj, *_ = q1_execs()
        return proj._kernel, [b, jnp.uint32(0)]

    def join_build():
        _, bb, join, _ = q3_execs()
        return join._build_kernel, [bb]

    def join_count():
        sb, bb, join, _ = q3_execs()
        sorted_h = jax.eval_shape(join._build_kernel, bb)[0]
        return join._count_kernel, [sb, sorted_h]

    def join_probe_shapes():
        """(stream, sorted build, (lo, counts, offsets), matched, join, sort)
        as the probe loop hands them to the expand kernel."""
        sb, bb, join, sort = q3_execs()
        sorted_h, sbuild, _ = jax.eval_shape(join._build_kernel, bb)
        lo, counts, offsets, _ = jax.eval_shape(join._count_kernel, sb,
                                                sorted_h)
        matched = jax.ShapeDtypeStruct((bb.capacity,), jnp.bool_)
        return sb, sbuild, (lo, counts, offsets), matched, join, sort

    def join_expand():
        *probe, join, _ = join_probe_shapes()
        return join._expand_kernel, [*probe, probe[0].capacity], (4,)

    def sort_kernel():
        # the join's output batches are coalesced into one before the sort:
        # 4 stream batches of `cap` rows
        from spark_rapids_tpu.exec.sort import sort_batch
        *probe, join, sort = join_probe_shapes()
        out, _ = jax.eval_shape(
            lambda *a: join._expand_kernel(*a, probe[0].capacity), *probe)
        return (lambda b: sort_batch(b, sort.orders, sort.ctx)), \
            [at_capacity(out, cap, cap * 4)]

    def exchange_execs():
        """Q3's stream side hashed eight ways on its join key, as the
        planner's shuffled join does."""
        from spark_rapids_tpu.shuffle import (HashPartitioning,
                                              ShuffleExchangeExec)
        stream, _ = bench.join_tables(cap, cap >> 1)
        ex = ShuffleExchangeExec(HashPartitioning([col("l_orderkey")], 8),
                                 InMemoryScanExec(stream.slice(0, 16)))
        return batch_of(stream), ex

    def exchange_split():
        b, ex = exchange_execs()
        return ex._split_jit, [b]

    def exchange_piece():
        # one of eight pieces, just over an eighth: the next bucket up
        b, ex = exchange_execs()
        perm = jnp.zeros(cap, jnp.int32)
        return ex._piece_jit, \
            [b, perm, jnp.int32(0), jnp.int32(0), cap >> 2], (4,)

    def pallas_murmur3():
        from spark_rapids_tpu.kernels.murmur3 import pallas_murmur3_int32
        z = jnp.zeros(cap, jnp.int32)
        return pallas_murmur3_int32, [z, jnp.ones(cap, bool), z]

    def pallas_string_search():
        # a 64-byte string column at `cap` rows packs two rows per 128 lanes
        from spark_rapids_tpu.kernels.string_search import \
            _pallas_match_packed
        return (lambda d: _pallas_match_packed(d, b"special requests", 64)), \
            [jnp.zeros((cap >> 1, 128), jnp.uint8)]

    def q1dec_execs():
        """TPC-H Q1 at decimal(15,2) (benchmarks/queries/tpchdec/q1.py):
        two limb multiplies, four limb sums, three decimal averages."""
        import decimal
        import pyarrow as pa
        n = 64
        money = {c: pa.array([decimal.Decimal(v)] * n, pa.decimal128(15, 2))
                 for c, v in (("l_quantity", "17.00"),
                              ("l_extendedprice", "1234.56"),
                              ("l_discount", "0.05"), ("l_tax", "0.02"))}
        t = pa.table(dict(
            money, l_returnflag=pa.array(["A"] * n),
            l_linestatus=pa.array(["F"] * n),
            l_shipdate=pa.array([10000] * n, pa.int32()).cast(pa.date32())))
        b0 = batch_of(t)
        b = at_capacity(b0, b0.capacity, cap)
        scan = InMemoryScanExec(t.slice(0, 16))
        one = lit(decimal.Decimal("1"))
        disc_price = col("l_extendedprice") * (one - col("l_discount"))
        proj = ProjectExec(
            [col("l_returnflag"), col("l_linestatus"), col("l_quantity"),
             col("l_extendedprice"), col("l_discount"),
             disc_price.alias("disc_price"),
             (disc_price * (one + col("l_tax"))).alias("charge")], scan)
        aggs = [Sum(col("l_quantity")).alias("sum_qty"),
                Sum(col("l_extendedprice")).alias("sum_base_price"),
                Sum(col("disc_price")).alias("sum_disc_price"),
                Sum(col("charge")).alias("sum_charge"),
                Average(col("l_quantity")).alias("avg_qty"),
                Average(col("l_extendedprice")).alias("avg_price"),
                Average(col("l_discount")).alias("avg_disc"),
                Count().alias("count_order")]
        keys = [col("l_returnflag"), col("l_linestatus")]
        partial = HashAggregateExec(keys, aggs, proj, AggregateMode.PARTIAL)
        final = HashAggregateExec(keys, aggs, partial, AggregateMode.FINAL)
        return b, proj, partial, final

    def q1dec_project():
        b, proj, *_ = q1dec_execs()
        return proj._kernel, [b, jnp.uint32(0)]

    def q1dec_update():
        b, proj, partial, _ = q1dec_execs()
        projected = jax.eval_shape(
            lambda x: proj._kernel(x, jnp.uint32(0))[0], b)
        return partial._update_kernel, [projected]

    def q1dec_merge(final, factor, rows=None):
        # ``factor`` partials of ``rows`` rows (default: uncut, ``cap``)
        b, proj, partial, fin = q1dec_execs()
        projected = jax.eval_shape(
            lambda x: proj._kernel(x, jnp.uint32(0))[0], b)
        buf = jax.eval_shape(partial._update_kernel, projected)
        big = at_capacity(buf, cap, (rows or cap) * factor)
        return (lambda x: fin._merge_kernel(x, final=final)), [big]

    def dec_mul(left_limbs):
        from spark_rapids_tpu.expressions.decimal128 import mul128
        a = jnp.zeros((cap, 4) if left_limbs else (cap,), jnp.int64)
        return (lambda x, y: mul128(x, y, 38)), [a, jnp.zeros(cap, jnp.int64)]

    def dec_avg():
        from spark_rapids_tpu.expressions.decimal128 import div_half_up
        slots = 1 << 12      # the aggregate's small group-slot layout
        return (lambda t, n: div_half_up(t, 4, n, 19)), \
            [jnp.zeros((slots, 4), jnp.int64), jnp.ones(slots, jnp.int64)]

    return {
        "q1dec.project": q1dec_project,
        "q1dec.agg_update": q1dec_update,
        "q1dec.agg_merge_4x": lambda: q1dec_merge(False, 4),
        "q1dec.agg_final_cut": lambda: q1dec_merge(True, 8, 16),
        "dec.mul_64x64": lambda: dec_mul(False),
        "dec.mul_128x64": lambda: dec_mul(True),
        "dec.avg_half_up": dec_avg,
        "q1.filter": filter_kernel,
        "q1.project": project_kernel,
        "q1.agg_update": lambda: agg_update(q1_execs, 2),
        "q1.agg_merge_4x": lambda: agg_merge(q1_execs, 2, False, 4),
        "q1.agg_final": lambda: agg_merge(q1_execs, 3, True, 1),
        "q1.agg_final_cut": lambda: agg_merge(q1_execs, 3, True, 8, 128),
        "q2.agg_update": lambda: agg_update(q2_execs, 0),
        "q2.agg_merge_4x": lambda: agg_merge(q2_execs, 0, False, 4),
        "q2.agg_final_4x": lambda: agg_merge(q2_execs, 1, True, 4),
        "q3.join_build": join_build,
        "q3.join_count": join_count,
        "q3.join_expand": join_expand,
        "q3.sort_4x": sort_kernel,
        "exchange.split": exchange_split,
        "exchange.piece": exchange_piece,
        "pallas.murmur3": pallas_murmur3,
        "pallas.string_search": pallas_string_search,
    }


def mesh_program(topo, n_fact):
    """The four-chip phase's ONE SPMD program (chip_smoke.py --chips 4) on a
    Mesh over the described devices."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import __graft_entry__ as g
    from spark_rapids_tpu.parallel.lowering import lower_to_mesh
    from spark_rapids_tpu.plan import Session
    from spark_rapids_tpu.plan.overrides import Overrides

    ses = Session(dict(g.MULTICHIP_CONF,
                       **{"spark.rapids.tpu.mesh.devices": 4}))
    plan = Overrides(ses.conf).plan(g.multichip_query(n_fact)().plan)
    # stage the inputs on four virtual CPU devices (shapes only are kept),
    # then build the same program over the described chips
    cpu_mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    stage = lower_to_mesh(plan, cpu_mesh)
    _, stacked = stage.prepare()
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), stacked)
    tpu_mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    stage.lowering.mesh = tpu_mesh
    program = stage.build_program()
    spec = NamedSharding(tpu_mesh, P("data"))
    args = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1], sharding=spec),
        shapes, is_leaf=lambda x: isinstance(x, tuple)
        and len(x) == 2 and isinstance(x[0], tuple))
    return program, args


# ---------------------------------------------------------------------------
# primitives: what the compiler's time follows
# ---------------------------------------------------------------------------

def primitive_programs(n):
    from jax import lax

    from spark_rapids_tpu.exec.common import lex_sort_permutation
    from spark_rapids_tpu.expressions.aggregates import _prefix_ladder

    def z(dt):
        return jnp.zeros(n, dt)

    def sort(keys, payload):
        ops = [z(d) for d in keys + payload]
        return (lambda *a: lax.sort(a, num_keys=len(keys))), ops

    i32, i64, f64, u8, u32, u64 = (jnp.int32, jnp.int64, jnp.float64,
                                   jnp.uint8, jnp.uint32, jnp.uint64)
    return {
        "sort i32": lambda: sort([i32], []),
        "sort i32 | i32": lambda: sort([i32], [i32]),
        "sort u32 | i32": lambda: sort([u32], [i32]),
        "sort i32 | i32,i32,i32": lambda: sort([i32], [i32] * 3),
        "sort i32 | i64,f64,f64": lambda: sort([i32], [i64, f64, f64]),
        "sort i32,i32 | i32": lambda: sort([i32, i32], [i32]),
        "sort u8,i32,i32 | i32": lambda: sort([u8, i32, i32], [i32]),
        "sort i64 | i32": lambda: sort([i64], [i32]),
        "sort f64 | i32": lambda: sort([f64], [i32]),
        "lex_sort_permutation u8,u32,u32": lambda: (
            lambda a, b, c: lex_sort_permutation([a, b, c]),
            [z(u8), z(u32), z(u32)]),
        "lex_sort_permutation u8,u64": lambda: (
            lambda a, b: lex_sort_permutation([a, b]), [z(u8), z(u64)]),
        "cumsum i32": lambda: (jnp.cumsum, [z(i32)]),
        "cumsum f64": lambda: (jnp.cumsum, [z(f64)]),
        "prefix_ladder i32": lambda: (_prefix_ladder, [z(i32)]),
        "prefix_ladder f64": lambda: (_prefix_ladder, [z(f64)]),
        "gather f64": lambda: (lambda x, i: x[i], [z(f64), z(i32)]),
        "searchsorted sort u64": lambda: (
            lambda a, q: jnp.searchsorted(a, q, method="sort"),
            [z(u64), z(u64)]),
        "searchsorted scan u64": lambda: (
            lambda a, q: jnp.searchsorted(a, q, method="scan"),
            [z(u64), z(u64)]),
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("names", nargs="*",
                   help="programs to compile (default: all)")
    p.add_argument("--cap", type=int, default=1 << 20,
                   help="scan batch capacity "
                        "(spark.rapids.tpu.sql.batchRowCapacity's default)")
    p.add_argument("--prims", action="store_true")
    p.add_argument("--mesh", action="store_true",
                   help="the four-chip mesh stage (needs "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    p.add_argument("--mesh-rows", type=int, default=1 << 22)
    args = p.parse_args(argv)

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    failed = 0
    if args.mesh:
        program, margs = mesh_program(topo, args.mesh_rows)
        t0 = time.perf_counter()
        compiled = program.lower(*margs).compile()
        text = compiled.as_text()
        ma = compiled.memory_analysis()
        print(json.dumps({
            "program": f"mesh_stage fact_rows={args.mesh_rows}",
            "compile_s": round(time.perf_counter() - t0, 2),
            "all_to_all": text.count("all-to-all("),
            "hlo_sorts": text.count(" sort("),
            "temp_bytes_per_device": ma.temp_size_in_bytes,
            "argument_bytes_per_device": ma.argument_size_in_bytes}),
            flush=True)
        return 0
    programs = primitive_programs(args.cap) if args.prims \
        else smoke_programs(args.cap)
    for name in args.names or programs:
        try:
            fn, fargs, *static = programs[name]()
            out = compile_one(name, fn, fargs, one_chip,
                              tuple(static[0]) if static else ())
        except Exception as e:   # report every program, fail at the end
            failed += 1
            out = {"program": name,
                   "error": f"{type(e).__name__}: {str(e)[:400]}"}
        print(json.dumps(dict(out, cap=args.cap)), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
