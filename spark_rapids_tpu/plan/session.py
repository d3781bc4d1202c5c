"""Session: conf-scoped query execution + plan capture.

Reference roles combined: the plugin's enable switch (spark.rapids.sql.enabled
master toggle — the differential harness flips it per run,
integration_tests/.../spark_session.py:35-60) and the plan-capture listener
(ExecutionPlanCaptureCallback.scala:31) tests use to assert which operators
actually ran on the accelerator vs fell back.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import pyarrow as pa

from ..config import RapidsTpuConf
from .interpreter import Interpreter
from .logical import DataFrame
from .overrides import CpuFallbackExec, ExplainMode, Overrides


class Session:
    def __init__(self, conf: Optional[Dict] = None):
        self.conf = RapidsTpuConf(conf)
        self.last_plan = None          # captured physical plan (exec tree)
        #: why the last plan's mesh lowering gave way to the host-mediated
        #: exchange (None: it did not, or ICI shuffle mode was not asked)
        self.last_mesh_giveway: Optional[str] = None
        #: shape fingerprint of the last prepared plan (None when the
        #: plan cache is off or the plan is uncacheable) — the key the
        #: observed-cost store records per-operator costs under
        self.last_fingerprint: Optional[str] = None
        #: query_id of the last collect (None when tracing is off)
        self.last_query_id: Optional[str] = None
        #: how the serving caches treated the last query:
        #: {"plan": hit|miss|uncacheable: ..., "result": hit|miss|off|...}
        self.last_cache: Dict[str, str] = {}
        #: (df, (key, digests) | None) kept between try_cached_result and
        #: the collect that consumes it (the server splits those calls)
        self._rc_state = None
        #: (execs, fell_back) of the run a cached result was stored from
        self._cached_serve = None
        #: raw Arrow IPC bytes of the last cached serve (b"" otherwise)
        self.last_result_ipc: bytes = b""
        #: (df, encode_plan result | Uncacheable) — ONE plandoc walk per
        #: query feeds both the result key and the shape fingerprint
        self._doc_memo = None
        #: the single-flight Flight this query leads (None when not
        #: leading) — settled by _store_result / abort_inflight
        self._sf_flight = None
        #: whether the result cache should be consulted/stored for the
        #: current query (the key may be computed for dedup alone)
        self._rc_lookup = False
        from ..dictenc import fallback_mark
        # watermark: dict_fallbacks() reports only reasons recorded on
        # THIS session's watch (the store itself is process-wide)
        self._dict_fb_mark = fallback_mark()
        from . import adaptive
        self._adaptive_mark0 = adaptive.reason_mark()

    def with_conf(self, **kv) -> "Session":
        settings = dict(self.conf._settings)
        settings.update({k.replace("_", "."): v for k, v in kv.items()})
        return Session(settings)

    def prepare(self, df: DataFrame):
        """Shared planning pipeline for every result surface (collect,
        ml export): applies sql_enabled, explain-only mode, CPU-topped
        plans and ICI mesh lowering. Returns ("interpret", None) when the
        query must run on the row interpreter, ("fallback", plan) for a
        CPU-topped plan, or ("exec", plan) for a device plan."""
        from .. import trace as qtrace
        self.last_fingerprint = None
        self.last_mesh_giveway = None
        if not self.conf.sql_enabled:
            self.last_plan = None
            return "interpret", None
        from ..config import MODE
        if self.conf.get(MODE.key) == "explainonly":
            # plan as if a TPU were present, execute on CPU
            self.last_plan = Overrides(self.conf).plan(df.plan)
            return "interpret", None
        from ..config import SERVER_PLAN_CACHE_ENABLED
        with qtrace.span("plan.prepare", kind="plan") as sp:
            fp = None
            if self.conf.get(SERVER_PLAN_CACHE_ENABLED.key):
                from . import plancache
                try:
                    with qtrace.span("plan.fingerprint", kind="plan"):
                        fp = plancache.shape_fingerprint(
                            df.plan, self.conf,
                            encoded=self._encoded_plan(df))
                except plancache.Uncacheable as e:
                    # never silent: the reason rides the cache-info surface
                    self.last_cache["plan"] = f"uncacheable: {e.reason}"
                self.last_fingerprint = fp
                if fp is not None:
                    from ..config import ADAPTIVE_COST_ENABLED
                    if self.conf.get(ADAPTIVE_COST_ENABLED.key):
                        from . import adaptive
                        advice = adaptive.advise(self.conf, fp)
                        if advice is not None:
                            # measured placement: never replayed from —
                            # and never written into — the planning
                            # cache, so a cost-fed decision cannot
                            # poison a cached fingerprint with a
                            # placement the EWMAs have since outgrown
                            self.last_cache["plan"] = \
                                f"bypass: adaptive cost-fed ({advice})"
                            if sp is not None:
                                sp.attrs["planCache"] = "adaptive"
                            return self._plan_fresh(df, fp, advice=advice,
                                                    cache_put=False)
                    with qtrace.span("plan.cacheLookup", kind="plan"):
                        decisions = plancache.planning_cache().get(fp)
                    if decisions is not None:
                        prepared = self._plan_from_decisions(df, decisions)
                        if prepared is not None:
                            plancache.metrics().note("plan_hits")
                            self.last_cache["plan"] = "hit"
                            if sp is not None:
                                sp.attrs["planCache"] = "hit"
                            return prepared
            if sp is not None:
                sp.attrs["planCache"] = "miss" if fp is not None \
                    else "uncacheable"
            return self._plan_fresh(df, fp)

    def _plan_fresh(self, df: DataFrame, fp: Optional[str],
                    advice: Optional[str] = None, cache_put: bool = True):
        """The uncached planning pipeline; when ``fp`` is set, the
        tag/CBO outcome and the fusion/mesh eligibility land in the
        process planning cache for the next same-shape query (cost-fed
        plans pass cache_put=False: adaptive decisions stay as fresh as
        the EWMAs that made them)."""
        from .. import trace as qtrace
        ov = Overrides(self.conf, adaptive_advice=advice)
        with qtrace.span("plan.overrides", kind="plan"):
            plan = ov.plan(df.plan)
        self.last_plan = plan
        from .overrides import CpuFallbackExec as _CFE
        kind = "exec"
        mesh_eligible = fuse_eligible = False
        if isinstance(plan, _CFE):
            # CPU-topped plan: stay on the host (no device round-trip for
            # the final island — required for device-unsupported types)
            kind = "fallback"
        else:
            from ..shuffle.manager import get_shuffle_manager
            lowered_done = False
            if get_shuffle_manager(self.conf).wants_mesh_lowering:
                # ICI shuffle mode: fuse the planned query onto ONE SPMD
                # mesh program (exchanges → XLA collectives); unsupported
                # plan shapes keep the host-mediated exchanges
                lowered = self._lower_to_mesh(plan)
                if lowered is not None:
                    plan = lowered
                    self.last_plan = plan
                    mesh_eligible = lowered_done = True
            if not lowered_done:
                from ..config import FUSION_ENABLED
                if self.conf.get(FUSION_ENABLED.key):
                    # whole-stage fusion: an eligible linear single-batch
                    # stage runs as ONE XLA program (overflow-flag retries
                    # inside FusedStage.run); ineligible shapes keep the
                    # iterator path
                    from ..exec.fuse import try_fuse_exec
                    fused = try_fuse_exec(plan)
                    if fused is not None:
                        plan = fused
                        self.last_plan = plan
                        fuse_eligible = True
        if fp is not None and cache_put:
            from ..config import SERVER_PLAN_CACHE_MAX_ENTRIES
            from . import plancache
            plancache.metrics().note("plan_misses")
            self.last_cache["plan"] = "miss"
            plancache.planning_cache().put(
                fp,
                plancache.PlanDecisions(
                    plancache.collect_reasons(ov.last_meta),
                    fuse_eligible=fuse_eligible,
                    mesh_eligible=mesh_eligible),
                max_entries=int(
                    self.conf.get(SERVER_PLAN_CACHE_MAX_ENTRIES.key)))
        return kind, plan

    def _plan_from_decisions(self, df: DataFrame, decisions):
        """Planning-cache hit: replay the cached tag/CBO outcome onto a
        fresh meta tree and REBUILD the physical execs (exec trees are
        stateful and never shared between collects). Fusion/mesh lowering
        run only when the cached shape proved eligible — and both
        re-validate, so a same-bucket input that no longer qualifies
        degrades to the iterator path instead of misexecuting. Returns
        None on a replay mismatch (fingerprint collision guard)."""
        from .. import trace as qtrace
        from . import plancache
        from .overrides import CpuFallbackExec as _CFE
        from .overrides import PlanMeta, insert_coalesce_transitions
        ov = Overrides(self.conf)
        with qtrace.span("plan.overrides", kind="plan", replay=True):
            meta = PlanMeta(df.plan, self.conf)
            if not plancache.apply_reasons(meta, decisions.reasons):
                return None
            ov.last_meta = meta
            from ..config import COALESCE_MAX_ROWS
            plan = insert_coalesce_transitions(
                ov._convert(meta), self.conf.batch_size_bytes,
                max_rows=int(self.conf.get(COALESCE_MAX_ROWS.key)))
        self.last_plan = plan
        if isinstance(plan, _CFE):
            return "fallback", plan
        if decisions.mesh_eligible:
            from ..shuffle.manager import get_shuffle_manager
            if get_shuffle_manager(self.conf).wants_mesh_lowering:
                lowered = self._lower_to_mesh(plan)
                if lowered is not None:
                    self.last_plan = lowered
                    return "exec", lowered
        if decisions.fuse_eligible:
            from ..exec.fuse import try_fuse_exec
            fused = try_fuse_exec(plan)
            if fused is not None:
                self.last_plan = fused
                return "exec", fused
        return "exec", plan

    def _watermark(self) -> None:
        """Snapshot every process-wide counter group ONCE per collect,
        regardless of which execution path runs (exec / interpret /
        fallback / cached serve) — an interpret collect after an exec one
        must report deltas against ITS OWN start, not the older exec
        watermark."""
        from .. import trace as qtrace
        from ..exec.python_exec import _python_semaphore
        from ..memory.retry import metrics as _retry_metrics
        from ..shuffle.lineage import metrics as _lineage_metrics
        from ..shuffle.transport import transport_metrics
        from . import adaptive, plancache, sharing
        self._retry0 = _retry_metrics().snapshot()
        self._sharing0 = sharing.metrics().snapshot()
        self._net0 = transport_metrics().snapshot()
        self._lineage0 = _lineage_metrics().snapshot()
        self._sem_wait0 = _python_semaphore.wait_time_ns
        self._cache0 = plancache.metrics().snapshot()
        self._trace0 = qtrace.metrics().snapshot()
        self._adaptive0 = adaptive.metrics().snapshot()
        self._adaptive_mark0 = adaptive.reason_mark()

    def try_cached_result(self, df: DataFrame,
                          cancelled=None) -> Optional[pa.Table]:
        """Serving-tier fast path: consult the result cache WITHOUT
        planning, then join (or lead) the in-flight single-flight table
        when sharing is on. Returns the served table (bit-for-bit: the
        stored/leader's Arrow IPC bytes) or None; the computed key is
        kept so the collect() that follows stores under it.
        ``cancelled`` (callable) lets the server's watchdog unpark a
        deduplicated waiter early."""
        from .. import trace as qtrace
        from . import plancache
        self.last_cache = {}
        self._cached_serve = None
        self.last_result_ipc = b""
        self.last_query_id = qtrace.current_query_id()
        self._sf_flight = None
        self._watermark()
        with qtrace.span("resultCache.lookup", kind="cache") as sp:
            kd = self._result_cache_key(df)
            self._rc_state = (df, kd)
            if kd is None:
                if sp is not None:
                    sp.attrs["outcome"] = \
                        self.last_cache.get("result", "off")
                return None
            if not self._rc_lookup:
                self.last_cache.setdefault("result", "off")
                if sp is not None:
                    sp.attrs["outcome"] = "off"
                return self._join_inflight(kd, cancelled)
            entry = plancache.result_cache().get(kd[0])
            if entry is None:
                plancache.metrics().note("result_misses")
                self.last_cache["result"] = "miss"
                if sp is not None:
                    sp.attrs["outcome"] = "miss"
                return self._join_inflight(kd, cancelled)
            plancache.metrics().note("result_hits")
            self.last_cache["result"] = "hit"
            if sp is not None:
                sp.attrs["outcome"] = "hit"
        self.last_plan = None
        self._cached_serve = (list(entry.execs), list(entry.fell_back))
        #: the stored bytes, so the server can forward them verbatim
        #: (bit-for-bit serving without a decode/re-encode round trip)
        self.last_result_ipc = entry.ipc
        self._rc_state = None
        from ..server import protocol
        return protocol.ipc_to_table(entry.ipc)

    def _join_inflight(self, kd, cancelled=None) -> Optional[pa.Table]:
        """In-flight dedup (docs/serving.md "Cross-query work sharing"):
        lead the flight for this result key, or park on the executing
        leader and serve its bytes verbatim. Returns the served table
        for a waiter, None for a leader/solo query (the collect that
        follows executes and settles the flight). Runs BEFORE prepare
        and admission — a parked waiter holds no slot."""
        from . import sharing
        if not sharing.inflight_on(self.conf):
            return None
        from .. import trace as qtrace
        sf = sharing.single_flight()
        timeout_s = sharing.wait_timeout_s(self.conf)
        while True:
            role, flight = sf.begin(kd[0], kd[1])
            if role == "leader":
                sharing.metrics().note("inflight_leaders")
                self._sf_flight = flight
                return None
            sharing.metrics().note("inflight_waits")
            with qtrace.span("sharing.inflightWait", kind="cache") as sp:
                out = sf.wait(flight, timeout_s, cancelled=cancelled)
                if sp is not None:
                    sp.attrs["outcome"] = out.state
            if out.state == "result":
                sharing.metrics().note("inflight_served")
                self.last_cache["result"] = "inflight"
                self.last_plan = None
                self._cached_serve = (
                    list(out.payload.get("execs", ())),
                    list(out.payload.get("fell_back", ())))
                self.last_result_ipc = out.ipc
                self._rc_state = None
                from ..server import protocol
                return protocol.ipc_to_table(out.ipc)
            if out.state == "promoted":
                # the leader failed; this waiter re-executes as the new
                # leader — an error is never served to a waiter verbatim
                sharing.metrics().note("inflight_promoted")
                self._sf_flight = flight
                return None
            if out.state in ("invalidated", "failed"):
                # drop_table/re-upload outdated the flight (or it
                # retired with no result): re-enter against the
                # post-drop table — never serve the stale leader result
                continue
            sharing.metrics().note("inflight_timeouts")
            return None     # execute solo, publish nothing

    def abort_inflight(self, error=None) -> None:
        """Settle an un-completed leader flight after a failure anywhere
        between try_cached_result and _store_result (prepare, admission,
        execution, cancellation): one parked waiter is promoted to
        leader, the rest keep waiting on it. Idempotent."""
        flight = self._sf_flight
        self._sf_flight = None
        if flight is not None:
            from . import sharing
            sharing.single_flight().fail(flight, error)

    def _encoded_plan(self, df: DataFrame):
        """Memoized plancache.encode_plan for the current query: one
        plandoc walk feeds both cache keys. Raises (and re-raises the
        memoized) Uncacheable."""
        from . import plancache
        memo = self._doc_memo
        if memo is not None and memo[0] is df:
            if isinstance(memo[1], plancache.Uncacheable):
                raise memo[1]
            return memo[1]
        try:
            enc = plancache.encode_plan(df.plan)
        except plancache.Uncacheable as e:
            self._doc_memo = (df, e)
            raise
        self._doc_memo = (df, enc)
        return enc

    def _result_cache_key(self, df: DataFrame):
        from ..config import SERVER_RESULT_CACHE_ENABLED
        from . import sharing
        want_cache = bool(self.conf.get(SERVER_RESULT_CACHE_ENABLED.key))
        self._rc_lookup = want_cache
        if not want_cache and not sharing.inflight_on(self.conf):
            self.last_cache.setdefault("result", "off")
            return None
        from . import plancache
        if want_cache:
            # attach the fleet's shared persistent tier when configured
            # (idempotent per path; a read-through miss there is free)
            plancache.configure_result_store(self.conf)
        try:
            return plancache.result_key(df.plan, self.conf,
                                        encoded=self._encoded_plan(df))
        except plancache.Uncacheable as e:
            self.last_cache["result"] = f"uncacheable: {e.reason}"
            return None

    def _store_result(self, kd, result: pa.Table) -> pa.Table:
        if kd is not None:
            from .. import trace as qtrace
            from ..config import SERVER_RESULT_CACHE_MAX_BYTES
            from ..server import protocol
            from . import plancache
            key, digests = kd
            with qtrace.span("serializer.pack", kind="serializer") as sp:
                ipc = protocol.table_to_ipc(result)
                if sp is not None:
                    sp.attrs["bytes"] = len(ipc)
            # the server's reply body IS these bytes: publish them so a
            # cacheable miss serializes once, not once to store and once
            # to reply
            self.last_result_ipc = ipc
            execs = tuple(self.executed_exec_names())
            fell_back = tuple(self.fell_back())
            if self._rc_lookup:
                plancache.result_cache().put(
                    plancache.ResultEntry(
                        key=key, ipc=ipc, digests=digests,
                        execs=execs, fell_back=fell_back,
                        rows=result.num_rows),
                    max_bytes=int(
                        self.conf.get(SERVER_RESULT_CACHE_MAX_BYTES.key)))
            flight = self._sf_flight
            if flight is not None:
                # publish the same bytes to every parked duplicate
                self._sf_flight = None
                from . import sharing
                sharing.single_flight().complete(
                    flight, ipc, {"execs": list(execs),
                                  "fell_back": list(fell_back),
                                  "rows": result.num_rows})
        return result

    def collect(self, df: DataFrame, _prepared=None) -> pa.Table:
        """``_prepared`` lets a caller that already ran ``prepare(df)``
        (the plan server separates the bind phase from execution for
        its failure classification) hand the result in, so the planning
        pipeline runs once per query. With ``trace.enabled`` and no
        trace already active (the plan server opens its own around the
        whole request), this collect opens one — spans land in the
        process flight recorder and the conf'd JSONL sink."""
        from .. import trace as qtrace
        from ..config import TRACE_ENABLED
        if qtrace.active() or not self.conf.get(TRACE_ENABLED.key):
            return self._collect_inner(df, _prepared)
        from ..config import TRACE_MAX_SPANS, TRACE_SINK_PATH
        qid = qtrace.mint_query_id()
        with qtrace.query_trace(
                qid, component="session",
                max_spans=int(self.conf.get(TRACE_MAX_SPANS.key)),
                recorder=qtrace.flight_recorder(),
                sink_path=str(self.conf.get(TRACE_SINK_PATH.key))):
            return self._collect_inner(df, _prepared)

    def _collect_inner(self, df: DataFrame, _prepared=None) -> pa.Table:
        from .. import trace as qtrace
        state = self._rc_state
        if state is None or state[0] is not df:
            # in-process path: this collect opens the query (the server
            # calls try_cached_result itself, before prepare)
            hit = self.try_cached_result(df)
            if hit is not None:
                return hit
            state = self._rc_state
        self._rc_state = None
        kd = state[1]
        try:
            return self._execute_collect(df, kd, _prepared)
        except BaseException as e:
            # leader unwind: promote one parked duplicate (it
            # re-executes; the error is never served verbatim)
            self.abort_inflight(e)
            raise

    def _execute_collect(self, df: DataFrame, kd,
                         _prepared=None) -> pa.Table:
        from .. import trace as qtrace
        kind, plan = _prepared if _prepared is not None \
            else self.prepare(df)
        if kind == "exec":
            from . import sharing
            if sharing.subplan_on(self.conf):
                shared = self._apply_subplan_sharing(df)
                if shared is not None:
                    # re-plan the substituted tree; the subtree's
                    # serialized output now feeds a plain scan
                    df = shared
                    kind, plan = self.prepare(df)
        if kind == "interpret":
            with qtrace.span("interpret", kind="execute"):
                result = Interpreter(ansi=self.conf.ansi).execute(df.plan)
            return self._store_result(kd, result)
        if kind == "fallback":
            import time as _time
            t0 = _time.perf_counter_ns()
            with qtrace.span("cpuFallback", kind="execute"):
                result = plan.interpret()
            # CPU-topped plans feed the cost store too: a measured
            # host-side operator cost is exactly the comparison point
            # an offload-decision CBO needs against the device path
            self._note_costs(plan)
            self._note_query_wall("cpu", _time.perf_counter_ns() - t0)
            return self._store_result(kd, result)
        from ..exec.base import collect as collect_exec
        from ..memory.retry import apply_session_conf
        # install this session's retry/OOM-injection/oomDumpDir settings
        # (process-wide, like the reference's per-executor RmmSpark state);
        # the metric watermarks were taken at query open in _watermark()
        apply_session_conf(self.conf)
        try:
            import time as _time
            t0 = _time.perf_counter_ns()
            with qtrace.span("execute", kind="execute"):
                result = collect_exec(plan)
            self._note_costs(plan)
            self._note_query_wall("device", _time.perf_counter_ns() - t0)
            return self._store_result(kd, result)
        finally:
            plan.close()    # free catalog-registered exchange/broadcast state

    def _apply_subplan_sharing(self, df: DataFrame):
        """Subplan-level result sharing (docs/serving.md): find the
        first aggregate whose input is a linear project/filter chain
        over a single-sliced in-memory scan and swap that subtree for
        its (cached or freshly materialized) serialized output — two
        queries sharing a scan+filter but diverging at the aggregate
        execute the subtree once, across tenants. Conservatively
        limited to subtrees whose output carries no floating-point
        columns and whose default batching is one batch, so the
        substitution is bit-for-bit by construction (exact arithmetic,
        unchanged batch count feeding the aggregate). Returns the
        substituted DataFrame, or None when nothing qualifies."""
        import dataclasses
        from .. import trace as qtrace
        from ..types import TypeKind
        from . import logical as L
        from . import plancache, sharing

        def chain_ok(n) -> bool:
            hops = 0
            while isinstance(n, (L.LogicalProject, L.LogicalFilter)):
                hops += 1
                n = n.children[0]
            return hops > 0 and isinstance(n, L.LogicalScan) and \
                n.data is not None and n.num_slices == 1 and \
                n.batch_rows is None

        target = None

        def find(n):
            nonlocal target
            if target is not None:
                return
            if isinstance(n, L.LogicalAggregate) and \
                    chain_ok(n.children[0]):
                target = n
                return
            for c in n.children:
                find(c)

        find(df.plan)
        if target is None:
            return None
        child = target.children[0]
        try:
            schema = child.schema()
            if any(f.dtype.kind in (TypeKind.FLOAT32, TypeKind.FLOAT64)
                   for f in schema.fields):
                return None
            key, digests = plancache.subtree_result_key(child, self.conf)
        except Exception:
            return None     # unbindable/unencodable subtree: no sharing
        from ..config import SHARING_SUBPLAN_MAX_BYTES
        from ..server import protocol
        cache = sharing.subplan_cache()
        with qtrace.span("sharing.subplan", kind="cache") as sp:
            entry = cache.get(key)
            if entry is not None:
                sharing.metrics().note("subplan_hits")
                self.last_cache["subplan"] = "hit"
                ipc = entry.ipc
            else:
                # materialize the subtree once (inside the caller's
                # already-admitted region) and publish its bytes
                sub = self._materialize_subtree(child)
                ipc = protocol.table_to_ipc(sub)
                cache.put(key, ipc, digests, rows=sub.num_rows,
                          max_bytes=int(self.conf.get(
                              SHARING_SUBPLAN_MAX_BYTES.key)))
                sharing.metrics().note("subplan_stores")
                self.last_cache["subplan"] = "store"
            if sp is not None:
                sp.attrs["outcome"] = self.last_cache["subplan"]
                sp.attrs["bytes"] = len(ipc)
        # hit and store both re-decode the SAME bytes, so the scan the
        # aggregate sees is identical on every query that shares the key
        table = protocol.ipc_to_table(ipc)
        plancache.register_digest(table, plancache.digest_ipc(ipc))
        new_child = L.LogicalScan((), data=table, _schema=schema)

        def swap(n):
            if n is child:
                return new_child
            if not n.children:
                return n
            ch = tuple(swap(c) for c in n.children)
            if all(a is b for a, b in zip(ch, n.children)):
                return n
            return dataclasses.replace(n, children=ch)

        return DataFrame(swap(df.plan))

    def _materialize_subtree(self, plan) -> pa.Table:
        from ..exec.base import collect as collect_exec
        from ..memory.retry import apply_session_conf
        sub = Overrides(self.conf).plan(plan)
        if isinstance(sub, CpuFallbackExec):
            return sub.interpret()
        apply_session_conf(self.conf)
        try:
            return collect_exec(sub)
        finally:
            sub.close()

    def _note_costs(self, plan) -> None:
        """Fold the executed plan's per-operator metrics into the
        observed-cost store under the query's shape fingerprint — the
        measured feed AQE/CBO re-planning consumes. Requires a
        fingerprint (plan cache on + cacheable shape) to key on."""
        from ..config import (TRACE_COST_STORE_ALPHA,
                              TRACE_COST_STORE_ENABLED)
        if self.last_fingerprint is None or \
                not self.conf.get(TRACE_COST_STORE_ENABLED.key):
            return
        if self._cached_serve is not None:
            # result-cache hit: NOTHING executed, so there is no
            # measurement — a verbatim cached reply must not drag the
            # per-operator wall EWMAs toward zero for this fingerprint
            return
        from .. import trace as qtrace
        qtrace.note_operator_costs(
            self.last_fingerprint, plan,
            alpha=float(self.conf.get(TRACE_COST_STORE_ALPHA.key)))

    def _note_query_wall(self, path: str, wall_ns: int) -> None:
        """Whole-query wall observation under the synthetic query:device
        / query:cpu cost-store operator — the apples-to-apples feed
        cost-fed planning (plan/adaptive.py) compares. Cached serves
        never reach here (try_cached_result returns before execution)."""
        if self._cached_serve is not None:
            return
        from . import adaptive
        adaptive.note_query_wall(self.conf, self.last_fingerprint,
                                 path, wall_ns)

    def _lower_to_mesh(self, plan):
        """ICI shuffle mode asked for the mesh data plane: the fused mesh
        stage, or None with the reason kept in ``last_mesh_giveway`` — the
        host-mediated exchange then runs, and ``executed_exec_names()`` /
        ``explain()`` say that it did and why."""
        from ..parallel.lowering import MeshUnsupported, lower_to_mesh
        try:
            return lower_to_mesh(plan, self._mesh())
        except MeshUnsupported as e:
            self.last_mesh_giveway = str(e) or type(e).__name__
            return None

    def _mesh(self):
        """1-axis data-parallel mesh over the visible devices."""
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from ..config import MESH_DEVICES
        n = self.conf.get(MESH_DEVICES.key) or len(jax.devices())
        return Mesh(np.array(jax.devices()[:n]), ("data",))

    def cache(self, df: DataFrame) -> DataFrame:
        """Materialize as parquet-compressed cached partitions (reference:
        ParquetCachedBatchSerializer behind df.cache())."""
        from ..config import FILECACHE_ENABLED
        if not self.conf.get(FILECACHE_ENABLED.key):
            return df      # caching disabled: keep the logical plan as-is
        from ..io.cache import CachedRelation
        from .logical import LogicalScan
        from .overrides import Overrides
        plan = Overrides(self.conf).plan(df.plan)
        cached = CachedRelation.build(plan)
        return DataFrame(LogicalScan((), source=cached,
                                     _schema=cached.schema))

    def write(self, df: DataFrame, path: str, format: str = "parquet",
              partition_by=None, bucket_by=None, compression="snappy",
              header: bool = True):
        """Execute and write TASK-BY-TASK — each plan partition streams
        its batches into its own part files; no driver-side collect
        (reference: GpuInsertIntoHadoopFsRelationCommand +
        GpuFileFormatDataWriter). ``bucket_by=(cols, n)`` routes rows with
        the shuffle's bit-exact murmur3-pmod. Returns WriteStats."""
        from ..io.writer import write_plan
        plan = self._physical_plan(df)
        return write_plan(plan, path, fmt=format,
                          compression=compression,
                          partition_by=partition_by or (),
                          bucket_by=bucket_by, header=header)

    def _physical_plan(self, df: DataFrame):
        if not self.conf.sql_enabled:
            from ..exec import InMemoryScanExec
            return InMemoryScanExec(
                Interpreter(ansi=self.conf.ansi).execute(df.plan))
        plan = Overrides(self.conf).plan(df.plan)
        self.last_plan = plan
        return plan

    def write_parquet(self, df: DataFrame, path: str,
                      partition_by=None, **kw):
        return self.write(df, path, "parquet",
                          partition_by=partition_by, **kw)

    def write_csv(self, df: DataFrame, path: str, **kw):
        return self.write(df, path, "csv", **kw)

    def write_orc(self, df: DataFrame, path: str, **kw):
        return self.write(df, path, "orc", **kw)

    def write_delta(self, df: DataFrame, path: str, mode: str = "append",
                    **kw):
        from ..io.delta import DeltaTable
        return DeltaTable.write(path, self.collect(df), mode=mode, **kw)

    def explain(self, df: DataFrame,
                mode: ExplainMode = ExplainMode.ALL) -> str:
        text = Overrides(self.conf).explain(df.plan, mode)
        # explain() tags and converts no exec (converting may still run a
        # build side: dynamic partition pruning); a give-way is a fact of
        # the last collect, reported as such
        if self.last_mesh_giveway is not None:
            text += ("\nlast collect: mesh lowering gave way to the "
                     f"host-mediated exchange: {self.last_mesh_giveway}")
        return text

    # ---- plan capture assertions (test support) ----
    def metrics(self) -> dict:
        """Aggregated operator metrics of the last executed plan, filtered
        by spark.rapids.tpu.sql.metrics.level (reference: the SQLMetrics
        the plugin posts to the Spark UI)."""
        if self.last_plan is None and self._cached_serve is None:
            return {}
        out = {}
        if self.last_plan is not None:
            from ..config import METRICS_LEVEL
            from ..exec.base import DEBUG, ESSENTIAL, MODERATE
            level = {"ESSENTIAL": ESSENTIAL, "MODERATE": MODERATE,
                     "DEBUG": DEBUG}.get(
                str(self.conf.get(METRICS_LEVEL.key)).upper(), MODERATE)
            out = self.last_plan.collect_metrics(level)
        from ..exec.python_exec import _python_semaphore
        # delta since this session's last collect — the semaphore counter
        # is process-global
        wait = _python_semaphore.wait_time_ns - \
            getattr(self, "_sem_wait0", _python_semaphore.wait_time_ns)
        if wait > 0:
            out["python.semaphoreWaitTime"] = wait
        # retry state machine counters since this session's last collect
        # (retryCount / splitAndRetryCount / retryBlockTime / spill bytes
        # the recovery forced) — the GpuTaskMetrics roll-up twin
        def emit_deltas(prefix: str, snap: dict, base) -> None:
            # process-wide counters report as deltas since this
            # session's last collect watermark (None = never collected)
            if base is None:
                return
            for k, v in snap.items():
                delta = v - base.get(k, 0)
                if delta > 0:
                    out[f"{prefix}.{k}"] = delta

        from ..memory.retry import metrics as _retry_metrics
        emit_deltas("retry", _retry_metrics().snapshot(),
                    getattr(self, "_retry0", None))
        # transport fetch-retry counters (fetchRetryCount /
        # fetchBackoffTime / corruptFrameCount / peerFailoverCount) ride
        # the same delta-since-last-collect shape
        from ..shuffle.transport import transport_metrics
        emit_deltas("net", transport_metrics().snapshot(),
                    getattr(self, "_net0", None))
        # query-recovery counters (recomputeCount / recomputedPartitions
        # / replicaBytes / lineageMissCount): the lineage plane's answer
        # to "did this query survive a lost executor, and how"
        from ..shuffle.lineage import metrics as _lineage_metrics
        emit_deltas("lineage", _lineage_metrics().snapshot(),
                    getattr(self, "_lineage0", None))
        # serving-cache counters (plan/result hit/miss/eviction/
        # invalidation) since this session's last collect opened
        from . import plancache
        emit_deltas("cache", plancache.metrics().snapshot(),
                    getattr(self, "_cache0", None))
        # query-tracing counters (spans recorded/dropped, profiles,
        # slow queries, cost observations) — the observability plane's
        # own cost is itself observable
        from .. import trace as qtrace
        emit_deltas("trace", qtrace.metrics().snapshot(),
                    getattr(self, "_trace0", None))
        # adaptive-execution counters (cost-fed plans, exploration runs,
        # runtime re-plans: coalesces / skew splits / broadcast switches)
        from . import adaptive
        emit_deltas("adaptive", adaptive.metrics().snapshot(),
                    getattr(self, "_adaptive0", None))
        # cross-query work-sharing counters (in-flight dedup waits/
        # serves/promotions, subplan hits, scan-share uploads ridden)
        from . import sharing
        emit_deltas("sharing", sharing.metrics().snapshot(),
                    getattr(self, "_sharing0", None))
        return out

    def executed_exec_names(self) -> List[str]:
        if self._cached_serve is not None:
            # cached serve: nothing executed; report the plan-capture
            # surface of the run the entry was stored from
            return list(self._cached_serve[0])
        names = []

        def walk(e):
            if getattr(e, "stood_aside", False):
                # an exchange is named only where it ran
                return walk(e.aside)
            names.append(e.name)
            for c in e.children:
                walk(c)
            # exchanges / fallback islands keep their own child refs
            for extra in getattr(e, "child_execs", []):
                walk(extra)

        if self.last_plan is not None:
            walk(self.last_plan)
        if self.last_mesh_giveway is not None:
            names.append(f"MeshGiveWay[{self.last_mesh_giveway}]")
        return names

    def fell_back(self) -> List[str]:
        if self._cached_serve is not None:
            return list(self._cached_serve[1])
        return [n for n in self.executed_exec_names()
                if n.startswith("CpuFallback")]

    def adaptive_decisions(self) -> List[str]:
        """Reason tags of every adaptive decision taken since this
        session's last query opened (cost-fed placement, exploration,
        runtime coalesce/skew-split/broadcast-switch) — the never-silent
        surface the plan server forwards in its reply. Same
        process-ring-plus-watermark contract as dict_fallbacks()."""
        from . import adaptive
        return adaptive.reasons(since=getattr(self, "_adaptive_mark0", 0))

    def dict_fallbacks(self) -> List[str]:
        """willNotWork-style reason tags recorded when a dictionary-encoded
        scan column fell back to the padded byte-matrix path (cardinality
        over threshold, conf off, null dictionary entries) SINCE this
        session was created. Runtime companion to the plan-time
        will_not_work reasons — same contract as the window over-capacity
        tag: the fallback NEVER happens silently."""
        from ..dictenc import fallback_reasons
        return fallback_reasons(since=self._dict_fb_mark)
