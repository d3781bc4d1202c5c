"""Serving-tier caches: plan-shape fingerprinting, planning memoization,
and a byte-budgeted result-set cache.

The multi-tenant story ("Accelerating Presto with GPUs", PAPERS.md): a GPU
engine under a production frontend wins by amortizing planning and
compilation across tenants and serving repeated query shapes from caches,
not by making any single query faster. Three layers, from cheapest to
most aggressive:

1. **Fingerprints** — a canonical hash over the plandoc wire dialect
   (server/plandoc.py), so the in-process API and the plan server share
   one definition. The *shape* fingerprint parameterizes literals under
   value-insensitive parents (``filter(x > ?)`` shapes collide by
   design) and folds in-memory scans down to their capacity buckets
   (batch.bucket_capacity) — the same buckets that make XLA programs
   reusable, so plans that share a shape fingerprint also share compiled
   kernels. The *result* key keeps literal values and replaces each scan
   with a content digest of its table.

2. **Planning cache** — memoizes the expensive planner walks per
   (shape fingerprint, planning-relevant conf): the tag()/CBO outcome
   (per-node willNotWork reasons, positionally replayed onto the
   isomorphic fresh tree) plus the fusion/mesh-lowering eligibility
   decision. Physical execs are REBUILT per query from the cached
   decisions — exec trees are stateful (metrics, exchange/broadcast
   catalog state, close()) and must never be shared between collects,
   so the cache stores decisions, not live operators.

3. **Result cache** — conf-gated LRU over serialized Arrow results,
   keyed on (literal-inclusive fingerprint, per-table content digests,
   result-relevant conf), byte-budgeted, invalidated when a table is
   dropped or re-uploaded. Keys include content digests, so serving a
   stale result for replaced data is structurally impossible; explicit
   invalidation just frees the budget eagerly.

Safety rules (documented in docs/serving.md):

- Literal values are parameterized ONLY under parents whose planning is
  value-insensitive (comparisons, arithmetic, boolean algebra,
  conditionals). Regex patterns, format strings, json paths etc. keep
  their values in the shape fingerprint — their tag decisions read the
  value.
- Window-without-PARTITION-BY capacity gating compares an exact row
  estimate against batchRowCapacity; the gate's boolean outcome is mixed
  into the shape fingerprint so bucketed row counts cannot smuggle an
  over-capacity input past a cached "fits on device" decision.
- File-backed scans fingerprint (path, mtime_ns, size) per file in BOTH
  key modes: a rewritten file changes its stats, which changes the
  result key, so the old entry is unreachable — stat-change
  invalidation. Sources without statable concrete paths stay loudly
  result-uncacheable.
- Plans the wire dialect cannot encode are uncacheable; the reason is
  recorded, never silent.
"""

from __future__ import annotations

import hashlib
import json
import threading
import weakref
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import pyarrow as pa

from ..batch import bucket_capacity, schema_from_arrow
from ..config import RapidsTpuConf
from . import logical as L

# ---------------------------------------------------------------------------
# metrics (process-wide; sessions report deltas between snapshots, the
# retry/net counter idiom)
# ---------------------------------------------------------------------------


class ServingMetrics:
    def __init__(self):
        self._lock = threading.Lock()
        self.plan_hits = 0
        self.plan_misses = 0
        self.plan_evictions = 0
        self.result_hits = 0
        self.result_misses = 0
        self.result_evictions = 0
        self.result_invalidations = 0
        # persistent tier (the fleet's shared disk store): a store hit is
        # a REHYDRATION — a result served from disk that this process's
        # memory tier had never seen (worker restart, or a sibling
        # worker computed it)
        self.store_hits = 0
        self.store_writes = 0
        self.store_evictions = 0
        self.store_invalidations = 0

    def note(self, name: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + n)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {
                "planCacheHitCount": self.plan_hits,
                "planCacheMissCount": self.plan_misses,
                "planCacheEvictionCount": self.plan_evictions,
                "resultCacheHitCount": self.result_hits,
                "resultCacheMissCount": self.result_misses,
                "resultCacheEvictionCount": self.result_evictions,
                "resultCacheInvalidationCount": self.result_invalidations,
                "resultStoreHitCount": self.store_hits,
                "resultStoreWriteCount": self.store_writes,
                "resultStoreEvictionCount": self.store_evictions,
                "resultStoreInvalidationCount": self.store_invalidations,
            }


_METRICS = ServingMetrics()


def metrics() -> ServingMetrics:
    return _METRICS


# ---------------------------------------------------------------------------
# table content digests
# ---------------------------------------------------------------------------

#: id(table) -> (weakref keeping the memo honest, digest). pa.Tables are
#: immutable, so a digest is valid for the object's lifetime; the weakref
#: callback retires the id before CPython can reuse it.
_DIGESTS: Dict[int, Tuple[weakref.ref, str]] = {}
#: re-entrant: a collection can start while this thread holds the lock, and
#: a dead table's ``_gone`` callback then takes it again on the same thread
_DIG_LOCK = threading.RLock()


def register_digest(table: pa.Table, digest: str) -> None:
    """Prime the digest memo (the plan server hashes the Arrow IPC body
    it already holds at table upload, so queries never re-hash)."""
    tid = id(table)

    def _gone(_ref, _tid=tid):
        with _DIG_LOCK:
            _DIGESTS.pop(_tid, None)

    with _DIG_LOCK:
        _DIGESTS[tid] = (weakref.ref(table, _gone), digest)


def content_digest(table: pa.Table) -> str:
    """Content hash of a pyarrow table, memoized per live object (one
    O(bytes) pass per distinct table, amortized across queries)."""
    with _DIG_LOCK:
        hit = _DIGESTS.get(id(table))
        if hit is not None and hit[0]() is table:
            return hit[1]
    from ..server import protocol
    digest = hashlib.blake2b(protocol.table_to_ipc(table),
                             digest_size=16).hexdigest()
    register_digest(table, digest)
    return digest


def digest_ipc(body: bytes) -> str:
    """Digest of a table shipped as Arrow IPC bytes (the upload seam)."""
    return hashlib.blake2b(body, digest_size=16).hexdigest()


# ---------------------------------------------------------------------------
# canonicalization
# ---------------------------------------------------------------------------

#: literal values under these parents never change a tagging decision —
#: tag() reads only their dtype (which stays in the fingerprint). Every
#: other parent (regex, format strings, json paths, repeat counts, ...)
#: keeps the value in the shape fingerprint: plan decisions may read it.
_VALUE_INSENSITIVE_PARENTS = frozenset({
    "EqualTo", "EqualNullSafe", "LessThan", "LessThanOrEqual",
    "GreaterThan", "GreaterThanOrEqual",
    "Add", "Subtract", "Multiply", "Divide", "IntegralDivide",
    "Remainder", "Pmod", "UnaryMinus", "Abs",
    "And", "Or", "Not",
    "If", "CaseWhen", "Coalesce", "LeastGreatest",
})

#: conf keys that cannot change a *plan*: serving-tier knobs (incl. the
#: cache confs themselves; excluded by prefix inline in
#: conf_fingerprint), test fault injection, metrics verbosity, and
#: diagnostic paths. Everything else the user set participates in the
#: fingerprint — over-keying only costs hit rate, never correctness.
_PLAN_CONF_EXCLUDED_KEYS = frozenset({
    "spark.rapids.tpu.sql.metrics.level",
    "spark.rapids.tpu.memory.oomDumpDir",
})


def conf_fingerprint(conf: RapidsTpuConf,
                     for_result: bool = False) -> List[Tuple[str, str]]:
    """Sorted explicit settings that can influence planning (or, with
    ``for_result``, the result bytes — test-injection confs stay in that
    key out of caution even though retries are bit-for-bit)."""
    out = []
    for k, v in conf._settings.items():
        if k.startswith("spark.rapids.tpu.server.") or \
                k in _PLAN_CONF_EXCLUDED_KEYS:
            continue
        if not for_result and k.startswith("spark.rapids.tpu.test."):
            continue
        out.append((k, str(v)))
    return sorted(out)


class Uncacheable(Exception):
    """The plan cannot participate in a cache layer; .reason says why."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def _file_stats(paths) -> List[Tuple[str, int, int]]:
    import os
    out = []
    for p in paths:
        try:
            st = os.stat(p)
            out.append((str(p), st.st_mtime_ns, st.st_size))
        except OSError:
            out.append((str(p), -1, -1))
    return out


def _walk_doc(doc, parent: Optional[str], tables, mode: str):
    """Rewrite a plandoc tree into canonical form. mode='shape'
    parameterizes literals and buckets scans; mode='result' keeps
    literal values and swaps scans for content digests."""
    if isinstance(doc, list):
        return [_walk_doc(x, parent, tables, mode) for x in doc]
    if not isinstance(doc, dict):
        return doc
    if "$e" in doc:
        name, args = doc["$e"][0], doc["$e"][1:]
        if name == "Literal" and mode == "shape" and \
                parent in _VALUE_INSENSITIVE_PARENTS:
            # value out, dtype stays: filter(x > ?) shapes collide
            return {"$e": ["Literal", {"$param": 1},
                           _walk_doc(args[1], name, tables, mode)]}
        return {"$e": [name]
                + [_walk_doc(a, name, tables, mode) for a in args]}
    if "$p" in doc:
        payload = doc["$p"]
        node = {"$p": [payload[0],
                       [_walk_doc(c, None, tables, mode)
                        for c in payload[1]]]
                + [_walk_doc(a, None, tables, mode)
                   for a in payload[2:]]}
        for k, v in doc.items():
            if k == "$p":
                continue
            if k == "table":
                t = tables[v]
                if mode == "shape":
                    # the capacity bucket IS the compile-cache key: plans
                    # whose scans bucket identically share XLA programs
                    node["scan_shape"] = [
                        bucket_capacity(max(1, t.num_rows)),
                        bucket_capacity(max(1, t.nbytes)),
                        _enc(schema_from_arrow(t.schema))]
                else:
                    node["scan_digest"] = content_digest(t)
                continue
            if k == "source":
                stats = _file_stats(v.get("paths", ()))
                if mode == "result" and (
                        not stats or any(s[1] < 0 for s in stats)):
                    # no concrete statable paths → no stand-in for a
                    # content digest; stay loudly uncacheable rather
                    # than risk serving a stale result
                    raise Uncacheable(
                        "file-backed scan without statable paths")
                node["source"] = _walk_doc(v, None, tables, mode)
                node["source_stat"] = stats
                continue
            node[k] = _walk_doc(v, None, tables, mode)
        return node
    return {k: _walk_doc(v, parent, tables, mode) for k, v in doc.items()}


def _enc(v):
    from ..server.plandoc import encode_value
    return encode_value(v)


def _window_overcap_bits(plan: L.LogicalPlan,
                         conf: RapidsTpuConf) -> List[int]:
    """Exact plan-time gate outcomes that bucketed row counts cannot
    stand in for: the unpartitioned-window capacity check compares an
    exact estimate to batchRowCapacity, and a cached 'fits on device'
    replayed onto a bigger same-bucket input would crash at execution."""
    from ..expressions.base import Alias
    from .overrides import estimate_rows
    bits: List[int] = []

    def walk(n: L.LogicalPlan):
        if isinstance(n, L.LogicalWindow):
            from ..expressions.window import WindowExpression
            unpartitioned = False
            for e in n.window_exprs:
                w = e.child if isinstance(e, Alias) else e
                if isinstance(w, WindowExpression) and \
                        not w.spec.partition_keys:
                    unpartitioned = True
            if unpartitioned:
                est = estimate_rows(n.children[0])
                cap = conf.batch_row_capacity
                bits.append(int(est is not None and est > cap))
        for c in n.children:
            walk(c)

    walk(plan)
    return bits


def _host_only_data_bits(plan: L.LogicalPlan) -> List[int]:
    """Data-dependent placement gates bucketed scan shapes cannot stand
    in for: whether an in-memory scan's arrays carry null elements
    (overrides.scan_host_only_reason forces a whole-plan CPU fallback).
    Without this bit, a same-bucket clean table could replay a cached
    all-CPU placement — or worse, a cached device placement would crash
    at the H2D boundary of a null-element input."""
    from .overrides import scan_host_only_reason
    bits: List[int] = []

    def walk(n: L.LogicalPlan):
        if isinstance(n, L.LogicalScan) and n.data is not None:
            bits.append(int(scan_host_only_reason(n.data) is not None))
        for c in n.children:
            walk(c)

    walk(plan)
    return bits


def _hash(payload) -> str:
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"),
                      default=str)
    return hashlib.blake2b(blob.encode("utf-8"),
                           digest_size=16).hexdigest()


def encode_plan(plan: L.LogicalPlan):
    """One shared plandoc encoding per query: both fingerprints
    canonicalize the same (doc, tables) pair, so callers that need both
    (Session) encode once. Raises Uncacheable for plans the wire
    dialect cannot encode."""
    from ..server.plandoc import PlanDecodeError, plan_to_doc
    try:
        return plan_to_doc(plan)
    except PlanDecodeError as e:
        raise Uncacheable(f"plan has no wire encoding: {e}")


def shape_fingerprint(plan: L.LogicalPlan, conf: RapidsTpuConf,
                      encoded=None) -> str:
    """Canonical hash of (parameterized plan structure, schemas, capacity
    buckets, planning-relevant conf). Raises Uncacheable for plans the
    wire dialect cannot encode. ``encoded`` reuses a prior
    encode_plan(plan) result."""
    doc, tables = encoded if encoded is not None else encode_plan(plan)
    shape = _walk_doc(doc, None, tables, "shape")
    payload = {"v": 1, "plan": shape,
               "overcap": _window_overcap_bits(plan, conf),
               "hostonly": _host_only_data_bits(plan),
               "conf": conf_fingerprint(conf)}
    from .cbo import CBO_ENABLED
    if conf.get(CBO_ENABLED.key):
        # the CBO cost gate reads EXACT row counts (cbo.estimated_rows),
        # so with it enabled a bucketed fingerprint could replay a
        # placement decided for a much smaller same-bucket input; key on
        # the exact counts instead (placement stays fresh, hit rate
        # narrows — correctness never depended on this, placement did)
        payload["cbo_rows"] = [
            int(t.num_rows) for t in tables.values()]
    return _hash(payload)


def result_key(plan: L.LogicalPlan, conf: RapidsTpuConf,
               encoded=None) -> Tuple[str, Tuple[str, ...]]:
    """(cache key, table digests the entry depends on). In-memory scans
    key on content digests; file-backed scans key on per-file
    (path, mtime_ns, size) stats (raises Uncacheable only when a source
    has no statable concrete paths). ``encoded`` reuses a prior
    encode_plan(plan) result."""
    doc, tables = encoded if encoded is not None else encode_plan(plan)
    return _result_key_parts(doc, tables, conf, "1")


def result_key_doc(doc: dict, tables: Dict[str, pa.Table],
                   conf: RapidsTpuConf) -> Tuple[str, Tuple[str, ...]]:
    """The SAME result key ``result_key`` computes, taken straight from
    a wire plandoc — the router's in-flight dedup keys on it without
    building a Session, so duplicates collapse before any worker
    dispatch regardless of ring placement."""
    return _result_key_parts(doc, tables, conf, "1")


def subtree_result_key(plan: L.LogicalPlan, conf: RapidsTpuConf
                       ) -> Tuple[str, Tuple[str, ...]]:
    """result_key for an interior subtree — the subplan-share key
    (docs/serving.md "Cross-query work sharing"). Versioned under its
    own namespace so a subtree's serialized output can never collide
    with a whole-query result entry for an identical plan."""
    doc, tables = encode_plan(plan)
    return _result_key_parts(doc, tables, conf, "subplan1")


def _result_key_parts(doc, tables, conf: RapidsTpuConf,
                      version: str) -> Tuple[str, Tuple[str, ...]]:
    full = _walk_doc(doc, None, tables, "result")
    digests = tuple(sorted({content_digest(t) for t in tables.values()}))
    key = _hash({"v": version, "plan": full,
                 "conf": conf_fingerprint(conf, for_result=True)})
    return key, digests


# ---------------------------------------------------------------------------
# planning cache
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PlanDecisions:
    """What the planner decided, detached from any live exec objects."""

    #: preorder (node-count-guarded) willNotWork reasons after tag + CBO
    reasons: Tuple[Tuple[str, ...], ...]
    #: try_fuse_exec produced a fused stage for this shape
    fuse_eligible: bool = False
    #: lower_to_mesh produced a mesh program for this shape
    mesh_eligible: bool = False


def collect_reasons(meta) -> Tuple[Tuple[str, ...], ...]:
    out: List[Tuple[str, ...]] = []

    def walk(m):
        out.append(tuple(m.reasons))
        for c in m.children:
            walk(c)

    walk(meta)
    return tuple(out)


def apply_reasons(meta, reasons: Tuple[Tuple[str, ...], ...]) -> bool:
    """Replay cached tag/CBO outcomes onto an isomorphic fresh meta tree.
    Returns False on a node-count mismatch (fingerprint collision guard)
    so the caller replans from scratch."""
    nodes = []

    def walk(m):
        nodes.append(m)
        for c in m.children:
            walk(c)

    walk(meta)
    if len(nodes) != len(reasons):
        return False
    for m, rs in zip(nodes, reasons):
        m.reasons = list(rs)
    return True


class PlanningCache:
    """LRU over PlanDecisions, keyed by shape fingerprint."""

    def __init__(self, max_entries: int = 256):
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, PlanDecisions]" = OrderedDict()
        self.max_entries = max_entries

    def get(self, key: str) -> Optional[PlanDecisions]:
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
            return e

    def put(self, key: str, decisions: PlanDecisions,
            max_entries: Optional[int] = None) -> None:
        with self._lock:
            if max_entries is not None:
                self.max_entries = max_entries
            self._entries[key] = decisions
            self._entries.move_to_end(key)
            while len(self._entries) > max(1, self.max_entries):
                self._entries.popitem(last=False)
                _METRICS.note("plan_evictions")

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self):
        with self._lock:
            return len(self._entries)


# ---------------------------------------------------------------------------
# result cache
# ---------------------------------------------------------------------------


@dataclass
class ResultEntry:
    key: str
    ipc: bytes                       # Arrow IPC stream, served verbatim
    digests: Tuple[str, ...]         # tables this result depends on
    execs: Tuple[str, ...] = ()      # plan-capture surface of the run
    fell_back: Tuple[str, ...] = ()
    rows: int = 0
    hits: int = 0


class ResultCache:
    """Byte-budgeted LRU over serialized results. Keys carry content
    digests, so a stale serve is impossible by construction; explicit
    invalidation (drop_table / re-upload) frees budget eagerly and is
    the count the server acks back.

    When a ``persistent`` tier (resultstore.PersistentResultStore) is
    attached — the serving fleet's shared disk store — gets read
    through to it on a memory miss (rehydration after a worker
    restart), puts write through, and invalidation covers both tiers so
    the drop_table ack is authoritative fleet-wide."""

    def __init__(self, max_bytes: int = 256 << 20):
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, ResultEntry]" = OrderedDict()
        self.max_bytes = max_bytes
        self.used_bytes = 0
        self.persistent = None       # Optional[PersistentResultStore]

    def get(self, key: str) -> Optional[ResultEntry]:
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                e.hits += 1
                self._entries.move_to_end(key)
        if e is None and self.persistent is not None:
            loaded = self.persistent.get(key)
            if loaded is not None:
                e = ResultEntry(key=key, ipc=loaded["ipc"],
                                digests=loaded["digests"],
                                execs=loaded["execs"],
                                fell_back=loaded["fell_back"],
                                rows=loaded["rows"], hits=1)
                _METRICS.note("store_hits")
                # promote into the memory LRU (no write-through: the
                # bytes came FROM the store)
                self._put_memory(e)
        return e

    def put(self, entry: ResultEntry,
            max_bytes: Optional[int] = None) -> bool:
        """Insert (idempotent per key); False when the entry alone
        exceeds the memory budget and was not stored there (the
        persistent tier, with its own budget, is still written)."""
        if self.persistent is not None:
            if self.persistent.put(entry.key, entry.ipc, entry.digests,
                                   execs=entry.execs,
                                   fell_back=entry.fell_back,
                                   rows=entry.rows):
                _METRICS.note("store_writes")
        return self._put_memory(entry, max_bytes)

    def _put_memory(self, entry: ResultEntry,
                    max_bytes: Optional[int] = None) -> bool:
        with self._lock:
            if max_bytes is not None:
                self.max_bytes = max_bytes
            size = len(entry.ipc)
            if size > self.max_bytes:
                return False
            old = self._entries.pop(entry.key, None)
            if old is not None:
                self.used_bytes -= len(old.ipc)
            self._entries[entry.key] = entry
            self.used_bytes += size
            while self.used_bytes > self.max_bytes and self._entries:
                k, victim = self._entries.popitem(last=False)
                if k == entry.key:     # never evict what we just stored
                    self._entries[k] = victim
                    self._entries.move_to_end(k, last=False)
                    break
                self.used_bytes -= len(victim.ipc)
                _METRICS.note("result_evictions")
            return True

    def invalidate_digest(self, digest: str) -> int:
        """Drop every entry depending on ``digest`` from BOTH tiers;
        returns the combined count (the drop_table ack surface — with a
        persistent tier attached the ack is authoritative across worker
        restarts, not just this process's memory). Fan-out across a
        fleet stays additive: file deletion is idempotent, so the
        second worker reached finds the store already clean and its ack
        counts only its own memory entries."""
        with self._lock:
            dead = [k for k, e in self._entries.items()
                    if digest in e.digests]
            for k in dead:
                self.used_bytes -= len(self._entries.pop(k).ipc)
            if dead:
                _METRICS.note("result_invalidations", len(dead))
        persisted = 0
        if self.persistent is not None:
            persisted = self.persistent.invalidate_digest(digest)
            if persisted:
                _METRICS.note("store_invalidations", persisted)
        return len(dead) + persisted

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.used_bytes = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            out = {"entries": len(self._entries),
                   "usedBytes": self.used_bytes,
                   "maxBytes": self.max_bytes}
        if self.persistent is not None:
            out["persistent"] = self.persistent.stats()
        return out

    def __len__(self):
        with self._lock:
            return len(self._entries)


# ---------------------------------------------------------------------------
# process-wide singletons (the catalog/semaphore idiom)
# ---------------------------------------------------------------------------

_PLAN_CACHE: Optional[PlanningCache] = None
_RESULT_CACHE: Optional[ResultCache] = None
_SINGLETON_LOCK = threading.Lock()


def planning_cache() -> PlanningCache:
    global _PLAN_CACHE
    with _SINGLETON_LOCK:
        if _PLAN_CACHE is None:
            _PLAN_CACHE = PlanningCache()
        return _PLAN_CACHE


def result_cache() -> ResultCache:
    global _RESULT_CACHE
    with _SINGLETON_LOCK:
        if _RESULT_CACHE is None:
            _RESULT_CACHE = ResultCache()
        return _RESULT_CACHE


#: set the moment a PlanServer configures the store (even to "off"):
#: in a serving process the store is INFRASTRUCTURE, owned by the
#: server's startup conf — a remote client's hello/plan conf, which the
#: server merges into every Session, must never attach, repoint, or
#: re-budget the fleet's shared tier (it would detach every tenant's
#: cache and write files to a client-chosen path on the server host)
_STORE_LOCKED = False


def configure_result_store(conf: RapidsTpuConf, _server: bool = False):
    """Attach the shared persistent result tier per the
    ``server.fleet.resultStore.*`` confs. Attach-only, first-wins
    semantics: the plan server's startup call (``_server=True``) is
    authoritative and locks the process; a per-Session call attaches
    only when the process is unlocked and nothing is attached yet (the
    in-process, no-server use). Re-calling with the attached path is a
    no-op; detaching at runtime is deliberate API
    (``result_cache().persistent = None``), not a conf flip."""
    from ..config import (FLEET_RESULT_STORE_MAX_BYTES,
                          FLEET_RESULT_STORE_PATH)
    global _STORE_LOCKED
    if not _server:
        # per-query fast paths — no global lock, no conf parse: (a)
        # the process is server-locked or a store is already attached
        # (both terminal for session-level calls); (b) the session
        # never SET the path conf (the default), so there is nothing
        # to attach
        cache = _RESULT_CACHE
        if _STORE_LOCKED or (cache is not None
                             and cache.persistent is not None):
            return cache.persistent if cache is not None else None
        if FLEET_RESULT_STORE_PATH.key not in conf._settings:
            return None
    path = str(conf.get(FLEET_RESULT_STORE_PATH.key) or "").strip()
    max_bytes = int(conf.get(FLEET_RESULT_STORE_MAX_BYTES.key))
    cache = result_cache()
    from .resultstore import PersistentResultStore
    with _SINGLETON_LOCK:
        store = cache.persistent
        if _server:
            _STORE_LOCKED = True
            if not path:
                # the server's startup conf is authoritative INCLUDING
                # "off": an embedded server started without the tier
                # must not keep serving a predecessor's store
                cache.persistent = None
            elif store is None or store.path != path:
                cache.persistent = PersistentResultStore(
                    path, max_bytes,
                    on_evict=lambda n: _METRICS.note(
                        "store_evictions", n))
            else:
                store.max_bytes = max_bytes
            return cache.persistent
        if not path or _STORE_LOCKED or store is not None:
            return store
        store = PersistentResultStore(
            path, max_bytes,
            on_evict=lambda n: _METRICS.note("store_evictions", n))
        cache.persistent = store
        return store


# ---------------------------------------------------------------------------
# router-side fingerprinting (the fleet seam)
# ---------------------------------------------------------------------------


def shape_fingerprint_doc(doc: dict, tables: Dict[str, pa.Table],
                          conf: RapidsTpuConf) -> str:
    """The SAME shape fingerprint ``shape_fingerprint`` computes, taken
    from a wire plandoc instead of a logical plan — the router routes on
    it without building a Session. The doc is decoded once (the window
    overcap/CBO gate bits read the logical tree), then hashed via the
    shared path so router placement and worker planning-cache keys
    always agree: the worker a shape lands on is exactly the worker
    whose cache is warm for it."""
    from ..server.plandoc import doc_to_plan
    plan = doc_to_plan(doc, tables)
    return shape_fingerprint(plan, conf, encoded=(doc, tables))
