"""Differential translator: Catalyst physical plans -> the plandoc dialect.

The driver half of the bridge (reference: Plugin.scala:44-51 hands the
executedPlan to GpuOverrides at GpuOverrides.scala:4271). A Spark driver
exports ``df.queryExecution.executedPlan.toJSON`` (plus the small bridge
extensions documented in docs/serving.md); :func:`translate` parses it
with :mod:`catalyst` and emits the in-house logical plan the serving tier
executes. ``PlanClient.collect_catalyst`` runs the result through a live
plan server or router.

Translation discipline (the reference's willNotWork analogue):

- attribute references resolve by **exprId** against the translated
  child's output scope and emit pre-bound ``BoundReference`` ordinals —
  duplicate column names across join sides resolve correctly, exactly
  like Catalyst's own BindReferences;
- anything unmapped raises :class:`CatalystUnsupportedError` carrying the
  node path from the root — NEVER a silent partial translation;
- physical artifacts of Spark's planner are *looked through*, because the
  engine re-derives them: exchanges (distribution), non-global sorts
  (sort-merge-join/window input ordering), codegen wrappers, and the
  partial/final aggregate split (collapsed onto one LogicalAggregate);
- Spark literals arrive in Catalyst's internal representation (epoch
  days/micros, unscaled decimals) and are re-hydrated to rich python
  values, so device and interpreter paths agree.

``UNSUPPORTED`` is the drift table `tools/lint_bridge.py` checks: every
plandoc-registered plan node / expression class must either be exercised
by a golden fixture under tests/fixtures/catalyst/ or carry an explicit
entry here. Adding an engine expression without either breaks tier-1.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import pyarrow as pa

from .. import types as T
from ..exec.join import JoinType
from ..exec.sort import SortOrder
from ..expressions import aggregates as AGG
from ..expressions import window as W
from ..expressions.base import Alias, BoundReference, Expression, Literal
from ..plan import logical as L
from ..plan.logical import DataFrame
from .catalyst import (ACCEPTED_VERSIONS_CONF, CatalystBridgeError,
                       CatalystMalformedError, CatalystUnsupportedError,
                       CatalystVersionError, CNode, EXPR_HANDLERS,
                       PLAN_HANDLERS, SCHEMA_VERSION, build_tree,
                       check_schema_version, expression, parse_expr_id,
                       parse_literal_value, parse_object_name,
                       parse_spark_type, plan_node)

__all__ = [
    "translate", "Translation", "UNSUPPORTED", "engine_classes",
    "CatalystBridgeError", "CatalystUnsupportedError",
    "CatalystMalformedError", "CatalystVersionError", "SCHEMA_VERSION",
]


# ---------------------------------------------------------------------------
# scopes: exprId -> (output ordinal, attribute)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Attr:
    expr_id: int
    name: str
    dtype: T.SqlType
    nullable: bool


class Scope:
    """The translated child's output attributes. ``entries`` are
    (ordinal-in-child-schema, Attr); ordinals are explicit because a
    pruned scan's visible attrs map into the FULL table schema."""

    def __init__(self, entries: Sequence[Tuple[int, Attr]]):
        self.entries: List[Tuple[int, Attr]] = list(entries)
        self.by_id: Dict[int, Tuple[int, Attr]] = {
            a.expr_id: (o, a) for o, a in self.entries}

    @staticmethod
    def dense(attrs: Sequence[Attr]) -> "Scope":
        return Scope(list(enumerate(attrs)))

    def attrs(self) -> List[Attr]:
        return [a for _, a in self.entries]

    def resolve(self, expr_id: int, name: str, path: str) -> BoundReference:
        hit = self.by_id.get(expr_id)
        if hit is None:
            known = ", ".join(f"{a.name}#{a.expr_id}"
                              for _, a in self.entries) or "<empty>"
            raise CatalystMalformedError(
                f"attribute {name}#{expr_id} is not produced by the child "
                f"(child output: {known})", path)
        o, a = hit
        return BoundReference(o, a.dtype, a.nullable, a.name)

    def shifted(self, offset: int) -> List[Tuple[int, Attr]]:
        return [(o + offset, a) for o, a in self.entries]


# ---------------------------------------------------------------------------
# translator core
# ---------------------------------------------------------------------------

#: planner artifacts the engine re-derives; skimmed through when a handler
#: needs to see the structural node underneath (partial-agg collapsing)
_PASSTHROUGH = {"ShuffleExchangeExec", "BroadcastExchangeExec",
                "WholeStageCodegenExec", "InputAdapter",
                "AQEShuffleReadExec", "CoalesceExec"}


def _skim(cnode: CNode, path: str) -> CNode:
    while True:
        if cnode.simple in _PASSTHROUGH:
            cnode = cnode.child_field("child", path)
            continue
        if cnode.simple == "SortExec" and not cnode.fields.get("global"):
            cnode = cnode.child_field("child", path)
            continue
        return cnode


class Translator:
    def __init__(self, tables: Optional[Dict[str, pa.Table]] = None,
                 conf: Optional[dict] = None):
        self.tables = dict(tables or {})
        self.conf = dict(conf or {})
        self.table_names: List[str] = []
        self._synth = 0

    def fresh_id(self) -> int:
        # synthetic (negative) ids for outputs Catalyst never names;
        # they can never collide with real exprIds
        self._synth -= 1
        return self._synth

    # ---- plans ----
    def plan(self, cnode: CNode, path: str) -> Tuple[L.LogicalPlan, Scope]:
        h = PLAN_HANDLERS.get(cnode.simple)
        if h is None:
            raise CatalystUnsupportedError(
                f"plan node {cnode.cls}", f"{path}/{cnode.simple}")
        return h(self, cnode, f"{path}/{cnode.simple}")

    def child_plan(self, cnode: CNode, path: str, name: str = "child"
                   ) -> Tuple[L.LogicalPlan, Scope]:
        return self.plan(cnode.child_field(name, path), path)

    # ---- expressions ----
    @staticmethod
    def child_at(cnode: CNode, i: Any, path: str) -> CNode:
        if not isinstance(i, int) or not 0 <= i < len(cnode.children):
            raise CatalystMalformedError(
                f"{cnode.simple}: child index {i!r} out of range "
                f"({len(cnode.children)} children)", path)
        return cnode.children[i]

    def expr(self, cnode: CNode, scope: Scope, path: str) -> Expression:
        h = EXPR_HANDLERS.get(cnode.simple)
        if h is None:
            raise CatalystUnsupportedError(
                f"expression class {cnode.cls}", f"{path}/{cnode.simple}")
        return h(self, cnode, scope, f"{path}/{cnode.simple}")

    def expr_child(self, cnode: CNode, fname: str, scope: Scope,
                   path: str) -> Expression:
        """A child-index field on an expression node."""
        return self.expr(cnode.child_field(fname, path), scope, path)

    def expr_children(self, cnode: CNode, fname: str, scope: Scope,
                      path: str) -> List[Expression]:
        """A Seq[child-index] field on an expression node."""
        idxs = cnode.fields.get(fname)
        if idxs is None:
            return []
        if not isinstance(idxs, list):
            raise CatalystMalformedError(
                f"{cnode.simple}.{fname} must be a list of child indices, "
                f"got {idxs!r}", path)
        return [self.expr(self.child_at(cnode, i, path), scope,
                          f"{path}.{fname}[{k}]")
                for k, i in enumerate(idxs)]

    def field_trees(self, cnode: CNode, fname: str, path: str
                    ) -> List[CNode]:
        """A plan-node field holding a list of fully nested flattened
        expression arrays (projectList, sortOrder, ...)."""
        v = cnode.fields.get(fname)
        if v is None:
            return []
        if not isinstance(v, list):
            raise CatalystMalformedError(
                f"{cnode.simple}.{fname} must be a list of flattened "
                f"expression arrays, got {v!r}", path)
        out = []
        for i, el in enumerate(v):
            out.append(build_tree(el if isinstance(el, list) else [el],
                                  f"{path}.{fname}[{i}]"))
        return out

    def field_tree(self, cnode: CNode, fname: str, path: str
                   ) -> Optional[CNode]:
        v = cnode.fields.get(fname)
        if v is None:
            return None
        return build_tree(v if isinstance(v, list) else [v],
                          f"{path}.{fname}")


# ---------------------------------------------------------------------------
# shared field helpers
# ---------------------------------------------------------------------------

def _attr_list(tr: Translator, cnode: CNode, fname: str, path: str
               ) -> List[Tuple[int, str, T.SqlType, bool]]:
    """Parse a Seq[Attribute] plan field -> (exprId, name, dtype,
    nullable) rows."""
    out = []
    for n in tr.field_trees(cnode, fname, path):
        if n.simple != "AttributeReference":
            raise CatalystMalformedError(
                f"{fname} entries must be AttributeReference, "
                f"got {n.simple}", path)
        out.append((
            parse_expr_id(n.fields.get("exprId"), path),
            str(n.fields.get("name")),
            parse_spark_type(n.fields.get("dataType"), tr.conf, path),
            bool(n.fields.get("nullable", True)),
        ))
    return out


def _check_eval_mode(cnode: CNode, path: str) -> None:
    """ANSI/TRY arithmetic changes result semantics; only LEGACY maps."""
    em = cnode.fields.get("evalMode")
    if em is not None and parse_object_name(em, path).upper() != "LEGACY":
        raise CatalystUnsupportedError(
            f"evalMode {parse_object_name(em, path)} (only LEGACY maps; "
            f"ANSI runs through spark.rapids.tpu.sql.ansi.enabled)", path)
    if cnode.fields.get("failOnError"):
        raise CatalystUnsupportedError("failOnError=true arithmetic", path)


def _named_output(e: Expression, cnode: CNode, tr: Translator, path: str
                  ) -> Attr:
    """Output attribute of a projection element: Alias and
    AttributeReference carry (name, exprId); anything else gets a
    synthetic id (Catalyst itself always aliases computed outputs)."""
    if cnode.simple in ("Alias", "AttributeReference"):
        return Attr(parse_expr_id(cnode.fields.get("exprId"), path),
                    str(cnode.fields.get("name")), e.dtype, e.nullable)
    return Attr(tr.fresh_id(), f"col{abs(tr._synth)}", e.dtype, e.nullable)


def _identity_projection(out_attrs: List[Attr], exprs: List[Expression],
                         scope: Scope) -> bool:
    """True when a resultExpressions projection is a no-op over the
    scope (same columns, same order, same names) — skip the Project."""
    if len(exprs) != len(scope.entries):
        return False
    for i, (e, a) in enumerate(zip(exprs, out_attrs)):
        o, sa = scope.entries[i]
        if not isinstance(e, BoundReference) or e.ordinal != o:
            return False
        if a.name != sa.name:
            return False
    return True


# ---------------------------------------------------------------------------
# plan handlers
# ---------------------------------------------------------------------------

@plan_node("ShuffleExchangeExec", "BroadcastExchangeExec",
           "WholeStageCodegenExec", "InputAdapter", "AQEShuffleReadExec",
           "CoalesceExec")
def _passthrough(tr, cnode, path):
    # distribution/codegen artifacts: the engine re-derives exchanges
    # from scan num_slices and operator needs (overrides.py)
    return tr.child_plan(cnode, path)


@plan_node("LocalTableScanExec", "InMemoryTableScanExec")
def _local_scan(tr, cnode, path):
    name = cnode.fields.get("rtpuTable")
    if not name:
        raise CatalystUnsupportedError(
            f"{cnode.simple} without an rtpuTable reference — the driver "
            "plugin must upload inline rows as a named table "
            "(PlanClient.register_table) and stamp the scan", path)
    tbl = tr.tables.get(name)
    if tbl is None:
        raise CatalystMalformedError(
            f"plan references table {name!r} that the session does not "
            f"hold (known: {sorted(tr.tables)})", path)
    if name not in tr.table_names:
        tr.table_names.append(name)
    entries = []
    for eid, aname, dtype, nullable in _attr_list(tr, cnode, "output", path):
        if aname not in tbl.column_names:
            raise CatalystMalformedError(
                f"scan output column {aname!r} is not in table {name!r} "
                f"(columns: {tbl.column_names})", path)
        ordinal = tbl.column_names.index(aname)
        actual = T.from_arrow(tbl.schema.field(aname).type).kind
        if actual is not dtype.kind:
            raise CatalystMalformedError(
                f"scan column {aname!r} types as {dtype} in the plan but "
                f"{actual.value} in table {name!r}", path)
        entries.append((ordinal, Attr(eid, aname, dtype, nullable)))
    plan = L.LogicalScan((), data=tbl,
                         num_slices=int(cnode.fields.get("rtpuNumSlices", 1)
                                        or 1),
                         batch_rows=cnode.fields.get("rtpuBatchRows"))
    return plan, Scope(entries)


@plan_node("FileSourceScanExec")
def _file_scan(tr, cnode, path):
    loc = cnode.fields.get("rtpuLocation")
    if not isinstance(loc, dict) or not loc.get("paths"):
        raise CatalystUnsupportedError(
            "FileSourceScanExec without an rtpuLocation {format, paths} "
            "block — the driver plugin must inline the (pruned) file "
            "listing; HadoopFsRelation does not serialize", path)
    fmt = loc.get("format")
    if fmt != "parquet":
        raise CatalystUnsupportedError(f"file scan format {fmt!r} "
                                       f"(parquet only for now)", path)
    if tr.field_trees(cnode, "partitionFilters", path):
        raise CatalystUnsupportedError(
            "partitionFilters on a file scan (hive-partition pruning "
            "must happen driver-side; ship the pruned listing)", path)
    # dataFilters are IGNORED by design: Spark re-applies every filter in
    # the FilterExec above the scan, so pushdown is a pure optimization —
    # dropping it cannot change results (docs/serving.md, bridge rules)
    from ..io.parquet import ParquetSource
    req = cnode.fields.get("requiredSchema")
    columns = None
    if isinstance(req, dict) and req.get("type") == "struct":
        columns = [str(f.get("name")) for f in req.get("fields", [])]
    src = ParquetSource([str(p) for p in loc["paths"]], columns=columns)
    schema = src.schema()
    names = [f.name for f in schema.fields]
    entries = []
    for eid, aname, dtype, nullable in _attr_list(tr, cnode, "output", path):
        if aname not in names:
            raise CatalystMalformedError(
                f"scan output column {aname!r} is not in the file schema "
                f"(columns: {names})", path)
        ordinal = names.index(aname)
        actual = schema.fields[ordinal].dtype.kind
        if actual is not dtype.kind:
            raise CatalystMalformedError(
                f"scan column {aname!r} types as {dtype} in the plan but "
                f"{actual.value} in the files", path)
        entries.append((ordinal, Attr(eid, aname, dtype, nullable)))
    plan = L.LogicalScan((), source=src, _schema=schema,
                         num_slices=int(cnode.fields.get("rtpuNumSlices", 1)
                                        or 1))
    return plan, Scope(entries)


@plan_node("RangeExec")
def _range(tr, cnode, path):
    rng = tr.field_tree(cnode, "range", path)
    if rng is None or rng.simple != "Range":
        raise CatalystMalformedError(
            "RangeExec must embed the logical Range node", path)
    attrs = _attr_list(tr, rng, "output", path)
    eid = attrs[0][0] if attrs else tr.fresh_id()
    plan = L.LogicalRange((), int(rng.fields.get("start", 0)),
                          int(rng.fields.get("end", 0)),
                          int(rng.fields.get("step", 1)))
    return plan, Scope.dense([Attr(eid, "id", T.INT64, False)])


@plan_node("ProjectExec")
def _project(tr, cnode, path):
    child, scope = tr.child_plan(cnode, path)
    exprs, attrs = [], []
    for i, en in enumerate(tr.field_trees(cnode, "projectList", path)):
        p = f"{path}/projectList[{i}]"
        e = tr.expr(en, scope, p)
        a = _named_output(e, en, tr, p)
        exprs.append(e if isinstance(e, Alias) or
                     (isinstance(e, BoundReference) and e.name == a.name)
                     else Alias(e, a.name))
        attrs.append(a)
    return L.LogicalProject((child,), exprs), Scope.dense(attrs)


@plan_node("FilterExec")
def _filter(tr, cnode, path):
    child, scope = tr.child_plan(cnode, path)
    cond_n = tr.field_tree(cnode, "condition", path)
    if cond_n is None:
        raise CatalystMalformedError("FilterExec without a condition", path)
    cond = tr.expr(cond_n, scope, f"{path}/condition")
    return L.LogicalFilter((child,), cond), scope


def _sort_orders(tr, cnode, fname, scope, path) -> List[SortOrder]:
    orders = []
    for i, on in enumerate(tr.field_trees(cnode, fname, path)):
        p = f"{path}/{fname}[{i}]"
        if on.simple != "SortOrder":
            raise CatalystMalformedError(
                f"{fname} entries must be SortOrder, got {on.simple}", p)
        orders.append(_sort_order(tr, on, scope, p))
    return orders


def _sort_order(tr, on: CNode, scope, path) -> SortOrder:
    child = tr.expr_child(on, "child", scope, path)
    direction = parse_object_name(on.fields.get("direction", "Ascending"),
                                  path)
    null_ord = parse_object_name(on.fields.get("nullOrdering",
                                               "NullsFirst"), path)
    if direction not in ("Ascending", "Descending"):
        raise CatalystMalformedError(f"sort direction {direction}", path)
    if null_ord not in ("NullsFirst", "NullsLast"):
        raise CatalystMalformedError(f"null ordering {null_ord}", path)
    return SortOrder(child, direction == "Descending",
                     null_ord == "NullsFirst")


@plan_node("SortExec")
def _sort(tr, cnode, path):
    if not cnode.fields.get("global"):
        # a non-global sort is SMJ/window input ordering; the engine's
        # own execs re-sort — translating it would be redundant work
        return tr.child_plan(cnode, path)
    child, scope = tr.child_plan(cnode, path)
    orders = _sort_orders(tr, cnode, "sortOrder", scope, path)
    return L.LogicalSort((child,), orders, True), scope


@plan_node("GlobalLimitExec", "CollectLimitExec")
def _limit(tr, cnode, path):
    inner = cnode.child_field("child", path)
    if inner.simple == "LocalLimitExec":
        # GlobalLimit(n, LocalLimit(n, child)): one logical limit
        inner = inner.child_field("child", path)
    child, scope = tr.plan(inner, path)
    return L.LogicalLimit((child,), int(cnode.fields.get("limit", 0))), scope


@plan_node("LocalLimitExec")
def _local_limit(tr, cnode, path):
    raise CatalystUnsupportedError(
        "LocalLimitExec without an enclosing GlobalLimitExec (a "
        "per-partition limit has no logical equivalent here)", path)


@plan_node("TakeOrderedAndProjectExec")
def _take_ordered(tr, cnode, path):
    child, scope = tr.child_plan(cnode, path)
    orders = _sort_orders(tr, cnode, "sortOrder", scope, path)
    plan = L.LogicalLimit(
        (L.LogicalSort((child,), orders, True),),
        int(cnode.fields.get("limit", 0)))
    exprs, attrs = [], []
    for i, en in enumerate(tr.field_trees(cnode, "projectList", path)):
        p = f"{path}/projectList[{i}]"
        e = tr.expr(en, scope, p)
        a = _named_output(e, en, tr, p)
        exprs.append(e)
        attrs.append(a)
    if exprs and not _identity_projection(attrs, exprs, scope):
        named = [e if isinstance(e, Alias) else Alias(e, a.name)
                 for e, a in zip(exprs, attrs)]
        return L.LogicalProject((plan,), named), Scope.dense(attrs)
    return plan, scope


@plan_node("UnionExec")
def _union(tr, cnode, path):
    if len(cnode.children) < 2:
        raise CatalystMalformedError("UnionExec needs >= 2 children", path)
    translated = [tr.plan(c, f"{path}[{i}]")
                  for i, c in enumerate(cnode.children)]
    plans = tuple(p for p, _ in translated)
    first = translated[0][1]
    # union output rides the first child's attrs; nullability ORs across
    # branches positionally (Spark's union output semantics)
    entries = []
    for i, (o, a) in enumerate(first.entries):
        nullable = a.nullable or any(
            s.entries[i][1].nullable for _, s in translated[1:]
            if i < len(s.entries))
        entries.append((o, Attr(a.expr_id, a.name, a.dtype, nullable)))
    return L.LogicalUnion(plans), Scope(entries)


@plan_node("ExpandExec")
def _expand(tr, cnode, path):
    child, scope = tr.child_plan(cnode, path)
    out = _attr_list(tr, cnode, "output", path)
    raw = cnode.fields.get("projections")
    if not isinstance(raw, list) or not raw:
        raise CatalystMalformedError("ExpandExec without projections", path)
    projections = []
    for pi, proj in enumerate(raw):
        if not isinstance(proj, list):
            raise CatalystMalformedError(
                f"projections[{pi}] must be a list of expression arrays",
                path)
        row = []
        for ei, el in enumerate(proj):
            p = f"{path}/projections[{pi}][{ei}]"
            e = tr.expr(build_tree(el if isinstance(el, list) else [el], p),
                        scope, p)
            if ei >= len(out):
                raise CatalystMalformedError(
                    f"projections[{pi}] is wider than output", path)
            row.append(Alias(e, out[ei][1]))
        projections.append(row)
    attrs = [Attr(eid, name, e.dtype, True)
             for (eid, name, _, _), e in zip(out, projections[0])]
    return L.LogicalExpand((child,), projections), Scope.dense(attrs)


@plan_node("SampleExec")
def _sample(tr, cnode, path):
    if cnode.fields.get("withReplacement"):
        raise CatalystUnsupportedError("sampling with replacement", path)
    lower = float(cnode.fields.get("lowerBound", 0.0))
    if lower != 0.0:
        raise CatalystUnsupportedError(
            f"sample lowerBound {lower} != 0 (range-splitting sample)",
            path)
    child, scope = tr.child_plan(cnode, path)
    plan = L.LogicalSample((child,),
                           float(cnode.fields.get("upperBound", 0.1)),
                           int(cnode.fields.get("seed", 0)))
    return plan, scope


# ---- joins ----------------------------------------------------------------

_JOIN_TYPES = {
    "Inner": JoinType.INNER, "LeftOuter": JoinType.LEFT_OUTER,
    "RightOuter": JoinType.RIGHT_OUTER, "FullOuter": JoinType.FULL_OUTER,
    "LeftSemi": JoinType.LEFT_SEMI, "LeftAnti": JoinType.LEFT_ANTI,
    "Cross": JoinType.CROSS,
}


@plan_node("SortMergeJoinExec", "ShuffledHashJoinExec",
           "BroadcastHashJoinExec")
def _join(tr, cnode, path):
    jt_name = parse_object_name(cnode.fields.get("joinType"), path)
    jt = _JOIN_TYPES.get(jt_name)
    if jt is None:
        raise CatalystUnsupportedError(f"join type {jt_name}", path)
    left, lscope = tr.child_plan(cnode, path, "left")
    right, rscope = tr.child_plan(cnode, path, "right")
    lkeys = [tr.expr(n, lscope, f"{path}/leftKeys[{i}]")
             for i, n in enumerate(tr.field_trees(cnode, "leftKeys", path))]
    rkeys = [tr.expr(n, rscope, f"{path}/rightKeys[{i}]")
             for i, n in enumerate(tr.field_trees(cnode, "rightKeys",
                                                  path))]
    if len(lkeys) != len(rkeys):
        raise CatalystMalformedError("left/right key count mismatch", path)
    n_left = len(left.schema().fields)
    ln = jt in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER)
    rn = jt in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER)
    pair_entries = (
        [(o, Attr(a.expr_id, a.name, a.dtype, a.nullable or ln))
         for o, a in lscope.entries]
        + [(o + n_left, Attr(a.expr_id, a.name, a.dtype, a.nullable or rn))
           for o, a in rscope.entries])
    cond = None
    cond_n = tr.field_tree(cnode, "condition", path)
    if cond_n is not None:
        cond = tr.expr(cond_n, Scope(pair_entries), f"{path}/condition")
    plan = L.LogicalJoin((left, right), lkeys, rkeys, jt, cond)
    if jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
        return plan, lscope
    return plan, Scope(pair_entries)


# ---- aggregates -----------------------------------------------------------

def _agg_function(tr, fn_node: CNode, scope, path) -> AGG.AggregateFunction:
    name = fn_node.simple
    p = f"{path}/{name}"
    if name == "Count":
        kids = fn_node.children
        if len(kids) == 1 and kids[0].simple == "Literal":
            return AGG.Count()            # count(*) == count(1)
        if len(kids) == 1:
            return AGG.Count(tr.expr(kids[0], scope, p))
        raise CatalystUnsupportedError("multi-argument count", p)
    cls = {"Sum": AGG.Sum, "Min": AGG.Min, "Max": AGG.Max,
           "Average": AGG.Average}.get(name)
    if cls is None:
        raise CatalystUnsupportedError(f"aggregate function {fn_node.cls}",
                                       p)
    _check_eval_mode(fn_node, p)
    if not fn_node.children:
        raise CatalystMalformedError(f"{name} without an argument", p)
    return cls(tr.expr(fn_node.children[0], scope, p))


def _agg_expression(tr, ae: CNode, scope, path, modes) -> Tuple[
        AGG.AggregateFunction, str, int]:
    """AggregateExpression wrapper -> (function, mode, resultId)."""
    if ae.simple != "AggregateExpression":
        raise CatalystMalformedError(
            f"expected AggregateExpression, got {ae.simple}", path)
    mode = parse_object_name(ae.fields.get("mode"), path)
    if mode not in modes:
        raise CatalystUnsupportedError(
            f"aggregate mode {mode} here (expected {sorted(modes)})", path)
    if ae.fields.get("isDistinct"):
        raise CatalystUnsupportedError("DISTINCT aggregates", path)
    if ae.fields.get("filter") is not None:
        raise CatalystUnsupportedError("FILTER (WHERE ...) aggregates",
                                       path)
    # aggregate functions parse structurally (not via expr dispatch):
    # they exist only inside AggregateExpression / window wrappers
    fn = _agg_function(tr, ae.child_field("aggregateFunction", path),
                       scope, path)
    rid_raw = ae.fields.get("resultId")
    rid = parse_expr_id(rid_raw, path) if rid_raw is not None \
        else tr.fresh_id()
    return fn, mode, rid


def _grouping_attr(g_node: CNode, e: Expression, path) -> Attr:
    if g_node.simple not in ("AttributeReference", "Alias"):
        raise CatalystUnsupportedError(
            f"unnamed grouping expression {g_node.simple} (Catalyst "
            "aliases computed grouping keys)", path)
    return Attr(parse_expr_id(g_node.fields.get("exprId"), path),
                str(g_node.fields.get("name")), e.dtype, e.nullable)


@plan_node("HashAggregateExec", "SortAggregateExec",
           "ObjectHashAggregateExec")
def _aggregate(tr, cnode, path):
    agg_nodes = tr.field_trees(cnode, "aggregateExpressions", path)
    modes = {parse_object_name(a.fields.get("mode"), path)
             for a in agg_nodes if a.simple == "AggregateExpression"}
    if modes - {"Final", "Complete", "Partial"}:
        raise CatalystUnsupportedError(
            f"aggregate modes {sorted(modes)}", path)
    if "Partial" in modes:
        raise CatalystUnsupportedError(
            "a Partial-mode aggregate at the top of a translated subtree "
            "(partial/final pairs collapse; export the whole plan)", path)
    base = cnode
    if modes == {"Final"}:
        # Final(Exchange(Partial(child))): grouping keys and aggregate
        # arguments live on the PARTIAL node (the final stage references
        # partial buffer attrs that exist only at runtime); result names
        # and ids come from THIS node
        inner = _skim(cnode.child_field("child", path), path)
        if inner.simple not in ("HashAggregateExec", "SortAggregateExec",
                                "ObjectHashAggregateExec"):
            raise CatalystMalformedError(
                f"Final-mode aggregate over {inner.simple} (expected the "
                "Partial half)", path)
        base = inner
        base_path = f"{path}/{inner.simple}"
    else:
        base_path = path
    child, scope = tr.child_plan(base, base_path)
    group_nodes = tr.field_trees(base, "groupingExpressions", base_path)
    group_exprs, group_attrs = [], []
    for i, gn in enumerate(group_nodes):
        p = f"{base_path}/groupingExpressions[{i}]"
        e = tr.expr(gn, scope, p)
        group_exprs.append(e)
        group_attrs.append(_grouping_attr(gn, e, p))
    base_aggs = tr.field_trees(base, "aggregateExpressions", base_path)
    final_attrs = _attr_list(tr, cnode, "aggregateAttributes", path)
    if len(final_attrs) != len(base_aggs):
        raise CatalystMalformedError(
            f"aggregateAttributes count {len(final_attrs)} != aggregate "
            f"count {len(base_aggs)}", path)
    agg_exprs, agg_attrs = [], []
    for j, (ae, (rid, rname, _, _)) in enumerate(zip(base_aggs,
                                                     final_attrs)):
        p = f"{base_path}/aggregateExpressions[{j}]"
        fn, _, _ = _agg_expression(tr, ae, scope, p,
                                   {"Partial", "Complete", "Final"})
        agg_exprs.append(Alias(fn, rname))
        bound = fn.bind(child.schema())
        agg_attrs.append(Attr(rid, rname, bound.dtype, bound.nullable))
    plan = L.LogicalAggregate((child,), group_exprs, agg_exprs)
    agg_scope = Scope.dense(group_attrs + agg_attrs)
    # resultExpressions: the final projection Catalyst folds into the agg
    res_nodes = tr.field_trees(cnode, "resultExpressions", path)
    if not res_nodes:
        return plan, agg_scope
    exprs, attrs = [], []
    for i, rn in enumerate(res_nodes):
        p = f"{path}/resultExpressions[{i}]"
        e = tr.expr(rn, agg_scope, p)
        a = _named_output(e, rn, tr, p)
        exprs.append(e)
        attrs.append(a)
    if _identity_projection(attrs, exprs, agg_scope):
        return plan, agg_scope
    named = [e if isinstance(e, Alias) else Alias(e, a.name)
             for e, a in zip(exprs, attrs)]
    return L.LogicalProject((plan,), named), Scope.dense(attrs)


# ---- windows --------------------------------------------------------------

def _frame_bound(node: CNode, path: str) -> Optional[int]:
    s = node.simple.rstrip("$")
    if s == "UnboundedPreceding" or s == "UnboundedFollowing":
        return None
    if s == "CurrentRow":
        return 0
    if s == "Literal":
        t = parse_spark_type(node.fields.get("dataType"), None, path)
        v = parse_literal_value(node.fields.get("value"), t, path)
        if not isinstance(v, int):
            raise CatalystUnsupportedError(
                f"non-integer frame bound {v!r}", path)
        return v
    raise CatalystUnsupportedError(f"frame bound {node.cls}", path)


def _window_frame(node: Optional[CNode], has_orders: bool, path: str
                  ) -> W.WindowFrame:
    if node is None or node.simple.rstrip("$") == "UnspecifiedFrame":
        return W.DEFAULT_FRAME if has_orders else W.FULL_FRAME
    if node.simple != "SpecifiedWindowFrame":
        raise CatalystUnsupportedError(f"window frame {node.cls}", path)
    ft = parse_object_name(node.fields.get("frameType"), path)
    if ft not in ("RowFrame", "RangeFrame"):
        raise CatalystMalformedError(f"frame type {ft}", path)
    lower = _frame_bound(node.child_field("lower", path), path)
    upper = _frame_bound(node.child_field("upper", path), path)
    return W.WindowFrame(ft == "RowFrame", lower, upper)


def _window_function(tr, fn: CNode, scope, path) -> W.WindowFunction:
    s = fn.simple
    p = f"{path}/{s}"
    if s == "RowNumber":
        return W.RowNumber()
    if s in ("Rank", "DenseRank"):
        # Spark carries the order exprs as children; they duplicate the
        # spec's orderSpec and are ignored here
        return W.Rank(dense=s == "DenseRank")
    if s == "PercentRank":
        return W.PercentRank()
    if s == "CumeDist":
        return W.CumeDist()
    if s == "NTile":
        b = tr.expr_child(fn, "buckets", scope, p)
        if not isinstance(b, Literal) or not isinstance(b.value, int):
            raise CatalystUnsupportedError("non-literal ntile buckets", p)
        return W.NTile(b.value)
    if s == "NthValue":
        if fn.fields.get("ignoreNulls"):
            raise CatalystUnsupportedError("nth_value ignoreNulls", p)
        off = tr.expr_child(fn, "offset", scope, p)
        if not isinstance(off, Literal) or not isinstance(off.value, int):
            raise CatalystUnsupportedError("non-literal nth_value offset",
                                           p)
        return W.NthValue(tr.expr_child(fn, "input", scope, p), off.value)
    if s in ("Lag", "Lead"):
        if fn.fields.get("ignoreNulls"):
            raise CatalystUnsupportedError(f"{s.lower()} ignoreNulls", p)
        child = tr.expr_child(fn, "input", scope, p)
        off = tr.expr_child(fn, "offset", scope, p)
        if not isinstance(off, Literal) or not isinstance(off.value, int):
            raise CatalystUnsupportedError(f"non-literal {s.lower()} "
                                           f"offset", p)
        default = tr.expr_child(fn, "default", scope, p)
        if isinstance(default, Literal) and default.value is None:
            default = None
        # Spark Lag stores a NEGATIVE offset; ours is positive-is-back
        offset = -off.value if s == "Lag" else off.value
        return W.LagLead(child, offset, default, is_lag=s == "Lag")
    if s == "AggregateExpression":
        f, _, _ = _agg_expression(tr, fn, scope, p, {"Complete"})
        return W.WindowAgg(f)
    raise CatalystUnsupportedError(f"window function {fn.cls}", p)


@plan_node("WindowExec")
def _window(tr, cnode, path):
    child, scope = tr.child_plan(cnode, path)
    wx, attrs = [], []
    for i, an in enumerate(tr.field_trees(cnode, "windowExpression", path)):
        p = f"{path}/windowExpression[{i}]"
        if an.simple != "Alias":
            raise CatalystMalformedError(
                "windowExpression entries must be aliased", p)
        wn = an.child_field("child", p)
        if wn.simple != "WindowExpression":
            raise CatalystMalformedError(
                f"expected WindowExpression under the alias, got "
                f"{wn.simple}", p)
        spec_n = wn.child_field("windowSpec", p)
        if spec_n.simple != "WindowSpecDefinition":
            raise CatalystMalformedError(
                f"expected WindowSpecDefinition, got {spec_n.simple}", p)
        keys = tr.expr_children(spec_n, "partitionSpec", scope, p)
        order_idx = spec_n.fields.get("orderSpec") or []
        orders = tuple(
            _sort_order(tr, tr.child_at(spec_n, ix, p), scope,
                        f"{p}.orderSpec[{k}]")
            for k, ix in enumerate(order_idx))
        frame_ref = spec_n.fields.get("frameSpecification")
        frame_n = tr.child_at(spec_n, frame_ref, p) \
            if isinstance(frame_ref, int) else None
        frame = _window_frame(frame_n, bool(orders), p)
        fn = _window_function(tr, wn.child_field("windowFunction", p),
                              scope, p)
        we = W.WindowExpression(fn, W.WindowSpec(tuple(keys), orders,
                                                 frame))
        name = str(an.fields.get("name"))
        wx.append(Alias(we, name))
        bound = we.bind(child.schema())
        attrs.append(Attr(parse_expr_id(an.fields.get("exprId"), p),
                          name, bound.dtype, bound.nullable))
    plan = L.LogicalWindow((child,), wx)
    n = len(child.schema().fields)
    return plan, Scope(scope.entries
                       + [(n + i, a) for i, a in enumerate(attrs)])


# ---- generate -------------------------------------------------------------

@plan_node("GenerateExec")
def _generate(tr, cnode, path):
    child, scope = tr.child_plan(cnode, path)
    gen_n = tr.field_tree(cnode, "generator", path)
    if gen_n is None:
        raise CatalystMalformedError("GenerateExec without a generator",
                                     path)
    pos = gen_n.simple == "PosExplode"
    if gen_n.simple not in ("Explode", "PosExplode"):
        raise CatalystUnsupportedError(f"generator {gen_n.cls}", path)
    if not gen_n.children:
        raise CatalystMalformedError(f"{gen_n.simple} without a child",
                                     path)
    gen = tr.expr(gen_n.children[0], scope, f"{path}/generator")
    req = _attr_list(tr, cnode, "requiredChildOutput", path)
    if [r[0] for r in req] != [a.expr_id for a in scope.attrs()]:
        raise CatalystUnsupportedError(
            "Generate with pruned requiredChildOutput (the bridge keeps "
            "the full child output)", path)
    gout = _attr_list(tr, cnode, "generatorOutput", path)
    outer = bool(cnode.fields.get("outer"))
    is_map = gen.dtype.kind is T.TypeKind.MAP
    want = (1 if pos else 0) + (2 if is_map else 1)
    if len(gout) != want:
        raise CatalystMalformedError(
            f"generatorOutput must have {want} attrs, got {len(gout)}",
            path)
    i = 0
    pos_name, pos_id = "pos", None
    if pos:
        pos_id, pos_name = gout[0][0], gout[0][1]
        i = 1
    elem_id, elem_name = gout[i][0], gout[i][1]
    value_id = value_name = None
    if is_map:
        value_id, value_name = gout[i + 1][0], gout[i + 1][1]
    plan = L.LogicalGenerate((child,), gen, outer, pos, elem_name,
                             pos_name, value_name or "value")
    out_schema = plan.schema()
    n = len(child.schema().fields)
    extra = []
    k = n
    if pos:
        extra.append((k, Attr(pos_id, pos_name,
                              out_schema.fields[k].dtype, outer)))
        k += 1
    extra.append((k, Attr(elem_id, elem_name,
                          out_schema.fields[k].dtype, outer)))
    if is_map:
        k += 1
        extra.append((k, Attr(value_id, value_name,
                              out_schema.fields[k].dtype, outer)))
    return plan, Scope(scope.entries + extra)


# ---------------------------------------------------------------------------
# expression handlers
# ---------------------------------------------------------------------------

@expression("AttributeReference")
def _attr_ref(tr, n, scope, path):
    eid = parse_expr_id(n.fields.get("exprId"), path)
    name = str(n.fields.get("name"))
    ref = scope.resolve(eid, name, path)
    declared = parse_spark_type(n.fields.get("dataType"), tr.conf, path)
    if declared.kind is not ref.dtype.kind:
        raise CatalystMalformedError(
            f"attribute {name}#{eid} declared {declared} but the child "
            f"produces {ref.dtype}", path)
    return ref


@expression("Alias")
def _alias(tr, n, scope, path):
    return Alias(tr.expr_child(n, "child", scope, path),
                 str(n.fields.get("name")))


@expression("Literal")
def _literal(tr, n, scope, path):
    t = parse_spark_type(n.fields.get("dataType"), tr.conf, path)
    v = parse_literal_value(n.fields.get("value"), t, path)
    return Literal(v, t)


@expression("Cast")
def _cast(tr, n, scope, path):
    _check_eval_mode(n, path)
    from ..expressions.cast import Cast
    return Cast(tr.expr_child(n, "child", scope, path),
                parse_spark_type(n.fields.get("dataType"), tr.conf, path))


def _binary(cls, check_mode=False):
    def h(tr, n, scope, path):
        if check_mode:
            _check_eval_mode(n, path)
        return cls(tr.expr_child(n, "left", scope, path),
                   tr.expr_child(n, "right", scope, path))
    return h


def _unary(cls, fname="child"):
    def h(tr, n, scope, path):
        return cls(tr.expr_child(n, fname, scope, path))
    return h


def _register_simple():
    from ..expressions import arithmetic as AR
    from ..expressions import boolean as B
    from ..expressions import comparison as CMP
    from ..expressions import conditional as COND
    from ..expressions import datetime as DTE
    from ..expressions import strings as S
    for name, cls in (("Add", AR.Add), ("Subtract", AR.Subtract),
                      ("Multiply", AR.Multiply), ("Divide", AR.Divide),
                      ("Remainder", AR.Remainder), ("Pmod", AR.Pmod),
                      ("IntegralDivide", AR.IntegralDivide)):
        expression(name)(_binary(cls, check_mode=True))
    for name, cls in (("And", B.And), ("Or", B.Or),
                      ("EqualTo", CMP.EqualTo),
                      ("EqualNullSafe", CMP.EqualNullSafe),
                      ("LessThan", CMP.LessThan),
                      ("LessThanOrEqual", CMP.LessThanOrEqual),
                      ("GreaterThan", CMP.GreaterThan),
                      ("GreaterThanOrEqual", CMP.GreaterThanOrEqual)):
        expression(name)(_binary(cls))
    for name, cls in (("Not", CMP.Not), ("IsNull", CMP.IsNull),
                      ("IsNotNull", CMP.IsNotNull), ("IsNaN", CMP.IsNaN),
                      ("UnaryMinus", AR.UnaryMinus), ("Abs", AR.Abs),
                      ("Upper", S.Upper), ("Lower", S.Lower),
                      ("Length", S.Length)):
        expression(name)(_unary(cls))
    for spark, part in (("Year", "year"), ("Month", "month"),
                        ("DayOfMonth", "day"), ("Quarter", "quarter"),
                        ("DayOfWeek", "dayofweek"),
                        ("DayOfYear", "dayofyear"),
                        ("WeekOfYear", "weekofyear"), ("Hour", "hour"),
                        ("Minute", "minute"), ("Second", "second")):
        def dh(tr, n, scope, path, _part=part):
            return DTE.ExtractDatePart(
                tr.expr_child(n, "child", scope, path), _part)
        expression(spark)(dh)
    for spark, neg in (("DateAdd", False), ("DateSub", True)):
        def dah(tr, n, scope, path, _neg=neg):
            return DTE.DateAddSub(
                tr.expr_child(n, "startDate", scope, path),
                tr.expr_child(n, "days", scope, path), _neg)
        expression(spark)(dah)

    def datediff(tr, n, scope, path):
        return DTE.DateDiff(tr.expr_child(n, "endDate", scope, path),
                            tr.expr_child(n, "startDate", scope, path))
    expression("DateDiff")(datediff)

    def if_h(tr, n, scope, path):
        return COND.If(tr.expr_child(n, "predicate", scope, path),
                       tr.expr_child(n, "trueValue", scope, path),
                       tr.expr_child(n, "falseValue", scope, path))
    expression("If")(if_h)

    def coalesce_h(tr, n, scope, path):
        kids = [tr.expr(c, scope, f"{path}[{i}]")
                for i, c in enumerate(n.children)]
        if not kids:
            raise CatalystMalformedError("coalesce() with no arguments",
                                         path)
        return COND.Coalesce(tuple(kids))
    expression("Coalesce")(coalesce_h)

    for spark, greatest in (("Least", False), ("Greatest", True)):
        def lg(tr, n, scope, path, _g=greatest):
            kids = [tr.expr(c, scope, f"{path}[{i}]")
                    for i, c in enumerate(n.children)]
            return COND.LeastGreatest(tuple(kids), _g)
        expression(spark)(lg)

    def concat_h(tr, n, scope, path):
        kids = [tr.expr(c, scope, f"{path}[{i}]")
                for i, c in enumerate(n.children)]
        return S.Concat(tuple(kids))
    expression("Concat")(concat_h)

    def substring_h(tr, n, scope, path):
        return S.Substring(tr.expr_child(n, "str", scope, path),
                           tr.expr_child(n, "pos", scope, path),
                           tr.expr_child(n, "len", scope, path))
    expression("Substring")(substring_h)

    for spark, op in (("Contains", "contains"),
                      ("StartsWith", "startswith"),
                      ("EndsWith", "endswith")):
        def sp(tr, n, scope, path, _op=op):
            pat = tr.expr_child(n, "right", scope, path)
            if not isinstance(pat, Literal):
                raise CatalystUnsupportedError(
                    f"non-literal {_op} pattern", path)
            return S.StringPredicate(
                tr.expr_child(n, "left", scope, path), pat, _op)
        expression(spark)(sp)


_register_simple()


@expression("CaseWhen")
def _case_when(tr, n, scope, path):
    from ..expressions.conditional import CaseWhen
    raw = n.fields.get("branches")
    if not isinstance(raw, list) or not raw:
        raise CatalystMalformedError("CaseWhen without branches", path)
    branches = []
    for i, b in enumerate(raw):
        p = f"{path}/branches[{i}]"
        if not isinstance(b, dict) or "_1" not in b or "_2" not in b:
            raise CatalystMalformedError(
                f"branch must be a Tuple2 of child indices, got {b!r}", p)
        pred = tr.expr(tr.child_at(n, b["_1"], p), scope, p)
        val = tr.expr(tr.child_at(n, b["_2"], p), scope, p)
        branches.append((pred, val))
    else_v = None
    if n.fields.get("elseValue") is not None:
        else_v = tr.expr_child(n, "elseValue", scope, path)
    return CaseWhen(tuple(branches), else_v)


@expression("In")
def _in(tr, n, scope, path):
    from ..expressions.comparison import In
    child = tr.expr_child(n, "value", scope, path)
    idxs = n.fields.get("list") or []
    values = []
    for k, i in enumerate(idxs):
        item = tr.expr(tr.child_at(n, i, path), scope, f"{path}/list[{k}]")
        if not isinstance(item, Literal):
            raise CatalystUnsupportedError(
                "non-literal IN list element (Catalyst rewrites those to "
                "OR chains / semi-joins)", f"{path}/list[{k}]")
        values.append(item.value)
    return In(child, tuple(values))


@expression("Like")
def _like(tr, n, scope, path):
    from ..expressions.regex import Like
    esc = n.fields.get("escapeChar", "\\")
    if esc not in (None, "\\"):
        raise CatalystUnsupportedError(f"LIKE escape char {esc!r}", path)
    pat = tr.expr_child(n, "right", scope, path)
    if not isinstance(pat, Literal) or not isinstance(pat.value, str):
        raise CatalystUnsupportedError("non-literal LIKE pattern", path)
    return Like(tr.expr_child(n, "left", scope, path), pat.value)


@expression("RLike")
def _rlike(tr, n, scope, path):
    from ..expressions.regex import RLike
    pat = tr.expr_child(n, "right", scope, path)
    if not isinstance(pat, Literal) or not isinstance(pat.value, str):
        raise CatalystUnsupportedError("non-literal RLIKE pattern", path)
    return RLike(tr.expr_child(n, "left", scope, path), pat.value)


# ---------------------------------------------------------------------------
# the drift table (tools/lint_bridge.py)
# ---------------------------------------------------------------------------

#: Engine (plandoc-registered) classes with NO golden Catalyst fixture
#: exercising their mapping — every entry needs a reason. The lint fails
#: when a registered class is neither translated by a fixture nor listed
#: here, and when an entry here IS covered (stale entry). This is the
#: bridge's analogue of the reference's api_validation drift checker.
UNSUPPORTED: Dict[str, str] = {
    # -- internal / structural (never arrive from Catalyst) --
    "AggregateFunction": "abstract base, never instantiated",
    "BinaryArithmetic": "abstract base, never instantiated",
    "_AddSub": "abstract base (Add/Subtract are the concrete classes)",
    "BinaryComparison": "abstract base, never instantiated",
    "BinaryLogic": "abstract base, never instantiated",
    "WindowFunction": "abstract marker base, never instantiated",
    "_ArraySetBase": "abstract base, never instantiated",
    "_CentralMoment": "abstract base, never instantiated",
    "_HofBase": "abstract base, never instantiated",
    "_MapHofBase": "abstract base, never instantiated",
    "_MinMax": "abstract base (Min/Max are the concrete classes)",
    "_MinMaxArray": "abstract base, never instantiated",
    "_Wrapped": "internal datetime rewrite helper, engine-side only",
    "UnresolvedColumn": "builder-API leaf; Catalyst plans arrive resolved "
                        "(the translator emits BoundReference)",
    "LambdaVariable": "rides only inside higher-order functions (below)",
    "_SlotRef": "UDF-compiler internal, engine-side only",
    "_WhileOut": "UDF-compiler internal, engine-side only",
    "_Memo": "UDF-compiler internal, engine-side only",
    "_LoopBudgetCheck": "UDF-compiler internal, engine-side only",
    # -- mapped-but-gated or unmapped Spark surface --
    "Pmod": "mapped (Pmod); no fixture yet",
    "IntegralDivide": "mapped (IntegralDivide); no fixture yet",
    "IsNaN": "mapped (IsNaN); no fixture yet",
    "Lower": "mapped (Lower); no fixture yet",
    "DateDiff": "mapped (DateDiff); no fixture yet",
    "LeastGreatest": "mapped (Least/Greatest); no fixture yet",
    "RLike": "mapped (RLike); no fixture yet",
    "NthValue": "mapped (NthValue); no fixture yet",
    "NTile": "mapped (NTile); no fixture yet",
    "PercentRank": "mapped (PercentRank); no fixture yet",
    "CumeDist": "mapped (CumeDist); no fixture yet",
    # -- no Catalyst mapping yet (each needs a handler + fixture) --
    "AddMonths": "no Catalyst mapping yet",
    "AggregateArray": "no Catalyst mapping yet (ArrayAggregate)",
    "ApproxPercentile": "no Catalyst mapping yet",
    "ArrayContains": "no Catalyst mapping yet",
    "ArrayDistinct": "no Catalyst mapping yet",
    "ArrayExcept": "no Catalyst mapping yet",
    "ArrayIntersect": "no Catalyst mapping yet",
    "ArrayMax": "no Catalyst mapping yet",
    "ArrayMin": "no Catalyst mapping yet",
    "ArrayPosition": "no Catalyst mapping yet",
    "ArrayRemove": "no Catalyst mapping yet",
    "ArrayRepeat": "no Catalyst mapping yet",
    "ArraySlice": "no Catalyst mapping yet",
    "ArrayUnion": "no Catalyst mapping yet",
    "ArraysOverlap": "no Catalyst mapping yet",
    "Ascii": "no Catalyst mapping yet",
    "Atan2": "no Catalyst mapping yet",
    "Bin": "no Catalyst mapping yet",
    "BitwiseNot": "no Catalyst mapping yet",
    "BitwiseOp": "no Catalyst mapping yet",
    "CollectList": "no Catalyst mapping yet",
    "CollectSet": "no Catalyst mapping yet",
    "Chr": "no Catalyst mapping yet",
    "ConcatWs": "no Catalyst mapping yet",
    "Conv": "no Catalyst mapping yet",
    "CreateArray": "no Catalyst mapping yet",
    "CreateStruct": "no Catalyst mapping yet",
    "DateFormat": "no Catalyst mapping yet (DateFormatClass)",
    "ElementAt": "no Catalyst mapping yet",
    "Empty2Null": "no Catalyst mapping yet",
    "ExistsArray": "no Catalyst mapping yet (ArrayExists)",
    "FilterArray": "no Catalyst mapping yet (ArrayFilter)",
    "FindInSet": "no Catalyst mapping yet",
    "First": "no Catalyst mapping yet",
    "Flatten": "no Catalyst mapping yet",
    "FloorCeil": "no Catalyst mapping yet (Floor/Ceiling)",
    "ForallArray": "no Catalyst mapping yet (ArrayForAll)",
    "FormatNumber": "no Catalyst mapping yet",
    "FromUnixtime": "no Catalyst mapping yet",
    "GetArrayItem": "no Catalyst mapping yet",
    "GetJsonObject": "no Catalyst mapping yet",
    "GetMapValue": "no Catalyst mapping yet",
    "GetStructField": "no Catalyst mapping yet",
    "Hex": "no Catalyst mapping yet",
    "Hypot": "no Catalyst mapping yet",
    "InitCap": "no Catalyst mapping yet",
    "InterleaveBits": "engine-internal (z-order clustering); Catalyst "
                      "has no such expression",
    "JsonToStructs": "no Catalyst mapping yet",
    "Last": "no Catalyst mapping yet",
    "LastDay": "no Catalyst mapping yet",
    "Levenshtein": "no Catalyst mapping yet",
    "Logarithm": "no Catalyst mapping yet",
    "MapContainsKey": "no Catalyst mapping yet",
    "MapFilter": "no Catalyst mapping yet",
    "MapFromArrays": "no Catalyst mapping yet",
    "MapKeys": "no Catalyst mapping yet",
    "MapValues": "no Catalyst mapping yet",
    "MonthsBetween": "no Catalyst mapping yet",
    "Murmur3Hash": "no Catalyst mapping yet",
    "NaNvl": "no Catalyst mapping yet",
    "NextDay": "no Catalyst mapping yet",
    "OctetLength": "no Catalyst mapping yet",
    "ParseDateTime": "no Catalyst mapping yet",
    "Percentile": "no Catalyst mapping yet",
    "PivotFirst": "no Catalyst mapping yet",
    "Pow": "no Catalyst mapping yet",
    "RaiseError": "no Catalyst mapping yet",
    "Rand": "nondeterministic; a translated plan must be replayable "
            "bit-for-bit (reference gates it the same way)",
    "RegexpExtract": "no Catalyst mapping yet",
    "RegexpReplace": "no Catalyst mapping yet",
    "ReplicateRows": "no Catalyst mapping yet",
    "Reverse": "no Catalyst mapping yet",
    "Round": "no Catalyst mapping yet",
    "Sequence": "no Catalyst mapping yet",
    "Shift": "no Catalyst mapping yet",
    "Signum": "no Catalyst mapping yet",
    "Size": "no Catalyst mapping yet",
    "SortArray": "no Catalyst mapping yet",
    "Soundex": "no Catalyst mapping yet",
    "StddevPop": "no Catalyst mapping yet",
    "StddevSamp": "no Catalyst mapping yet",
    "StringLocate": "no Catalyst mapping yet",
    "StringPad": "no Catalyst mapping yet",
    "StringRepeat": "no Catalyst mapping yet",
    "StringReplace": "no Catalyst mapping yet",
    "StringSplit": "no Catalyst mapping yet",
    "StringToMap": "no Catalyst mapping yet",
    "StringTrim": "no Catalyst mapping yet",
    "SubstringIndex": "no Catalyst mapping yet",
    "TransformArray": "no Catalyst mapping yet (ArrayTransform)",
    "TransformKeys": "no Catalyst mapping yet",
    "TransformValues": "no Catalyst mapping yet",
    "Translate": "no Catalyst mapping yet",
    "TruncDateTime": "no Catalyst mapping yet",
    "UTCTimestampConv": "no Catalyst mapping yet",
    "UnaryMath": "no Catalyst mapping yet (Sqrt/Exp/Log/...)",
    "UnixTimestampConv": "no Catalyst mapping yet",
    "VariancePop": "no Catalyst mapping yet",
    "VarianceSamp": "no Catalyst mapping yet",
    "XxHash64": "no Catalyst mapping yet",
    "ZipWith": "no Catalyst mapping yet",
}


# ---------------------------------------------------------------------------
# public surface
# ---------------------------------------------------------------------------

@dataclass
class Translation:
    """The result of translating one Catalyst plan document."""

    dataframe: DataFrame
    plan: L.LogicalPlan
    #: in-memory tables the plan references, in first-use order
    table_names: List[str]
    #: schemaVersion the document declared
    schema_version: int


def translate(doc: Any, tables: Optional[Dict[str, pa.Table]] = None,
              conf: Optional[dict] = None) -> Translation:
    """Catalyst `queryExecution` JSON (text or parsed) -> Translation.

    ``tables`` supplies the pyarrow tables in-memory scans reference by
    their ``rtpuTable`` name. ``conf`` carries ``spark.rapids.tpu.
    bridge.*`` settings (accepted schema versions, string budgets)."""
    if isinstance(doc, (str, bytes)):
        try:
            doc = json.loads(doc)
        except json.JSONDecodeError as e:
            raise CatalystMalformedError(f"document is not JSON: {e}")
    if not isinstance(doc, dict):
        raise CatalystMalformedError(
            f"expected a plan document object, got {type(doc).__name__}")
    version = check_schema_version(doc, conf)
    plan_arr = doc.get("plan")
    if plan_arr is None:
        raise CatalystMalformedError("document has no 'plan' array")
    root = build_tree(plan_arr)
    tr = Translator(tables, conf)
    plan, _scope = tr.plan(root, "$")
    return Translation(DataFrame(plan), plan, tr.table_names, version)


def engine_classes(plan: L.LogicalPlan) -> Set[str]:
    """Every plandoc-registered engine class a translated plan uses —
    plan node classes plus all expression classes reachable through the
    node fields (window specs, sort orders, case branches included).
    The lint's coverage walker."""
    import dataclasses
    seen: Set[str] = set()

    def walk_value(v):
        if isinstance(v, Expression):
            seen.add(type(v).__name__)
            for f in dataclasses.fields(v):
                walk_value(getattr(v, f.name))
            return
        if isinstance(v, SortOrder):
            walk_value(v.child)
            return
        if isinstance(v, W.WindowSpec):
            for k in v.partition_keys:
                walk_value(k)
            for o in v.orders:
                walk_value(o)
            return
        if isinstance(v, (list, tuple)):
            for x in v:
                walk_value(x)

    def walk_plan(p: L.LogicalPlan):
        seen.add(type(p).__name__)
        for f in p.__dataclass_fields__:
            if f in ("children", "data", "source", "_schema"):
                continue
            walk_value(getattr(p, f))
        for c in p.children:
            walk_plan(c)

    walk_plan(plan)
    return seen
