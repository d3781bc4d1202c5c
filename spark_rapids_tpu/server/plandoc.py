"""Wire dialect: logical plans + expression trees <-> JSON documents.

The serialized-plan format an external driver speaks (the reference's
equivalent moment is Spark handing a physical plan to GpuOverrides,
GpuOverrides.scala:4271; here the plan crosses a process boundary first).

Encoding rules — every value is either a JSON scalar or a single-key tagged
object, so decoding is unambiguous:

  {"$e": [ClassName, field...]}     expression (registry-driven: expression
                                    classes are frozen dataclasses, fields
                                    encoded positionally)
  {"$p": [NodeName, [children...], field...]}   logical plan node
  {"$t": [kind, precision, scale, max_len, [children...]]}   SqlType
  {"$schema": [[name, type, nullable]...]}      Schema
  {"$sort": [child, descending, nulls_first]}   SortOrder
  {"$enum": [EnumName, member]}     registered enum
  {"$l": [...]}                     list/tuple
  {"$d": [[k, v]...]}               dict
  {"$b": "base64"}                  bytes
  {"$f": "nan"|"inf"|"-inf"}        non-finite float
  {"$date": ordinal} / {"$ts": iso} / {"$dec": str}   datetime literals
  {"$table": name}                  external table reference (Arrow IPC
                                    stream shipped separately)
  {"$src": {...}}                   file-backed source (paths + pushdown)

In-memory scan data is NOT inlined: ``plan_to_doc`` externalizes each
``LogicalScan.data`` pyarrow table into the returned table registry; the
protocol layer ships those as Arrow IPC.
"""

from __future__ import annotations

import base64
import datetime as _dt
import decimal as _pydec
import enum
import math
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa

from .. import types as T
from ..batch import Field as SField, Schema
from ..exec.aggregate import AggregateMode
from ..exec.join import JoinType
from ..exec.sort import SortOrder
from ..expressions.base import Expression
from ..io.source import FileSource, ReaderType
from ..plan import logical as L

PROTOCOL_VERSION = 1


class PlanDecodeError(ValueError):
    """Wire-dialect violation. Decode-side failures carry ``path`` — the
    ``$p``/``$e`` node path from the document root (e.g.
    ``$p:LogicalProject/exprs[1]/$e:Add[0]``) — the same discipline the
    Catalyst bridge's CatalystUnsupportedError uses, so a client sees
    WHICH subtree of its submitted plan failed, not just the tag."""

    def __init__(self, message: str, path: Optional[str] = None):
        super().__init__(f"{message} [at {path}]" if path else message)
        self.reason = message
        self.path = path


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------

_PLAN_NODES: Dict[str, type] = {
    cls.__name__: cls
    for cls in (L.LogicalScan, L.LogicalRange, L.LogicalProject,
                L.LogicalFilter, L.LogicalAggregate, L.LogicalJoin,
                L.LogicalSort, L.LogicalLimit, L.LogicalUnion,
                L.LogicalExpand, L.LogicalWindow, L.LogicalSample,
                L.LogicalGenerate)
}

_ENUMS: Dict[str, type] = {"JoinType": JoinType, "ReaderType": ReaderType,
                           "AggregateMode": AggregateMode}


_PLAIN_DATACLASSES: Dict[str, type] = {}


def _plain_dataclasses() -> Dict[str, type]:
    """Non-Expression frozen dataclasses that ride expression trees
    (window specs); encoded positionally like expressions. Cached —
    encode_value consults this per value on the server hot path."""
    if not _PLAIN_DATACLASSES:
        from ..expressions.window import WindowFrame, WindowSpec
        _PLAIN_DATACLASSES.update(WindowSpec=WindowSpec,
                                  WindowFrame=WindowFrame)
    return _PLAIN_DATACLASSES


def _late_expression(name: str) -> Optional[type]:
    """An expression class registers itself when its module is imported,
    and a server imports a module only when a plan needs it: a window
    function's first arrival finds ``expressions/window.py`` not yet
    imported. Import the package's modules (once) and look again."""
    import importlib
    import pkgutil
    from .. import expressions
    for m in pkgutil.iter_modules(expressions.__path__):
        importlib.import_module(f"{expressions.__name__}.{m.name}")
    return Expression._registry.get(name)


def _file_sources() -> Dict[str, type]:
    from ..io.avro import AvroSource
    from ..io.csv import CsvSource
    from ..io.json import JsonSource
    from ..io.orc import OrcSource
    from ..io.parquet import ParquetSource
    return {"parquet": ParquetSource, "orc": OrcSource, "csv": CsvSource,
            "json": JsonSource, "avro": AvroSource}


# ---------------------------------------------------------------------------
# value codec
# ---------------------------------------------------------------------------

def _refuse_unstated(cls: type) -> None:
    """An expression is written down as its dataclass fields, so they must
    be all its constructor takes: a class with a hand-written ``__init__``
    (``udf/compiler``'s loop nodes) holds state that ``astuple()`` does
    not list, and would read as equal to every other instance of its
    class."""
    made_by = next(c for c in cls.__mro__ if "__init__" in vars(c))
    if "__dataclass_fields__" not in vars(made_by):
        raise PlanDecodeError(
            f"cannot serialize {cls.__name__}: its fields do not state it")


def encode_value(v: Any) -> Any:
    if v is None or isinstance(v, (bool, int, str)):
        return v
    if isinstance(v, float):
        if math.isfinite(v):
            return v
        return {"$f": "nan" if math.isnan(v) else
                ("inf" if v > 0 else "-inf")}
    if isinstance(v, np.generic):
        return encode_value(v.item())
    if isinstance(v, Expression):
        _refuse_unstated(type(v))
        return {"$e": [type(v).__name__]
                + [encode_value(x) for x in v.astuple()]}
    if isinstance(v, SortOrder):
        return {"$sort": [encode_value(v.child), v.descending,
                          v.nulls_first]}
    if isinstance(v, T.SqlType):
        return {"$t": [v.kind.value, v.precision, v.scale, v.max_len,
                       [encode_value(c) for c in v.children],
                       list(v.names)]}
    if isinstance(v, Schema):
        return {"$schema": [[f.name, encode_value(f.dtype), f.nullable]
                            for f in v.fields]}
    if isinstance(v, enum.Enum):
        name = type(v).__name__
        if name not in _ENUMS:
            raise PlanDecodeError(f"unregistered enum type {name}")
        return {"$enum": [name, v.name]}
    dc_cls = _plain_dataclasses().get(type(v).__name__)
    if dc_cls is not None and type(v) is dc_cls:
        import dataclasses
        return {"$dc": [type(v).__name__]
                + [encode_value(getattr(v, f.name))
                   for f in dataclasses.fields(v)]}
    if isinstance(v, (list, tuple)):
        return {"$l": [encode_value(x) for x in v]}
    if isinstance(v, dict):
        return {"$d": [[encode_value(k), encode_value(x)]
                       for k, x in v.items()]}
    if isinstance(v, (bytes, bytearray)):
        return {"$b": base64.b64encode(bytes(v)).decode("ascii")}
    if isinstance(v, _dt.datetime):
        return {"$ts": v.isoformat()}
    if isinstance(v, _dt.date):
        return {"$date": v.toordinal()}
    if isinstance(v, _pydec.Decimal):
        return {"$dec": str(v)}
    raise PlanDecodeError(
        f"cannot serialize {type(v).__name__} ({v!r}) into the plan dialect")


def decode_value(v: Any, path: str = "$") -> Any:
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if not isinstance(v, dict) or len(v) != 1:
        raise PlanDecodeError(f"malformed document value: {v!r}", path)
    (tag, payload), = v.items()
    if tag == "$f":
        return {"nan": math.nan, "inf": math.inf,
                "-inf": -math.inf}[payload]
    if tag == "$e":
        name, *args = payload
        cls = Expression._registry.get(name) or _late_expression(name)
        if cls is None:
            raise PlanDecodeError(f"unknown expression class {name}",
                                  path)
        return cls(*[decode_value(a, f"{path}/$e:{name}[{i}]")
                     for i, a in enumerate(args)])
    if tag == "$sort":
        child, desc, nf = payload
        return SortOrder(decode_value(child, f"{path}/$sort"), desc, nf)
    if tag == "$t":
        kind, precision, scale, max_len, children, names = payload
        return T.SqlType(T.TypeKind(kind), precision, scale, max_len,
                         tuple(decode_value(c, f"{path}/$t")
                               for c in children),
                         tuple(names))
    if tag == "$schema":
        return Schema([SField(n, decode_value(t, f"{path}/$schema:{n}"),
                              nullable)
                       for n, t, nullable in payload])
    if tag == "$enum":
        name, member = payload
        cls = _ENUMS.get(name)
        if cls is None:
            raise PlanDecodeError(f"unknown enum type {name}", path)
        return cls[member]
    if tag == "$dc":
        name, *args = payload
        cls = _plain_dataclasses().get(name)
        if cls is None:
            raise PlanDecodeError(f"unknown dataclass {name}", path)
        return cls(*[decode_value(a, f"{path}/$dc:{name}[{i}]")
                     for i, a in enumerate(args)])
    if tag == "$l":
        return tuple(decode_value(x, f"{path}[{i}]")
                     for i, x in enumerate(payload))
    if tag == "$d":
        return {decode_value(k, f"{path}<key>"):
                decode_value(x, f"{path}[{k!r}]") for k, x in payload}
    if tag == "$b":
        return base64.b64decode(payload)
    if tag == "$ts":
        return _dt.datetime.fromisoformat(payload)
    if tag == "$date":
        return _dt.date.fromordinal(payload)
    if tag == "$dec":
        return _pydec.Decimal(payload)
    raise PlanDecodeError(f"unknown document tag {tag!r}", path)


# ---------------------------------------------------------------------------
# file sources
# ---------------------------------------------------------------------------

def _encode_source(src: FileSource) -> dict:
    kinds = _file_sources()
    fmt = next((k for k, cls in kinds.items() if type(src) is cls), None)
    if fmt is None:
        raise PlanDecodeError(
            f"file source {type(src).__name__} has no wire encoding")
    doc = {
        "format": fmt,
        "paths": list(src.files),
        "columns": src._requested_columns,
        "predicate": (encode_value(src.predicate)
                      if src.predicate is not None else None),
        "reader_type": src.reader_type.name,
        "with_file_name": src.with_file_name,
    }
    if getattr(src, "rebase_mode", None) not in (None, "EXCEPTION"):
        doc["rebase_mode"] = src.rebase_mode
    return doc


def _decode_source(doc: dict) -> FileSource:
    cls = _file_sources().get(doc["format"])
    if cls is None:
        raise PlanDecodeError(f"unknown source format {doc['format']!r}")
    kw = {}
    if doc.get("rebase_mode"):
        kw["rebase_mode"] = doc["rebase_mode"]
    pred = doc.get("predicate")
    return cls(doc["paths"], columns=doc.get("columns"),
               predicate=decode_value(pred) if pred is not None else None,
               reader_type=ReaderType[doc.get("reader_type", "AUTO")],
               with_file_name=doc.get("with_file_name", False), **kw)


# ---------------------------------------------------------------------------
# plan codec
# ---------------------------------------------------------------------------

def _plan_fields(node: L.LogicalPlan) -> List[str]:
    """Dataclass field names excluding ``children`` (encoded separately)."""
    return [f for f in node.__dataclass_fields__ if f != "children"]


def plan_to_doc(plan: L.LogicalPlan,
                tables: Optional[Dict[str, pa.Table]] = None
                ) -> Tuple[dict, Dict[str, pa.Table]]:
    """Serialize; in-memory scan data lands in the ``tables`` registry
    (identity-deduplicated) to be shipped as Arrow IPC alongside."""
    tables = tables if tables is not None else {}
    by_id = {id(t): name for name, t in tables.items()}

    def enc(node: L.LogicalPlan) -> dict:
        children = [enc(c) for c in node.children]
        if isinstance(node, L.LogicalScan):
            doc: dict = {"$p": ["LogicalScan", children],
                         "num_slices": node.num_slices,
                         "batch_rows": node.batch_rows}
            if node.data is not None:
                name = by_id.get(id(node.data))
                if name is None:
                    # collision-safe: the registry may be pre-seeded with
                    # client-chosen names (PlanClient.register_table) —
                    # an auto name must never rebind an existing entry
                    i = len(tables)
                    name = f"t{i}"
                    while name in tables:
                        i += 1
                        name = f"t{i}"
                    tables[name] = node.data
                    by_id[id(node.data)] = name
                doc["table"] = name
            elif node.source is not None:
                if isinstance(node.source, FileSource):
                    doc["source"] = _encode_source(node.source)
                else:
                    raise PlanDecodeError(
                        f"scan source {type(node.source).__name__} has no "
                        "wire encoding (cached/iceberg/delta relations are "
                        "server-side objects)")
            else:
                doc["schema"] = encode_value(node._schema)
            return doc
        name = type(node).__name__
        if name not in _PLAN_NODES:
            raise PlanDecodeError(f"unknown plan node {name}")
        fields = [encode_value(getattr(node, f)) for f in _plan_fields(node)]
        return {"$p": [name, children] + fields}

    return enc(plan), tables


def doc_to_plan(doc: dict, tables: Dict[str, pa.Table]) -> L.LogicalPlan:
    def dec(d: dict, path: str) -> L.LogicalPlan:
        if not isinstance(d, dict) or "$p" not in d:
            raise PlanDecodeError(f"malformed plan node: {d!r}", path)
        payload = d["$p"]
        name, children = payload[0], payload[1]
        here = f"{path}/$p:{name}"
        kids = tuple(dec(c, f"{here}[{i}]")
                     for i, c in enumerate(children))
        if name == "LogicalScan":
            if "table" in d:
                ref = d["table"]
                if ref not in tables:
                    raise PlanDecodeError(
                        f"plan references table {ref!r} that was not sent",
                        here)
                return L.LogicalScan(kids, data=tables[ref],
                                     num_slices=d.get("num_slices", 1),
                                     batch_rows=d.get("batch_rows"))
            if "source" in d:
                try:
                    src = _decode_source(d["source"])
                except PlanDecodeError as e:
                    raise PlanDecodeError(
                        e.reason, e.path if e.path not in (None, "$")
                        else f"{here}.source")
                return L.LogicalScan(kids, source=src, _schema=src.schema(),
                                     num_slices=d.get("num_slices", 1),
                                     batch_rows=d.get("batch_rows"))
            return L.LogicalScan(kids,
                                 _schema=decode_value(d.get("schema"),
                                                      f"{here}.schema"),
                                 num_slices=d.get("num_slices", 1),
                                 batch_rows=d.get("batch_rows"))
        cls = _PLAN_NODES.get(name)
        if cls is None:
            raise PlanDecodeError(f"unknown plan node {name}", path)
        fields = [f for f in cls.__dataclass_fields__ if f != "children"]
        args = [decode_value(a, f"{here}.{fields[i]}"
                             if i < len(fields) else f"{here}.arg{i}")
                for i, a in enumerate(payload[2:])]
        return cls(kids, *args)

    return dec(doc, "$")
