"""The least bytes a query has to stream: the columns it reads, at the
widths of the configuration's schema, times the configuration's row counts,
a table scanned twice counted twice. It counts the same work whatever
implements it, and is what ``hbm_roofline_pct`` divides by the chip's peak
bytes a second."""

import re

from . import loader

_FIXED = {"bool": 1, "int8": 1, "int16": 2, "int32": 4, "int64": 8,
          "float": 4, "double": 8, "date32[day]": 4, "timestamp[us]": 8}
_DECIMAL = re.compile(r"^decimal128\((\d+), *(\d+)\)$")
STRING_OFFSET_BYTES = 4


def column_bytes(column):
    t = column["type"]
    if t in _FIXED:
        return _FIXED[t]
    m = _DECIMAL.match(t)
    if m:
        p = int(m.group(1))
        return 4 if p <= 9 else 8 if p <= 18 else 16
    if t in ("string", "large_string"):
        if "avg_bytes" not in column:
            raise loader.BenchmarkError(
                f"string column {column['name']!r} states no avg_bytes")
        return column["avg_bytes"] + STRING_OFFSET_BYTES
    raise loader.BenchmarkError(f"no width known for type {t!r}")


def touched_bytes(config, query):
    times = getattr(query, "SCANS", {})
    total = 0
    for table, columns in query.TABLES.items():
        spec = config["tables"][table]
        by_name = {c["name"]: c for c in spec["columns"]}
        row = sum(column_bytes(by_name[c]) for c in columns)
        total += row * spec["rows"] * times.get(table, 1)
    return total
