"""The comparison that decides ``correct``: a reply against the plain
reference's answer, as numbers each with a limit of its own.

``exact_mismatches``  cells of integer, string, date, decimal and boolean
                      columns that differ, rows missing or extra counted
                      whole, a wrong column name or type counted once;
                      limit 0.
``double_rel_err``    the largest |got - want| / max(|want|, DOUBLE_FLOOR)
                      over the cells of double columns; the limit is the
                      configuration's ``guarantees.double_rel_err``.
Rows are compared in order where the query orders them, else after sorting
both sides by the non-double columns.
"""

import numpy as np
import pyarrow as pa

DOUBLE_FLOOR = 1e-9


def _sorted(table):
    keys = [(f.name, "ascending") for f in table.schema
            if not pa.types.is_floating(f.type)]
    return table.sort_by(keys) if keys else table


def compare(got, want, ordered):
    """Returns ``{"exact_mismatches": int, "double_rel_err": float}``."""
    mism, err = 0, 0.0
    if got.num_columns != want.num_columns:
        return {"exact_mismatches": max(got.num_rows, want.num_rows, 1)
                * max(got.num_columns, want.num_columns),
                "double_rel_err": float("inf")}
    mism += sum(1 for a, b in zip(got.column_names, want.column_names)
                if a != b)
    got = got.rename_columns(want.column_names)
    if not ordered:
        got, want = _sorted(got), _sorted(want)
    rows = min(got.num_rows, want.num_rows)
    mism += abs(got.num_rows - want.num_rows) * want.num_columns
    got, want = got.slice(0, rows), want.slice(0, rows)
    for i, field in enumerate(want.schema):
        a, e = got.column(i), want.column(i)
        if a.type != e.type:
            mism += 1
            try:
                a = a.cast(e.type)
            except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
                mism += rows
                continue
        a_null = a.is_null().to_numpy(zero_copy_only=False)
        e_null = e.is_null().to_numpy(zero_copy_only=False)
        if pa.types.is_floating(field.type):
            av = a.to_numpy(zero_copy_only=False).astype(np.float64)
            ev = e.to_numpy(zero_copy_only=False).astype(np.float64)
            both = ~a_null & ~e_null
            mism += int((a_null != e_null).sum())
            if both.any():
                with np.errstate(invalid="ignore"):
                    rel = np.abs(av[both] - ev[both]) / np.maximum(
                        np.abs(ev[both]), DOUBLE_FLOOR)
                rel = np.where(np.isnan(rel), np.inf, rel)
                same_nan = np.isnan(av[both]) & np.isnan(ev[both])
                rel = np.where(same_nan, 0.0, rel)
                err = max(err, float(rel.max()))
        else:
            same = pa.compute.equal(a, e).fill_null(False) \
                .to_numpy(zero_copy_only=False)
            mism += int((~(same | (a_null & e_null))).sum())
    return {"exact_mismatches": mism, "double_rel_err": err}


def verdict(readings, failed, limit):
    """Every number compared beside its limit, and ``correct``: the worst of
    the replies' readings number by number, the count of failed submissions,
    and ``limit`` for ``double_rel_err`` (None where the configuration has
    no double). No reply at all is not correct."""
    compared = {
        "failed_queries": {"value": failed, "limit": 0},
        "replies_compared": {"value": len(readings), "limit": None},
        "exact_mismatches": {"value": max(
            (r["exact_mismatches"] for r in readings), default=0),
            "limit": 0}}
    if limit is not None:
        compared["double_rel_err"] = {"value": max(
            (r["double_rel_err"] for r in readings), default=0.0),
            "limit": limit}
    correct = bool(readings) and all(
        c["limit"] is None or c["value"] <= c["limit"]
        for c in compared.values())
    return compared, correct
