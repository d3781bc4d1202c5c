"""The yardstick's constants: the table of peaks and the touched-bytes
function that ``hbm_roofline_pct`` rests on."""

import types

import pytest

import benchlib  # noqa: F401  (puts benchmarks/ on sys.path)
from rtbench import loader, plans, touched


def _config(name):
    return loader.config(loader.benchmark(), name)


def test_q1_at_sf1_touches_about_028_gb():
    cfg = _config("tpch_sf1")
    q1 = loader.query("tpch", "q1")
    # 4 doubles, a date and two one-character strings (1 byte + a 4-byte
    # offset each) a row, 6.0M rows
    assert touched.touched_bytes(cfg, q1) == (4 * 8 + 4 + 2 * 5) * 6_000_000
    assert touched.touched_bytes(cfg, q1) == pytest.approx(0.28e9, rel=0.03)


def test_a_table_scanned_twice_counts_twice():
    cfg = _config("tpch_sf1")
    once = types.SimpleNamespace(TABLES={"lineitem": ["l_orderkey",
                                                      "l_quantity"]})
    twice = types.SimpleNamespace(TABLES=once.TABLES, SCANS={"lineitem": 2})
    assert touched.touched_bytes(cfg, once) == 16 * 6_000_000
    assert touched.touched_bytes(cfg, twice) == 2 * 16 * 6_000_000
    written = {"lineitem": {"rows": 5_999_000, "paths": []}}
    assert plans.scanned_rows(written, once) == 5_999_000
    assert plans.scanned_rows(written, twice) == 2 * 5_999_000


@pytest.mark.parametrize("column,width", [
    ({"type": "int32"}, 4), ({"type": "int64"}, 8), ({"type": "double"}, 8),
    ({"type": "date32[day]"}, 4), ({"type": "decimal128(7, 2)"}, 4),
    ({"type": "decimal128(17, 2)"}, 8), ({"type": "decimal128(38, 2)"}, 16),
    ({"type": "string", "avg_bytes": 10.5}, 14.5)])
def test_column_widths(column, width):
    assert touched.column_bytes(dict(column, name="c")) == width


def test_a_string_without_a_stated_length_is_refused():
    with pytest.raises(loader.BenchmarkError):
        touched.column_bytes({"name": "c", "type": "string"})
    with pytest.raises(loader.BenchmarkError):
        touched.column_bytes({"name": "c", "type": "list<int32>"})


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_v5e_peaks(kind):
    p = loader.peaks(kind)
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    assert "Google Cloud" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "NVIDIA H100"])
def test_an_unknown_device_kind_is_an_error_not_a_default(kind):
    with pytest.raises(loader.BenchmarkError):
        loader.peaks(kind)
