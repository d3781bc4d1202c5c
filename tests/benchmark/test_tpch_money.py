"""Generator ``tpch`` makes money in the type its configuration states.
``double`` is byte for byte what the generator made before it knew a second
type (digests of each table's IPC stream, taken from the parent's generator
at this seed and scale); ``decimal(15,2)`` holds the same cents, and the
spec's identities hold for it exactly (``domains/tpch.py``)."""

import hashlib
import json
import os

import pyarrow as pa
import pytest

from benchlib import BENCH, domains
from rtbench import loader

SEED = 2 ** 31 + 12345
SCALE = 0.005
# sha256 of pa.ipc.new_stream(...).write_table(t), PR 29's datagen/tpch.py
PARENT = {
    "customer":
        "3f845abbc6a3098b4dfee87377d2618b73f476fae9719ae10c6c708c7e3c047b",
    "lineitem":
        "5f21358aaebefd6199f6e732a2ea75040501520b4f02c3225764455275f24e6a",
    "orders":
        "7126165c9e849ea669e16d0923c9d6723c246abc4ffb530a131fad33baa297ba"}
MONEY = {"customer": ["c_acctbal"], "orders": ["o_totalprice"],
         "lineitem": ["l_quantity", "l_extendedprice", "l_discount",
                      "l_tax"]}


@pytest.fixture(scope="module")
def forms():
    with open(os.path.join(BENCH, "configs", "tpch_sf1.json")) as f:
        cfg = json.load(f)
    assert cfg["money_type"] == "double"
    dec = dict(cfg, money_type="decimal(15,2)")
    gen = loader.generator("tpch")
    return (cfg, gen.generate(cfg, SCALE, SEED, sorted(PARENT)),
            dec, gen.generate(dec, SCALE, SEED, sorted(PARENT)))


@pytest.mark.parametrize("table", sorted(PARENT))
def test_double_tables_are_the_parents_byte_for_byte(table, forms):
    t = forms[1][table]
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, t.schema) as w:
        w.write_table(t)
    assert hashlib.sha256(sink.getvalue().to_pybytes()).hexdigest() \
        == PARENT[table]


@pytest.mark.parametrize("table", sorted(PARENT))
def test_the_decimal_form_holds_the_double_forms_cents(table, forms):
    cfg, double, dec, decimal = forms
    cents = domains("tpch").cents
    for f in double[table].schema:
        if f.name in MONEY[table]:
            assert decimal[table].schema.field(f.name).type \
                == pa.decimal128(15, 2)
            assert (cents(decimal[table], f.name)
                    == cents(double[table], f.name)).all()
        else:       # every other column is the same column
            assert decimal[table][f.name].equals(double[table][f.name])


@pytest.mark.parametrize("table", sorted(PARENT))
def test_the_specs_identities_hold_for_decimals_exactly(table, forms):
    _, _, dec, decimal = forms
    domains("tpch").DOMAINS[table](decimal[table], decimal, dec)


def test_a_money_type_the_generator_does_not_make_is_refused(forms):
    with pytest.raises(ValueError, match="money_type"):
        loader.generator("tpch").generate(
            dict(forms[0], money_type="decimal(38,6)"), SCALE, SEED,
            ["customer"])
