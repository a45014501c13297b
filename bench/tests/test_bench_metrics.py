"""Each metric reader against values worked out by hand, in the case
tables of ``reader_cases/``: every module there is found, so a reader
joins with its case as new files alone."""
import importlib
import os
import pkgutil

import pytest

import reader_cases
from harness import spec
from reader_cases.records_and_trace import ctx, rec, records, trace

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics")
TABLES = {m.name: importlib.import_module(f"reader_cases.{m.name}")
          for m in pkgutil.iter_modules(reader_cases.__path__)}
TABLE_OF = {n: t for t in TABLES.values() for n in t.CASES}


def case_problems(readers, cases):
    """What keeps ``readers`` (reader names) and ``cases`` (table name ->
    that table's ``CASES``) from matching one to one: a reader without a
    case, a case without a reader, a name with cases in two tables."""
    where = {}
    for table, names in sorted(cases.items()):
        for n in names:
            where.setdefault(n, []).append(table)
    return ([f"{n}: cases in {ts}" for n, ts in sorted(where.items())
             if len(ts) > 1]
            + [f"{n}: no case" for n in sorted(set(readers) - set(where))]
            + [f"{n}: no reader" for n in sorted(set(where) - set(readers))])


@pytest.mark.parametrize("name", sorted(TABLE_OF))
def test_reader(name):
    table = TABLE_OF[name]
    with table.context(traced=True) as c:
        assert spec.load_reader(name, METRICS)(c) == \
            pytest.approx(table.CASES[name])


@pytest.mark.parametrize("name", [n for n in sorted(TABLE_OF)
                                  if "roofline" in n or "mfu" in n
                                  or "idle" in n])
def test_trace_readers_read_nothing_without_a_trace(name):
    with TABLE_OF[name].context(traced=False) as c:
        assert spec.load_reader(name, METRICS)(c) is None


def test_every_reader_has_a_case():
    readers = [f[:-3] for f in os.listdir(METRICS) if f.endswith(".py")]
    assert case_problems(readers, {n: t.CASES for n, t in TABLES.items()}) \
        == []


@pytest.mark.parametrize("readers,cases", [
    (["a", "b"], {"t": {"a": 1.0}}),
    (["a"], {"t": {"a": 1.0, "b": 2.0}}),
    (["a", "b"], {"t": {"a": 1.0, "b": 2.0}, "u": {"b": 2.0}}),
], ids=["reader_without_a_case", "case_without_a_reader",
        "cases_in_two_tables"])
def test_the_case_check_finds(readers, cases):
    assert len(case_problems(readers, cases)) == 1


def test_failed_invocation_misses_every_latency_limit():
    rs = records()
    rs[0] = rec(0, 100.0, 0.0, 0.1, 0.0, 0.0, 0.05, failed=True)
    read = spec.load_reader("latency_p95_ms", METRICS)
    assert read(ctx(records=rs)) == float("inf")


def test_idle_gaps_are_named_by_the_host_span_over_them():
    t = trace()
    assert t.busy_s == pytest.approx(0.070)
    assert t.idle_gaps() == [["upload", pytest.approx(0.020)],
                             ["execute", pytest.approx(0.010)]]
    assert t.device_ops() == [["jit__decode", pytest.approx(0.060)],
                              ["jit__prefill", pytest.approx(0.010)]]
