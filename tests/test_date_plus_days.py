"""Spark's date arithmetic and date / string comparisons in SQL:
``date + int``, ``int + date`` and ``date - int`` are ``date_add`` /
``date_sub`` (TPC-DS q72's ``d1.d_date + 5`` as the spec writes it;
before, both engines failed with ``no common type for date and
bigint``), a STRING compared with a DATE is cast to a date, and a cast
reads Spark's ``yyyy-[m]m-[d]d`` (q95's ``'1999-2-01'``).  The device
path runs under ``sql.test.enabled``, so a CPU operator would raise; the
CPU engine gives the same rows, and both equal plain Python dates."""
import datetime as dt

import pytest

from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf

DAYS = [dt.date(1999, 1, 28) + dt.timedelta(days=i) for i in range(0, 90, 3)]
DATA = {"d": DAYS, "n": list(range(len(DAYS)))}


def _session(device: bool):
    s = TpuSession(TpuConf({"spark.rapids.tpu.sql.enabled": device,
                            "spark.rapids.tpu.sql.test.enabled": device}))
    s.create_dataframe(DATA, num_partitions=2) \
        .create_or_replace_temp_view("t")
    return s


@pytest.mark.parametrize("device", [True, False], ids=["device", "cpu"])
def test_date_plus_and_minus_an_integer(device):
    got = sorted(_session(device).sql(
        "select n, d + 5, d - 3, 2 + d, d + n, d - n from t").collect())
    want = sorted((n, d + dt.timedelta(5), d - dt.timedelta(3),
                   d + dt.timedelta(2), d + dt.timedelta(n),
                   d - dt.timedelta(n)) for d, n in zip(DAYS, DATA["n"]))
    assert got == want


@pytest.mark.parametrize("device", [True, False], ids=["device", "cpu"])
def test_a_date_step_in_a_where(device):
    got = sorted(_session(device).sql(
        "select a.n, b.n from t a, t b where a.n + 1 = b.n "
        "and b.d > a.d + 2 and a.d - 1 < date '1999-03-01'").collect())
    want = sorted((a, b) for (a, da), (b, db) in
                  [((i, DAYS[i]), (i + 1, DAYS[i + 1]))
                   for i in range(len(DAYS) - 1)]
                  if db > da + dt.timedelta(2)
                  and da - dt.timedelta(1) < dt.date(1999, 3, 1))
    assert got == want and want


@pytest.mark.parametrize("device", [True, False], ids=["device", "cpu"])
def test_strings_compared_with_dates_are_dates(device):
    first = dt.date(1999, 2, 1)
    got = sorted(r[0] for r in _session(device).sql(
        "select d from t where d between '1999-2-01' and "
        "(cast('1999-2-01' as date) + interval 60 days)").collect())
    assert got == [d for d in DAYS
                   if first <= d <= first + dt.timedelta(60)]
    got = sorted(r[0] for r in _session(device).sql(
        "select d from t where d >= '1999-03-1' and '1999-03-10' > d")
        .collect())
    assert got == [d for d in DAYS
                   if dt.date(1999, 3, 1) <= d < dt.date(1999, 3, 10)]


@pytest.mark.parametrize("device", [True, False], ids=["device", "cpu"])
def test_a_cast_reads_sparks_date_forms(device):
    (row,) = _session(device).sql(
        "select cast('1999-2-01' as date), cast('1999-12-1 10:00' as date), "
        "cast('1999' as date), cast('1999-13-01' as date), "
        "cast('19x' as date) from t limit 1").collect()
    assert row == (dt.date(1999, 2, 1), dt.date(1999, 12, 1),
                   dt.date(1999, 1, 1), None, None)
