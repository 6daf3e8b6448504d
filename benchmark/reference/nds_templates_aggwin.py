"""Plain reference for the templates of ``nds-sf1-power-aggwindow-1chip``:
the power test's aggregate and window classes (templates 2, 47, 51).

A sibling of ``nds_templates.py`` under the same rules: numpy and
pandas over the generator's raw files, nothing of ``ndstpu`` imported,
each query's parameters read from the rendered text.  It shares that
module's raw-file parser and small SQL helpers; the six tables these
templates read are all in its column lists.  Decimal columns are exact
integers of cents.

Each function computes what the SQL means, not how the system plans
it.  Where the meaning is subtle it is written out:

* query2: ``sum`` over no row (a day of a week without a sale) is NULL,
  and a ratio with a NULL side is NULL.  ``round(a / b, 2)`` is
  HALF_UP, computed from the exact integer quotient of the two sums of
  cents.  The system rounds a float64 quotient, which can only land on
  the other side of a half-cent tie when the quotient lies within its
  rounding error of one: the reference raises if any ratio of the
  answer lies within 1e-9 of a half-cent tie, so the two can never
  differ silently.  ``y`` and ``z`` join ``wswscs`` to ``date_dim`` on
  the week, once per day of that week in the year: a week's row is
  repeated once per (day of y, day of z) pair, as the SQL says.
* query47: ``avg(sum(x))`` over the partition is the exact decimal sum
  of the months' sums over their count, in float64, as the program
  computes it (a month whose sum is NULL is not counted); ``rank()``
  gives ties equal ranks, with gaps; ``v1.rn = v1_lag.rn + 1`` joins on
  rank values, and a NULL in any of the four name keys matches nothing
  in those joins; ``abs(sum - avg) / avg > 0.1`` runs in float64, and
  the reference raises if a row lies within 1e-9 of 0.1.
* query51: the ``ROWS`` frames run per row (``UNBOUNDED PRECEDING ..
  CURRENT ROW``), and a running sum skips NULL; the full outer join on
  (item, date) keeps both sides' unmatched rows, and its ``case when ...
  is not null`` merges the keys; ``max`` over a running frame skips
  NULL (NULL until a value comes); ``web_cumulative >
  store_cumulative`` is not true where either side is NULL.

Under the ``float32`` control the float columns (query2's ratios,
query47's average and the filter over it) are computed in float32.
"""

from __future__ import annotations

from typing import Callable, Dict

import numpy as np
import pandas as pd

from benchmark.reference import nds_templates as _base
from benchmark.reference.nds_templates import (
    _dec, _int, _join, _keys_in, _none, _order, _param)

RawTables = _base.RawTables

# how close to a decision boundary a float result may come before the
# reference refuses to decide it (see the module's docstring)
_TIE_GAP = 1e-9
_DAYS = ("Sunday", "Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
         "Saturday")


class TieError(ValueError):
    """A float result lies on a decision boundary within ``_TIE_GAP``."""


# -- query2 ------------------------------------------------------------------

def _week_day_sums(raw: RawTables) -> pd.DataFrame:
    """``wswscs``: per d_week_seq, the seven day sums (cents, NaN for
    NULL) of web and catalog sales together."""
    parts = []
    for table, prefix in (("web_sales", "ws"), ("catalog_sales", "cs")):
        f = raw.frame(table, [f"{prefix}_sold_date_sk",
                              f"{prefix}_ext_sales_price"])
        parts.append(f.set_axis(["sold_date_sk", "sales_price"], axis=1))
    wscs = pd.concat(parts, ignore_index=True)
    dd = raw.frame("date_dim", ["d_date_sk", "d_week_seq", "d_day_name"])
    j = _join(wscs, dd, ["sold_date_sk"], ["d_date_sk"])
    g = j.groupby(["d_week_seq", "d_day_name"], dropna=False,
                  sort=False).sales_price.sum(min_count=1)
    return g.unstack("d_day_name").reindex(columns=list(_DAYS))


def _half_up_ratio(a, b, ft):
    """round(a / b, 2), HALF_UP, from the exact integer quotient of two
    sums of cents; None where a side is NULL or b is 0."""
    if a is None or b is None or b == 0:
        return None
    a, b = int(a), int(b)
    sign = -1 if (a < 0) != (b < 0) else 1
    num, den = 200 * abs(a), 2 * abs(b)
    # distance of |a / b| from the nearest half-cent tie (k + 0.5) / 100
    if abs(num % den - abs(b)) <= _TIE_GAP * 200 * abs(b):
        raise TieError(f"query2: {a} / {b} lies within {_TIE_GAP} of a "
                       f"half-cent tie")
    k = (num + abs(b)) // den
    return float(ft(sign * k) / ft(100))


def query2(raw: RawTables, sql: str, ft=np.float64):
    year = int(_param(sql, r"d_year\s*=\s*(\d+)"))
    weeks = _week_day_sums(raw)
    dd = raw.frame("date_dim", ["d_week_seq", "d_year"])
    # days of each week in the year (date_dim's rows of that week)
    y_days = dd[dd.d_year == year].groupby("d_week_seq").size()
    z_days = dd[dd.d_year == year + 1].groupby("d_week_seq").size()
    sums = {int(w): [_none(v) for v in vals]
            for w, vals in zip(weeks.index, weeks.values)}
    rows = []
    for w1, n1 in y_days.items():
        w1 = int(w1)
        n2 = int(z_days.get(w1 + 53, 0))
        if w1 not in sums or w1 + 53 not in sums or n2 == 0:
            continue
        row = (w1,) + tuple(_half_up_ratio(a, b, ft) for a, b in zip(
            sums[w1], sums[w1 + 53]))
        rows += [row] * (int(n1) * n2)
    return "i" + "f" * 7, _order(rows, [(lambda r: r[0], True)], None)


# -- query47 -----------------------------------------------------------------

_NAMES47 = ["i_category", "i_brand", "s_store_name", "s_company_name"]


def query47(raw: RawTables, sql: str, ft=np.float64):
    year = int(_param(sql, r"d_year\s*=\s*(\d+)"))
    dd = raw.frame("date_dim", ["d_date_sk", "d_year", "d_moy"])
    dd = dd[(dd.d_year == year) | ((dd.d_year == year - 1) & (dd.d_moy == 12))
            | ((dd.d_year == year + 1) & (dd.d_moy == 1))]
    it = raw.frame("item", ["i_item_sk", "i_category", "i_brand"])
    st = raw.frame("store", ["s_store_sk", "s_store_name", "s_company_name"])
    ss = raw.frame("store_sales", ["ss_sold_date_sk", "ss_item_sk",
                                   "ss_store_sk", "ss_sales_price"])
    ss = ss[_keys_in(ss.ss_sold_date_sk.values, dd.d_date_sk.values)]
    j = _join(_join(_join(ss, it, ["ss_item_sk"], ["i_item_sk"]),
                    dd, ["ss_sold_date_sk"], ["d_date_sk"]),
              st, ["ss_store_sk"], ["s_store_sk"])
    # v1: the monthly sums; NULL names group together here
    v1 = j.groupby(_NAMES47 + ["d_year", "d_moy"], dropna=False,
                   sort=False).ss_sales_price.sum(min_count=1).reset_index(
                       name="sum_sales")
    # avg(sum) over (names, d_year): exact cents over the months counted
    part = v1.groupby(_NAMES47 + ["d_year"], dropna=False, sort=False)
    tot = part.sum_sales.transform(lambda s: s.sum(min_count=1)).values
    cnt = part.sum_sales.transform("count").values
    with np.errstate(invalid="ignore", divide="ignore"):
        avg = (tot.astype(ft) / np.maximum(cnt, 1).astype(ft)) / ft(100)
    v1["avg"] = np.where(cnt > 0, avg, np.nan)
    # rank() over (names order by d_year, d_moy): (d_year, d_moy) is a
    # group key, so no two rows of a partition tie and rank counts rows
    v1 = v1.sort_values(["d_year", "d_moy"], kind="stable")
    v1["rn"] = v1.groupby(_NAMES47, dropna=False, sort=False).cumcount() + 1
    # the self-joins: equality on the names, so a NULL name drops out
    named = v1.dropna(subset=_NAMES47)
    lag = named[_NAMES47 + ["rn", "sum_sales"]].rename(
        columns={"sum_sales": "psum"}).assign(rn=lambda f: f.rn + 1)
    lead = named[_NAMES47 + ["rn", "sum_sales"]].rename(
        columns={"sum_sales": "nsum"}).assign(rn=lambda f: f.rn - 1)
    v2 = named.merge(lag, on=_NAMES47 + ["rn"]).merge(
        lead, on=_NAMES47 + ["rn"])
    v2 = v2[(v2.d_year == year) & (v2.avg > 0) & v2.sum_sales.notna()]
    diff = (v2.sum_sales.values / 100.0).astype(ft) - v2.avg.values.astype(ft)
    dev = np.abs(diff) / v2.avg.values.astype(ft)
    near = np.abs(dev.astype(np.float64) - 0.1) <= _TIE_GAP
    if near.any():
        raise TieError(f"query47: {int(near.sum())} row(s) lie within "
                       f"{_TIE_GAP} of the 0.1 deviation threshold")
    keep = dev > ft(0.1)
    v2, diff = v2[keep], diff[keep]
    rows = [(_none(r.i_category), _none(r.i_brand), _none(r.s_store_name),
             _none(r.s_company_name), _int(r.d_year), _int(r.d_moy),
             float(ft(r.avg)), _dec(r.sum_sales), _dec(r.psum),
             _dec(r.nsum), float(d))
            for r, d in zip(v2.itertuples(index=False), diff)]
    rows = _order(rows, [(lambda r: r[10], True), (lambda r: r[2], True)],
                  100)
    return "ssssiifddd", [r[:10] for r in rows]


# -- query51 -----------------------------------------------------------------

def _running_daily(raw: RawTables, table: str, item: str, date: str,
                   price: str, days: pd.DataFrame) -> pd.DataFrame:
    """``sum(sum(price)) over (partition by item order by d_date rows
    between unbounded preceding and current row)`` of one channel's
    (item, d_date) groups, item not NULL."""
    f = raw.frame(table, [item, date, price])
    f = f[f[item].notna() & _keys_in(f[date].values, days.d_date_sk.values)]
    j = _join(f, days, [date], ["d_date_sk"])
    g = j.groupby([item, "d_date"], sort=False)[price].sum(
        min_count=1).reset_index(name="day").sort_values(
            [item, "d_date"], kind="stable")
    # a running sum skips NULL; NULL until the first value
    seen = g.day.notna().groupby(g[item].values).cumsum().values
    run = g.day.fillna(0.0).groupby(g[item].values).cumsum().values
    return pd.DataFrame({"item_sk": g[item].values, "d_date": g.d_date.values,
                         "cume": np.where(seen > 0, run, np.nan)})


def query51(raw: RawTables, sql: str, ft=np.float64):
    dms = int(_param(sql, r"d_month_seq\s+between\s+(\d+)"))
    dd = raw.frame("date_dim", ["d_date_sk", "d_date", "d_month_seq"])
    days = dd[(dd.d_month_seq >= dms) & (dd.d_month_seq <= dms + 11)][
        ["d_date_sk", "d_date"]]
    web = _running_daily(raw, "web_sales", "ws_item_sk", "ws_sold_date_sk",
                         "ws_sales_price", days)
    store = _running_daily(raw, "store_sales", "ss_item_sk",
                           "ss_sold_date_sk", "ss_sales_price", days)
    # keys are never NULL on either side here (item NOT NULL, d_date
    # from a join), so the outer merge pairs exactly the equal keys
    x = web.merge(store, on=["item_sk", "d_date"], how="outer",
                  suffixes=("_web", "_store")).sort_values(
                      ["item_sk", "d_date"], kind="stable")
    by = x.item_sk.values

    def running_max(col: pd.Series) -> np.ndarray:
        # skips NULL: NaN until a value comes, then the largest so far
        # (pandas' cummax leaves a NaN row NaN: carry the last one on)
        return col.groupby(by).cummax().groupby(by).ffill().values

    web_cum = running_max(x.cume_web)
    store_cum = running_max(x.cume_store)
    keep = ~np.isnan(web_cum) & ~np.isnan(store_cum) & (web_cum > store_cum)
    x = x[keep]
    rows = [(_int(i), d, _dec(w), _dec(s), _dec(wc), _dec(sc))
            for i, d, w, s, wc, sc in zip(
                x.item_sk.values, x.d_date.values, x.cume_web.values,
                x.cume_store.values, web_cum[keep], store_cum[keep])]
    return "isdddd", _order(rows, [(lambda r: r[0], True),
                                   (lambda r: r[1], True)], 100)


TEMPLATES: Dict[str, Callable] = {
    "query2": query2, "query47": query47, "query51": query51,
}


def answer(raw: RawTables, template: str, sql: str,
           floats: str = "float64"):
    """(column kinds, rows) the template asks for on this data, as
    ``nds_templates.answer``: ``floats`` is the type the float columns
    are computed in."""
    if template not in TEMPLATES:
        raise KeyError(f"the plain reference has no template {template!r}")
    return TEMPLATES[template](raw, sql, ft=np.dtype(floats).type)
