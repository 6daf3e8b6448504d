"""Plain reference for the templates of ``nds-sf1-power-joinclasses-1chip``:
the power test's semi / anti / mark / residual-semi / full joins, its
INTERSECT and its twelvefold self-join (templates 69, 10, 94, 97, 38,
95).

A sibling of ``nds_templates.py`` under the same rules: numpy and
pandas over the generator's raw files, nothing of ``ndstpu`` imported,
each query's parameters read from the rendered text.  It shares that
module's raw-file parser and small SQL helpers and brings the column
lists of the four tables these templates add.  Decimal columns are
exact integers of cents; the six answers hold no float column.

Each function computes what the SQL means, not how the system plans
it.  Where the meaning is subtle it is written out:

* ``EXISTS`` / ``NOT EXISTS`` over an equality: a NULL on either side
  equals nothing, so a probe row with a NULL key fails ``EXISTS`` and
  passes ``NOT EXISTS`` (it is kept);
* ``a <> b`` is not true when either side is NULL;
* ``INTERSECT`` compares row values with NULL equal to NULL (one
  group), and is distinct;
* ``count(distinct x)`` skips NULLs, a ``sum`` over no row is NULL;
* a full outer join never matches a row whose key is NULL; the row
  stays, on its own side.

The raw files have to be this configuration's: ``RawTables`` refuses a
data set whose web orders are not the source's 8 to 16 lines (the
configuration's ``assumed.web_order_lines``).  A program whose
generator still gives every order 3 lines would run query95's
self-join at a quarter of its published expansion and set a baseline
for other work.
"""

from __future__ import annotations

import datetime
import re
from typing import Callable, Dict, List, Sequence

import numpy as np
import pandas as pd

from benchmark.reference import nds_templates as _base
from benchmark.reference.nds_templates import (
    _dec, _int, _join, _keys_in, _none, _order, _param)

# Column order of the raw files: TPC-DS v3.2 specification, section 2.
# ("d": a decimal with two places, read as cents.)  The parser of
# nds_templates.py looks a table up in its module's map: the four
# tables are added to it, none of its own is touched.
_MORE_RAW_COLUMNS: Dict[str, str] = {
    "customer": (
        "c_customer_sk:i c_customer_id:s c_current_cdemo_sk:i "
        "c_current_hdemo_sk:i c_current_addr_sk:i c_first_shipto_date_sk:i "
        "c_first_sales_date_sk:i c_salutation:s c_first_name:s "
        "c_last_name:s c_preferred_cust_flag:s c_birth_day:i "
        "c_birth_month:i c_birth_year:i c_birth_country:s c_login:s "
        "c_email_address:s c_last_review_date_sk:i"),
    "customer_address": (
        "ca_address_sk:i ca_address_id:s ca_street_number:s "
        "ca_street_name:s ca_street_type:s ca_suite_number:s ca_city:s "
        "ca_county:s ca_state:s ca_zip:s ca_country:s ca_gmt_offset:d "
        "ca_location_type:s"),
    "web_returns": (
        "wr_returned_date_sk:i wr_returned_time_sk:i wr_item_sk:i "
        "wr_refunded_customer_sk:i wr_refunded_cdemo_sk:i "
        "wr_refunded_hdemo_sk:i wr_refunded_addr_sk:i "
        "wr_returning_customer_sk:i wr_returning_cdemo_sk:i "
        "wr_returning_hdemo_sk:i wr_returning_addr_sk:i wr_web_page_sk:i "
        "wr_reason_sk:i wr_order_number:i wr_return_quantity:i "
        "wr_return_amt:d wr_return_tax:d wr_return_amt_inc_tax:d wr_fee:d "
        "wr_return_ship_cost:d wr_refunded_cash:d wr_reversed_charge:d "
        "wr_account_credit:d wr_net_loss:d"),
    "web_site": (
        "web_site_sk:i web_site_id:s web_rec_start_date:s "
        "web_rec_end_date:s web_name:s web_open_date_sk:i "
        "web_close_date_sk:i web_class:s web_manager:s web_mkt_id:i "
        "web_mkt_class:s web_mkt_desc:s web_market_manager:s "
        "web_company_id:i web_company_name:s web_street_number:s "
        "web_street_name:s web_street_type:s web_suite_number:s web_city:s "
        "web_county:s web_state:s web_zip:s web_country:s "
        "web_gmt_offset:d web_tax_percentage:d"),
}
_base._RAW_COLUMNS.update(_MORE_RAW_COLUMNS)

# the fewest lines a web order of this configuration's data has
_ORDER_LINES_MIN = 8


class RawTables(_base.RawTables):
    """The raw files, if they are this configuration's: rows of one
    ``ws_order_number`` are adjacent, 8 to 16 of them."""

    def __init__(self, raw_dir: str):
        super().__init__(raw_dir)
        orders = self.frame("web_sales",
                            ["ws_order_number"]).ws_order_number.values
        runs = 1 + int(np.count_nonzero(np.diff(orders)))
        if len(orders) and len(orders) / runs < _ORDER_LINES_MIN:
            raise ValueError(
                f"the raw files under {raw_dir} are not "
                f"nds-sf1-power-joinclasses-1chip's: a web order has "
                f"{len(orders) / runs:.2f} lines on average, and the "
                f"configuration's assumed.web_order_lines says 8 to 16 "
                f"(the generator of this checkout predates them)")


# -- shared pieces -----------------------------------------------------------

def _days(raw: RawTables, pick: Callable[[pd.DataFrame], pd.Series],
          columns: Sequence[str]) -> np.ndarray:
    """d_date_sk of the date_dim rows ``pick`` selects."""
    dd = raw.frame("date_dim", ["d_date_sk", *columns])
    return dd.d_date_sk.values[pick(dd).values]


def _buyers(raw: RawTables, table: str, customer: str, date: str,
            days: np.ndarray) -> np.ndarray:
    """The (non-NULL) customers with a sale of that channel on one of
    ``days``: the key set of an EXISTS subquery over the channel."""
    f = raw.frame(table, [customer, date])
    got = f[customer].values[_keys_in(f[date].values, days)]
    return np.unique(got[~np.isnan(got)])


def _customers_where(raw: RawTables, address_column: str,
                     values: List[str]) -> pd.DataFrame:
    """customer x customer_address on the current address, the address
    column among ``values``."""
    cu = raw.frame("customer", ["c_customer_sk", "c_current_cdemo_sk",
                                "c_current_addr_sk"])
    ca = raw.frame("customer_address", ["ca_address_sk", address_column])
    addrs = ca.ca_address_sk.values[ca[address_column].isin(values).values]
    return cu[_keys_in(cu.c_current_addr_sk.values, addrs)]


def _demographic_counts(raw: RawTables, customers: pd.DataFrame,
                        columns: List[str], kinds: str):
    """The customers joined to their demographics, counted by
    ``columns``; the count is repeated after the third column and after
    each later one (cnt1, cnt2, ...), as templates 69 and 10 print it."""
    cd = raw.frame("customer_demographics", ["cd_demo_sk", *columns])
    j = _join(customers, cd, ["c_current_cdemo_sk"], ["cd_demo_sk"])
    g = j.groupby(columns, dropna=False, sort=False).size().reset_index(
        name="cnt")
    rows = []
    for r in g.itertuples(index=False):
        key = [_none(v) if k == "s" else _int(v)
               for k, v in zip(kinds, r[:-1])]
        n = int(r[-1])
        row = key[:3] + [n]
        for v in key[3:]:
            row += [v, n]
        rows.append((tuple(row), tuple(key)))
    rows = _order(rows, [((lambda r, i=i: r[1][i]), True)
                         for i in range(len(columns))], 100)
    out_kinds = kinds[:3] + "i" + "".join(k + "i" for k in kinds[3:])
    return out_kinds, [r[0] for r in rows]


def _quoted(sql: str, pattern: str) -> List[str]:
    return re.findall(r"'([^']*)'", _param(sql, pattern))


# -- the templates -----------------------------------------------------------
# Each returns (column kinds, rows).  Kinds: "s" string, "i" integer,
# "d" decimal compared exactly, "f" float compared within the limit.

def query69(raw: RawTables, sql: str):
    """EXISTS (store) AND NOT EXISTS (web) AND NOT EXISTS (catalog)."""
    year = int(_param(sql, r"d_year\s*=\s*(\d+)"))
    moy = int(_param(sql, r"d_moy\s+between\s+(\d+)"))
    states = _quoted(sql, r"ca_state\s+in\s*\(([^)]*)\)")
    days = _days(raw, lambda d: (d.d_year == year) & (d.d_moy >= moy)
                 & (d.d_moy <= moy + 2), ["d_year", "d_moy"])
    cu = _customers_where(raw, "ca_state", states)
    sk = cu.c_customer_sk.values      # the primary key: never NULL
    keep = np.isin(sk, _buyers(raw, "store_sales", "ss_customer_sk",
                               "ss_sold_date_sk", days)) \
        & ~np.isin(sk, _buyers(raw, "web_sales", "ws_bill_customer_sk",
                               "ws_sold_date_sk", days)) \
        & ~np.isin(sk, _buyers(raw, "catalog_sales", "cs_ship_customer_sk",
                               "cs_sold_date_sk", days))
    return _demographic_counts(
        raw, cu[keep], ["cd_gender", "cd_marital_status",
                        "cd_education_status", "cd_purchase_estimate",
                        "cd_credit_rating"], "sssis")


def query10(raw: RawTables, sql: str):
    """EXISTS (store) AND (EXISTS (web) OR EXISTS (catalog))."""
    year = int(_param(sql, r"d_year\s*=\s*(\d+)"))
    moy = int(_param(sql, r"d_moy\s+between\s+(\d+)"))
    counties = _quoted(sql, r"ca_county\s+in\s*\(([^)]*)\)")
    days = _days(raw, lambda d: (d.d_year == year) & (d.d_moy >= moy)
                 & (d.d_moy <= moy + 3), ["d_year", "d_moy"])
    cu = _customers_where(raw, "ca_county", counties)
    sk = cu.c_customer_sk.values
    keep = np.isin(sk, _buyers(raw, "store_sales", "ss_customer_sk",
                               "ss_sold_date_sk", days)) \
        & (np.isin(sk, _buyers(raw, "web_sales", "ws_bill_customer_sk",
                               "ws_sold_date_sk", days))
           | np.isin(sk, _buyers(raw, "catalog_sales",
                                 "cs_ship_customer_sk", "cs_sold_date_sk",
                                 days)))
    return _demographic_counts(
        raw, cu[keep], ["cd_gender", "cd_marital_status",
                        "cd_education_status", "cd_purchase_estimate",
                        "cd_credit_rating", "cd_dep_count",
                        "cd_dep_employed_count", "cd_dep_college_count"],
        "sssisiii")


def _shipped_web_sales(raw: RawTables, sql: str) -> pd.DataFrame:
    """ws1 of templates 94 and 95: web_sales shipped within 60 days of
    the first of the month, to the state, from a site of company 'pri';
    with ``n_wh``, the distinct (non-NULL) warehouses of the row's
    order over ALL of web_sales."""
    y, m, d = (int(x) for x in _param(
        sql, r"d_date\s+between\s+'(\d+-\d+-\d+)'").split("-"))
    start = datetime.date(y, m, d)
    end = start + datetime.timedelta(days=60)
    state = _param(sql, r"ca_state\s*=\s*'([^']*)'")
    dd = raw.frame("date_dim", ["d_date_sk", "d_date"])
    ok = np.array([v is not None and start.isoformat() <= v
                   <= end.isoformat() for v in dd.d_date.values])
    days = dd.d_date_sk.values[ok]
    ca = raw.frame("customer_address", ["ca_address_sk", "ca_state"])
    addrs = ca.ca_address_sk.values[(ca.ca_state == state).values]
    web = raw.frame("web_site", ["web_site_sk", "web_company_name"])
    sites = web.web_site_sk.values[(web.web_company_name == "pri").values]
    ws = raw.frame("web_sales", [
        "ws_ship_date_sk", "ws_ship_addr_sk", "ws_web_site_sk",
        "ws_order_number", "ws_warehouse_sk", "ws_ext_ship_cost",
        "ws_net_profit"])
    # nunique skips NULL warehouses; rows of a NULL order number (there
    # are none: it is the key) would equal no order
    n_wh = ws.groupby("ws_order_number").ws_warehouse_sk.nunique()
    ws = ws[_keys_in(ws.ws_ship_date_sk.values, days)
            & _keys_in(ws.ws_ship_addr_sk.values, addrs)
            & _keys_in(ws.ws_web_site_sk.values, sites)]
    return ws.assign(n_wh=n_wh.reindex(ws.ws_order_number.values).values)


def _returned_orders(raw: RawTables) -> np.ndarray:
    wr = raw.frame("web_returns", ["wr_order_number"])
    return wr.wr_order_number.values


def _order_totals(ws: pd.DataFrame):
    n = int(ws.ws_order_number.nunique())
    ship = ws.ws_ext_ship_cost.sum(min_count=1)
    profit = ws.ws_net_profit.sum(min_count=1)
    return "idd", [(n, _dec(ship), _dec(profit))]


def query94(raw: RawTables, sql: str):
    """EXISTS (another row of the order from a different warehouse)
    AND NOT EXISTS (a return of the order)."""
    ws = _shipped_web_sales(raw, sql)
    # some row of the order has a warehouse <> this row's: this row's is
    # not NULL and the order has two distinct ones
    other_wh = ws.ws_warehouse_sk.notna().values & (ws.n_wh.values >= 2)
    returned = _keys_in(ws.ws_order_number.values, _returned_orders(raw))
    return _order_totals(ws[other_wh & ~returned])


def query95(raw: RawTables, sql: str):
    """ws_wh is web_sales joined with itself on the order number where
    the two warehouses differ: its order numbers are the orders with
    two distinct (non-NULL) warehouses, which is all the two IN
    subqueries read of it."""
    ws = _shipped_web_sales(raw, sql)
    in_ws_wh = ws.n_wh.values >= 2
    returned = _keys_in(ws.ws_order_number.values, _returned_orders(raw))
    return _order_totals(ws[in_ws_wh & returned])


def _customer_items(raw: RawTables, table: str, customer: str, item: str,
                    date: str, days: np.ndarray) -> pd.DataFrame:
    """``select customer, item ... group by customer, item``: NULL is a
    group value like any other."""
    f = raw.frame(table, [customer, item, date])
    f = f[_keys_in(f[date].values, days)]
    return f[[customer, item]].drop_duplicates().set_axis(
        ["customer_sk", "item_sk"], axis=1)


def query97(raw: RawTables, sql: str):
    dms = int(_param(sql, r"d_month_seq\s+between\s+(\d+)"))
    days = _days(raw, lambda d: (d.d_month_seq >= dms)
                 & (d.d_month_seq <= dms + 11), ["d_month_seq"])
    ssci = _customer_items(raw, "store_sales", "ss_customer_sk",
                           "ss_item_sk", "ss_sold_date_sk", days)
    csci = _customer_items(raw, "catalog_sales", "cs_bill_customer_sk",
                           "cs_item_sk", "cs_sold_date_sk", days)
    # a pair with a NULL in it matches nothing and stays on its side;
    # the three sums look at customer_sk alone
    both = len(ssci.dropna().merge(csci.dropna(),
                                   on=["customer_sk", "item_sk"]))
    store_only = int(ssci.customer_sk.notna().sum()) - both
    catalog_only = int(csci.customer_sk.notna().sum()) - both
    if len(ssci) + len(csci) == 0:
        return "iii", [(None, None, None)]    # sums over no row
    return "iii", [(store_only, catalog_only, both)]


def _name_dates(raw: RawTables, table: str, customer: str, date: str,
                dd: pd.DataFrame) -> pd.DataFrame:
    """``select distinct c_last_name, c_first_name, d_date`` of one
    channel."""
    cu = raw.frame("customer", ["c_customer_sk", "c_last_name",
                                "c_first_name"])
    f = raw.frame(table, [customer, date])
    j = _join(_join(f, dd, [date], ["d_date_sk"]), cu, [customer],
              ["c_customer_sk"])
    return j[["c_last_name", "c_first_name", "d_date"]].drop_duplicates()


def query38(raw: RawTables, sql: str):
    dms = int(_param(sql, r"d_month_seq\s+between\s+(\d+)"))
    dd = raw.frame("date_dim", ["d_date_sk", "d_date", "d_month_seq"])
    dd = dd[(dd.d_month_seq >= dms) & (dd.d_month_seq <= dms + 11)][
        ["d_date_sk", "d_date"]]
    sides = [_name_dates(raw, "store_sales", "ss_customer_sk",
                         "ss_sold_date_sk", dd),
             _name_dates(raw, "catalog_sales", "cs_bill_customer_sk",
                         "cs_sold_date_sk", dd),
             _name_dates(raw, "web_sales", "ws_bill_customer_sk",
                         "ws_sold_date_sk", dd)]
    # a row value is in the INTERSECT when every side has it; each side
    # is distinct, so it is there when it occurs three times.  NULL
    # names group with NULL names (dropna=False)
    seen = pd.concat(sides, ignore_index=True).groupby(
        ["c_last_name", "c_first_name", "d_date"], dropna=False,
        sort=False).size()
    return "i", [(int((seen.values == len(sides)).sum()),)]


TEMPLATES: Dict[str, Callable] = {
    "query10": query10, "query38": query38, "query69": query69,
    "query94": query94, "query95": query95, "query97": query97,
}


def answer(raw: RawTables, template: str, sql: str,
           floats: str = "float64"):
    """(column kinds, rows) the template asks for on this data; as
    ``nds_templates.answer`` (``floats`` moves nothing: no template
    here has a float column)."""
    if template not in TEMPLATES:
        raise KeyError(f"the plain reference has no template {template!r}")
    return TEMPLATES[template](raw, sql)
