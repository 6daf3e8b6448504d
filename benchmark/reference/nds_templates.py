"""Plain reference for the NDS query templates the benchmark's cells run.

A straightforward implementation of the same SQL semantics in numpy and
pandas, independent of the system under test: it imports nothing of
``ndstpu``, parses the generator's raw ``.dat`` files itself (so the
program's transcode, loader, dictionaries and catalog are on the tested
side), reads each query's parameters from the rendered SQL text with a
regular expression of its own, and computes the answer the TPC-DS
template asks for.  Decimal columns are exact integers of cents
throughout; averages and ratios are float64 (``ft``; the float32
control computes them in float32).

One function per template.  A later configuration that runs further
templates adds functions to a further file like this one and names it
in its configuration's ``reference``.

Departures from full SQL generality, each safe for these templates:
string ordering is by code point; ``ORDER BY`` puts NULLs first
ascending and last descending (Spark's default, which the NDS reference
runs on); the comparison sorts both sides canonically before it
compares, so an order among exact ties is not judged.
"""

from __future__ import annotations

import datetime
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.csv as pacsv

# Column order of the raw files: TPC-DS v3.2 specification, section 2
# (the generator writes every column, '|'-terminated).  "d" marks a
# decimal(7,2) money column, "s" a string, "i" an integer.
_RAW_COLUMNS: Dict[str, str] = {
    "store_sales": (
        "ss_sold_date_sk:i ss_sold_time_sk:i ss_item_sk:i ss_customer_sk:i "
        "ss_cdemo_sk:i ss_hdemo_sk:i ss_addr_sk:i ss_store_sk:i "
        "ss_promo_sk:i ss_ticket_number:i ss_quantity:i ss_wholesale_cost:d "
        "ss_list_price:d ss_sales_price:d ss_ext_discount_amt:d "
        "ss_ext_sales_price:d ss_ext_wholesale_cost:d ss_ext_list_price:d "
        "ss_ext_tax:d ss_coupon_amt:d ss_net_paid:d ss_net_paid_inc_tax:d "
        "ss_net_profit:d"),
    "store_returns": (
        "sr_returned_date_sk:i sr_return_time_sk:i sr_item_sk:i "
        "sr_customer_sk:i sr_cdemo_sk:i sr_hdemo_sk:i sr_addr_sk:i "
        "sr_store_sk:i sr_reason_sk:i sr_ticket_number:i "
        "sr_return_quantity:i sr_return_amt:d sr_return_tax:d "
        "sr_return_amt_inc_tax:d sr_fee:d sr_return_ship_cost:d "
        "sr_refunded_cash:d sr_reversed_charge:d sr_store_credit:d "
        "sr_net_loss:d"),
    "catalog_sales": (
        "cs_sold_date_sk:i cs_sold_time_sk:i cs_ship_date_sk:i "
        "cs_bill_customer_sk:i cs_bill_cdemo_sk:i cs_bill_hdemo_sk:i "
        "cs_bill_addr_sk:i cs_ship_customer_sk:i cs_ship_cdemo_sk:i "
        "cs_ship_hdemo_sk:i cs_ship_addr_sk:i cs_call_center_sk:i "
        "cs_catalog_page_sk:i cs_ship_mode_sk:i cs_warehouse_sk:i "
        "cs_item_sk:i cs_promo_sk:i cs_order_number:i cs_quantity:i "
        "cs_wholesale_cost:d cs_list_price:d cs_sales_price:d "
        "cs_ext_discount_amt:d cs_ext_sales_price:d "
        "cs_ext_wholesale_cost:d cs_ext_list_price:d cs_ext_tax:d "
        "cs_coupon_amt:d cs_ext_ship_cost:d cs_net_paid:d "
        "cs_net_paid_inc_tax:d cs_net_paid_inc_ship:d "
        "cs_net_paid_inc_ship_tax:d cs_net_profit:d"),
    "web_sales": (
        "ws_sold_date_sk:i ws_sold_time_sk:i ws_ship_date_sk:i ws_item_sk:i "
        "ws_bill_customer_sk:i ws_bill_cdemo_sk:i ws_bill_hdemo_sk:i "
        "ws_bill_addr_sk:i ws_ship_customer_sk:i ws_ship_cdemo_sk:i "
        "ws_ship_hdemo_sk:i ws_ship_addr_sk:i ws_web_page_sk:i "
        "ws_web_site_sk:i ws_ship_mode_sk:i ws_warehouse_sk:i ws_promo_sk:i "
        "ws_order_number:i ws_quantity:i ws_wholesale_cost:d "
        "ws_list_price:d ws_sales_price:d ws_ext_discount_amt:d "
        "ws_ext_sales_price:d ws_ext_wholesale_cost:d ws_ext_list_price:d "
        "ws_ext_tax:d ws_coupon_amt:d ws_ext_ship_cost:d ws_net_paid:d "
        "ws_net_paid_inc_tax:d ws_net_paid_inc_ship:d "
        "ws_net_paid_inc_ship_tax:d ws_net_profit:d"),
    "item": (
        "i_item_sk:i i_item_id:s i_rec_start_date:s i_rec_end_date:s "
        "i_item_desc:s i_current_price:d i_wholesale_cost:d i_brand_id:i "
        "i_brand:s i_class_id:i i_class:s i_category_id:i i_category:s "
        "i_manufact_id:i i_manufact:s i_size:s i_formulation:s i_color:s "
        "i_units:s i_container:s i_manager_id:i i_product_name:s"),
    "date_dim": (
        "d_date_sk:i d_date_id:s d_date:s d_month_seq:i d_week_seq:i "
        "d_quarter_seq:i d_year:i d_dow:i d_moy:i d_dom:i d_qoy:i "
        "d_fy_year:i d_fy_quarter_seq:i d_fy_week_seq:i d_day_name:s "
        "d_quarter_name:s d_holiday:s d_weekend:s d_following_holiday:s "
        "d_first_dom:i d_last_dom:i d_same_day_ly:i d_same_day_lq:i "
        "d_current_day:s d_current_week:s d_current_month:s "
        "d_current_quarter:s d_current_year:s"),
    "time_dim": (
        "t_time_sk:i t_time_id:s t_time:i t_hour:i t_minute:i t_second:i "
        "t_am_pm:s t_shift:s t_sub_shift:s t_meal_time:s"),
    "customer_demographics": (
        "cd_demo_sk:i cd_gender:s cd_marital_status:s "
        "cd_education_status:s cd_purchase_estimate:i cd_credit_rating:s "
        "cd_dep_count:i cd_dep_employed_count:i cd_dep_college_count:i"),
    "household_demographics": (
        "hd_demo_sk:i hd_income_band_sk:i hd_buy_potential:s "
        "hd_dep_count:i hd_vehicle_count:i"),
    "promotion": (
        "p_promo_sk:i p_promo_id:s p_start_date_sk:i p_end_date_sk:i "
        "p_item_sk:i p_cost:d p_response_target:i p_promo_name:s "
        "p_channel_dmail:s p_channel_email:s p_channel_catalog:s "
        "p_channel_tv:s p_channel_radio:s p_channel_press:s "
        "p_channel_event:s p_channel_demo:s p_channel_details:s "
        "p_purpose:s p_discount_active:s"),
    "store": (
        "s_store_sk:i s_store_id:s s_rec_start_date:s s_rec_end_date:s "
        "s_closed_date_sk:i s_store_name:s s_number_employees:i "
        "s_floor_space:i s_hours:s s_manager:s s_market_id:i "
        "s_geography_class:s s_market_desc:s s_market_manager:s "
        "s_division_id:i s_division_name:s s_company_id:i "
        "s_company_name:s s_street_number:s s_street_name:s "
        "s_street_type:s s_suite_number:s s_city:s s_county:s s_state:s "
        "s_zip:s s_country:s s_gmt_offset:d s_tax_precentage:d"),
    "reason": "r_reason_sk:i r_reason_id:s r_reason_desc:s",
}

TABLES = tuple(_RAW_COLUMNS)


class RawTables:
    """The generator's raw files, read column by column on first use.

    Integers and cents come back as float64 with NaN for NULL (every
    value is an integer below 2**53, so sums of them are exact);
    strings as object with None for NULL."""

    def __init__(self, raw_dir: str):
        self.raw_dir = raw_dir
        self._cols: Dict[Tuple[str, str], np.ndarray] = {}

    def frame(self, table: str, columns: Sequence[str]) -> pd.DataFrame:
        missing = [c for c in columns if (table, c) not in self._cols]
        if missing:
            self._read(table, missing)
        return pd.DataFrame({c: self._cols[(table, c)] for c in columns})

    def _read(self, table: str, columns: List[str]) -> None:
        spec = [f.split(":") for f in _RAW_COLUMNS[table].split()]
        kinds = dict(spec)
        names = [n for n, _ in spec] + ["_terminator"]
        files = sorted(glob.glob(
            os.path.join(self.raw_dir, table, "*.dat")))
        if not files:
            raise FileNotFoundError(
                f"no raw files of {table} under {self.raw_dir}")
        types = {c: (pa.string() if kinds[c] == "s" else pa.float64())
                 for c in columns}
        parts = [pacsv.read_csv(
            f, read_options=pacsv.ReadOptions(column_names=names),
            parse_options=pacsv.ParseOptions(delimiter="|",
                                             quote_char=False),
            convert_options=pacsv.ConvertOptions(
                include_columns=list(columns), column_types=types,
                strings_can_be_null=True, null_values=[""]))
            for f in files]
        t = pa.concat_tables(parts)
        for c in columns:
            col = t.column(c)
            if kinds[c] == "s":
                enc = col.combine_chunks().dictionary_encode()
                words = np.array(enc.dictionary.to_pylist() + [None],
                                 dtype=object)
                codes = enc.indices.to_numpy(zero_copy_only=False)
                codes = np.where(np.isnan(codes), len(words) - 1,
                                 codes).astype(np.int64) \
                    if codes.dtype.kind == "f" else codes
                arr = words[codes]
            else:
                arr = col.to_numpy(zero_copy_only=False).astype(np.float64)
                if kinds[c] == "d":
                    arr = np.rint(arr * 100.0)   # exact cents
            self._cols[(table, c)] = arr


# -- small SQL helpers -------------------------------------------------------

def _keys_in(values: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """SQL equi-join membership: NULL matches nothing."""
    return np.isin(values, keys[~np.isnan(keys)])


def _join(left: pd.DataFrame, right: pd.DataFrame, left_on: List[str],
          right_on: List[str]) -> pd.DataFrame:
    """Inner equi-join; rows with a NULL key on either side drop out
    (pandas alone would match NaN with NaN)."""
    left = left.dropna(subset=left_on)
    right = right.dropna(subset=right_on)
    return left.merge(right, left_on=left_on, right_on=right_on,
                      how="inner")


def _sum(frame: pd.DataFrame, by: List[str],
         cols: List[str]) -> pd.DataFrame:
    """GROUP BY with SUM: NULL keys group together, a sum over only
    NULLs is NULL."""
    return frame.groupby(by, dropna=False, sort=False)[cols].sum(
        min_count=1).reset_index()


def _none(v):
    if v is None:
        return None
    if isinstance(v, float) and np.isnan(v):
        return None
    return v


def _order(rows: List[tuple], keys: List[Tuple[Callable, bool]],
           limit: Optional[int]) -> List[tuple]:
    """ORDER BY (NULLs first ascending, last descending) then LIMIT."""
    def sort_key(row):
        out = []
        for fn, asc in keys:
            v = fn(row)
            if asc:
                out.append((0,) if v is None else (1, v))
            else:
                out.append((1,) if v is None else (0, -v))
        return tuple(out)
    rows = sorted(rows, key=sort_key)
    return rows[:limit] if limit is not None else rows


def _int(v):
    v = _none(v)
    return None if v is None else int(v)


def _dec(v):
    """Cents to the value the system prints: int64 / 100 in float64."""
    v = _none(v)
    return None if v is None else float(np.float64(int(v)) / 100)


def _flt(v):
    v = _none(v)
    return None if v is None else float(v)


def _param(sql: str, pattern: str) -> str:
    m = re.search(pattern, sql, re.IGNORECASE | re.DOTALL)
    if m is None:
        raise ValueError(f"parameter /{pattern}/ not found in the query")
    return m.group(1)


# -- the templates -----------------------------------------------------------
# Each returns (column kinds, rows).  Kinds: "s" string, "i" integer,
# "d" decimal compared exactly, "f" float compared within the limit.

def query55(raw: RawTables, sql: str, ft=np.float64):
    manager = int(_param(sql, r"i_manager_id\s*=\s*(\d+)"))
    moy = int(_param(sql, r"d_moy\s*=\s*(\d+)"))
    year = int(_param(sql, r"d_year\s*=\s*(\d+)"))
    dd = raw.frame("date_dim", ["d_date_sk", "d_moy", "d_year"])
    it = raw.frame("item", ["i_item_sk", "i_brand_id", "i_brand",
                            "i_manager_id"])
    ss = raw.frame("store_sales", ["ss_sold_date_sk", "ss_item_sk",
                                   "ss_ext_sales_price"])
    days = dd[(dd.d_moy == moy) & (dd.d_year == year)].d_date_sk.values
    it = it[it.i_manager_id == manager]
    ss = ss[_keys_in(ss.ss_sold_date_sk.values, days)]
    j = _join(ss, it, ["ss_item_sk"], ["i_item_sk"])
    g = _sum(j, ["i_brand", "i_brand_id"], ["ss_ext_sales_price"])
    rows = [(_int(r.i_brand_id), _none(r.i_brand),
             _dec(r.ss_ext_sales_price)) for r in g.itertuples()]
    return "isd", _order(rows, [(lambda r: r[2], False),
                                (lambda r: r[0], True)], 100)


def query3(raw: RawTables, sql: str, ft=np.float64):
    manufact = int(_param(sql, r"i_manufact_id\s*=\s*(\d+)"))
    moy = int(_param(sql, r"d_moy\s*=\s*(\d+)"))
    dd = raw.frame("date_dim", ["d_date_sk", "d_moy", "d_year"])
    it = raw.frame("item", ["i_item_sk", "i_brand_id", "i_brand",
                            "i_manufact_id"])
    ss = raw.frame("store_sales", ["ss_sold_date_sk", "ss_item_sk",
                                   "ss_ext_sales_price"])
    dd = dd[dd.d_moy == moy]
    it = it[it.i_manufact_id == manufact]
    ss = ss[_keys_in(ss.ss_sold_date_sk.values, dd.d_date_sk.values)]
    j = _join(_join(ss, it, ["ss_item_sk"], ["i_item_sk"]),
              dd, ["ss_sold_date_sk"], ["d_date_sk"])
    g = _sum(j, ["d_year", "i_brand_id", "i_brand"],
             ["ss_ext_sales_price"])
    rows = [(_int(r.d_year), _int(r.i_brand_id), _none(r.i_brand),
             _dec(r.ss_ext_sales_price)) for r in g.itertuples()]
    return "iisd", _order(rows, [(lambda r: r[0], True),
                                 (lambda r: r[3], False),
                                 (lambda r: r[1], True)], 100)


def query96(raw: RawTables, sql: str, ft=np.float64):
    hour = int(_param(sql, r"t_hour\s*=\s*(\d+)"))
    dep = int(_param(sql, r"hd_dep_count\s*=\s*(\d+)"))
    td = raw.frame("time_dim", ["t_time_sk", "t_hour", "t_minute"])
    hd = raw.frame("household_demographics", ["hd_demo_sk",
                                               "hd_dep_count"])
    st = raw.frame("store", ["s_store_sk", "s_store_name"])
    ss = raw.frame("store_sales", ["ss_sold_time_sk", "ss_hdemo_sk",
                                   "ss_store_sk"])
    times = td[(td.t_hour == hour) & (td.t_minute >= 30)].t_time_sk.values
    demos = hd[hd.hd_dep_count == dep].hd_demo_sk.values
    stores = st[st.s_store_name == "ese"].s_store_sk.values
    n = int((_keys_in(ss.ss_sold_time_sk.values, times)
             & _keys_in(ss.ss_hdemo_sk.values, demos)
             & _keys_in(ss.ss_store_sk.values, stores)).sum())
    return "i", [(n,)]


def query7(raw: RawTables, sql: str, ft=np.float64):
    gen = _param(sql, r"cd_gender\s*=\s*'([^']*)'")
    ms = _param(sql, r"cd_marital_status\s*=\s*'([^']*)'")
    es = _param(sql, r"cd_education_status\s*=\s*'([^']*)'")
    year = int(_param(sql, r"d_year\s*=\s*(\d+)"))
    cd = raw.frame("customer_demographics", [
        "cd_demo_sk", "cd_gender", "cd_marital_status",
        "cd_education_status"])
    dd = raw.frame("date_dim", ["d_date_sk", "d_year"])
    it = raw.frame("item", ["i_item_sk", "i_item_id"])
    pr = raw.frame("promotion", ["p_promo_sk", "p_channel_email",
                                 "p_channel_event"])
    ss = raw.frame("store_sales", [
        "ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk", "ss_promo_sk",
        "ss_quantity", "ss_list_price", "ss_coupon_amt",
        "ss_sales_price"])
    demos = cd[(cd.cd_gender == gen) & (cd.cd_marital_status == ms)
               & (cd.cd_education_status == es)].cd_demo_sk.values
    days = dd[dd.d_year == year].d_date_sk.values
    promos = pr[(pr.p_channel_email == "N")
                | (pr.p_channel_event == "N")].p_promo_sk.values
    ss = ss[_keys_in(ss.ss_sold_date_sk.values, days)
            & _keys_in(ss.ss_cdemo_sk.values, demos)
            & _keys_in(ss.ss_promo_sk.values, promos)]
    j = _join(ss, it, ["ss_item_sk"], ["i_item_sk"])
    avgs = ["ss_quantity", "ss_list_price", "ss_coupon_amt",
            "ss_sales_price"]
    j = j.astype({c: ft for c in avgs})
    g = j.groupby("i_item_id", dropna=False, sort=False)[
        avgs].mean().reset_index()
    rows = [(_none(r.i_item_id), _flt(r.ss_quantity),
             _cents_avg(r.ss_list_price, ft),
             _cents_avg(r.ss_coupon_amt, ft),
             _cents_avg(r.ss_sales_price, ft)) for r in g.itertuples()]
    return "sffff", _order(rows, [(lambda r: r[0], True)], 100)


def _cents_avg(v, ft):
    v = _none(v)
    return None if v is None else float(ft(v) / ft(100))


def query12(raw: RawTables, sql: str, ft=np.float64):
    cats = re.findall(r"'([^']*)'", _param(
        sql, r"i_category\s+in\s*\(([^)]*)\)"))
    start = datetime.date.fromisoformat(_param(
        sql, r"between\s+cast\('(\d{4}-\d{2}-\d{2})'\s+as\s+date\)"))
    end = start + datetime.timedelta(days=30)
    dd = raw.frame("date_dim", ["d_date_sk", "d_date"])
    it = raw.frame("item", ["i_item_sk", "i_item_id", "i_item_desc",
                            "i_category", "i_class", "i_current_price"])
    ws = raw.frame("web_sales", ["ws_item_sk", "ws_sold_date_sk",
                                 "ws_ext_sales_price"])
    dates = dd.d_date.values
    ok = np.array([d is not None and start.isoformat() <= d
                   <= end.isoformat() for d in dates])
    days = dd.d_date_sk.values[ok]
    it = it[it.i_category.isin(cats)]
    ws = ws[_keys_in(ws.ws_sold_date_sk.values, days)]
    j = _join(ws, it, ["ws_item_sk"], ["i_item_sk"])
    g = _sum(j, ["i_item_id", "i_item_desc", "i_category", "i_class",
                 "i_current_price"], ["ws_ext_sales_price"])
    total = g.groupby("i_class", dropna=False)[
        "ws_ext_sales_price"].transform(lambda s: s.sum(min_count=1))
    ratio = g.ws_ext_sales_price.astype(ft) * ft(100) / total.astype(ft)
    rows = [(_none(r.i_item_id), _none(r.i_item_desc), _none(r.i_category),
             _none(r.i_class), _dec(r.i_current_price),
             _dec(r.ws_ext_sales_price), _flt(q))
            for r, q in zip(g.itertuples(), ratio)]
    return "ssssddf", _order(rows, [
        (lambda r: r[2], True), (lambda r: r[3], True),
        (lambda r: r[0], True), (lambda r: r[1], True),
        (lambda r: r[6], True)], 100)


def query86(raw: RawTables, sql: str, ft=np.float64):
    dms = int(_param(sql, r"d_month_seq\s+between\s+(\d+)"))
    dd = raw.frame("date_dim", ["d_date_sk", "d_month_seq"])
    it = raw.frame("item", ["i_item_sk", "i_category", "i_class"])
    ws = raw.frame("web_sales", ["ws_sold_date_sk", "ws_item_sk",
                                 "ws_net_paid"])
    days = dd[(dd.d_month_seq >= dms)
              & (dd.d_month_seq <= dms + 11)].d_date_sk.values
    ws = ws[_keys_in(ws.ws_sold_date_sk.values, days)]
    j = _join(ws, it, ["ws_item_sk"], ["i_item_sk"])
    # ROLLUP(i_category, i_class): three grouping sets; the last value
    # of each row is (grouping(i_category), grouping(i_class))
    leaf = _sum(j, ["i_category", "i_class"], ["ws_net_paid"])
    by_cat = _sum(j, ["i_category"], ["ws_net_paid"])
    all_sum = j.ws_net_paid.sum(min_count=1) if len(j) else None
    groups = [(_none(r.ws_net_paid), _none(r.i_category),
               _none(r.i_class), 0, 0) for r in leaf.itertuples()]
    groups += [(_none(r.ws_net_paid), _none(r.i_category), None, 0, 1)
               for r in by_cat.itertuples()]
    groups.append((_none(all_sum), None, None, 1, 1))
    # rank() over (partition by lochierarchy,
    #              case when grouping(i_class) = 0 then i_category end
    #              order by sum desc)
    parts: Dict[tuple, List[tuple]] = {}
    for g in groups:
        loch = g[3] + g[4]
        parts.setdefault((loch, g[1] if g[4] == 0 else None), []).append(g)
    rows = []
    for (loch, _cat), members in parts.items():
        # descending, NULL sums last; equal sums share a rank
        members.sort(key=lambda g: (g[0] is None,
                                    -(g[0] if g[0] is not None else 0)))
        rank = 0
        prev = object()
        for pos, g in enumerate(members, start=1):
            if g[0] != prev:
                rank, prev = pos, g[0]
            rows.append((_dec(g[0]), g[1], g[2], loch, rank))
    return "dssii", _order(rows, [
        (lambda r: r[3], False),
        (lambda r: r[1] if r[3] == 0 else None, True),
        (lambda r: r[4], True)], 100)


def query25(raw: RawTables, sql: str, ft=np.float64):
    year = int(_param(sql, r"d1\.d_year\s*=\s*(\d+)"))
    dd = raw.frame("date_dim", ["d_date_sk", "d_moy", "d_year"])
    it = raw.frame("item", ["i_item_sk", "i_item_id", "i_item_desc"])
    st = raw.frame("store", ["s_store_sk", "s_store_id", "s_store_name"])
    ss = raw.frame("store_sales", [
        "ss_sold_date_sk", "ss_item_sk", "ss_store_sk", "ss_customer_sk",
        "ss_ticket_number", "ss_net_profit"])
    sr = raw.frame("store_returns", [
        "sr_returned_date_sk", "sr_item_sk", "sr_customer_sk",
        "sr_ticket_number", "sr_net_loss"])
    cs = raw.frame("catalog_sales", [
        "cs_sold_date_sk", "cs_bill_customer_sk", "cs_item_sk",
        "cs_net_profit"])
    d1 = dd[(dd.d_moy == 4) & (dd.d_year == year)].d_date_sk.values
    d23 = dd[(dd.d_moy >= 4) & (dd.d_moy <= 10)
             & (dd.d_year == year)].d_date_sk.values
    ss = ss[_keys_in(ss.ss_sold_date_sk.values, d1)]
    sr = sr[_keys_in(sr.sr_returned_date_sk.values, d23)]
    cs = cs[_keys_in(cs.cs_sold_date_sk.values, d23)]
    j = _join(ss, sr, ["ss_customer_sk", "ss_item_sk", "ss_ticket_number"],
              ["sr_customer_sk", "sr_item_sk", "sr_ticket_number"])
    j = _join(j, cs, ["sr_customer_sk", "sr_item_sk"],
              ["cs_bill_customer_sk", "cs_item_sk"])
    j = _join(j, it, ["ss_item_sk"], ["i_item_sk"])
    j = _join(j, st, ["ss_store_sk"], ["s_store_sk"])
    g = _sum(j, ["i_item_id", "i_item_desc", "s_store_id", "s_store_name"],
             ["ss_net_profit", "sr_net_loss", "cs_net_profit"])
    rows = [(_none(r.i_item_id), _none(r.i_item_desc), _none(r.s_store_id),
             _none(r.s_store_name), _dec(r.ss_net_profit),
             _dec(r.sr_net_loss), _dec(r.cs_net_profit))
            for r in g.itertuples()]
    return "ssssddd", _order(rows, [
        (lambda r: r[0], True), (lambda r: r[1], True),
        (lambda r: r[2], True), (lambda r: r[3], True)], 100)


def query9(raw: RawTables, sql: str, ft=np.float64):
    limits = [int(x) for x in re.findall(
        r"between\s+\d+\s+and\s+\d+\)\s*>\s*(\d+)", sql, re.IGNORECASE)]
    if len(limits) != 5:
        raise ValueError(f"query9: found {len(limits)} thresholds, not 5")
    rs = raw.frame("reason", ["r_reason_sk"])
    ss = raw.frame("store_sales", ["ss_quantity", "ss_ext_discount_amt",
                                   "ss_net_paid"])
    qty = ss.ss_quantity.values
    row = []
    for k, limit in enumerate(limits):
        lo, hi = 20 * k + 1, 20 * k + 20
        mask = (qty >= lo) & (qty <= hi)
        col = ss.ss_ext_discount_amt if int(mask.sum()) > limit \
            else ss.ss_net_paid
        vals = col.values[mask]
        vals = vals[~np.isnan(vals)]
        row.append(float(vals.astype(ft).sum(dtype=ft) / ft(len(vals))
                         / ft(100)) if len(vals) else None)
    n = int((rs.r_reason_sk == 1).sum())
    return "fffff", [tuple(row)] * n


TEMPLATES: Dict[str, Callable] = {
    "query3": query3, "query7": query7, "query9": query9,
    "query12": query12, "query25": query25, "query55": query55,
    "query86": query86, "query96": query96,
}


def answer(raw: RawTables, template: str, sql: str,
           floats: str = "float64"):
    """(column kinds, rows) the template asks for on this data.
    ``floats`` is the type the float columns (averages, ratios) are
    computed in: the configurations state float64; "float32" is the
    control that ``correct`` has to refuse."""
    if template not in TEMPLATES:
        raise KeyError(f"the plain reference has no template {template!r}")
    return TEMPLATES[template](raw, sql, ft=np.dtype(floats).type)
