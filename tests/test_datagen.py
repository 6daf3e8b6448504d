"""Data generator tests: determinism, chunking, schema conformance, refresh sets."""

import os
import subprocess

import pytest

from ndstpu import schema
from ndstpu.check import check_build


@pytest.fixture(scope="module")
def tool():
    return str(check_build())


def run_gen(tool, outdir, *extra):
    outdir.mkdir(parents=True, exist_ok=True)
    subprocess.run([tool, "-scale", "0.01", "-dir", str(outdir), *extra],
                   check=True)


def test_all_tables_generated(tool, tmp_path):
    run_gen(tool, tmp_path)
    for t in schema.SOURCE_TABLE_NAMES:
        assert (tmp_path / f"{t}_1_1.dat").exists(), t


def test_field_counts_match_schema(tool, tmp_path):
    run_gen(tool, tmp_path)
    schemas = schema.get_schemas()
    for t, s in schemas.items():
        path = tmp_path / f"{t}_1_1.dat"
        with open(path) as f:
            line = f.readline().rstrip("\n")
        # dsdgen convention: trailing '|' terminator -> n fields + empty tail
        fields = line.split("|")
        assert fields[-1] == "", f"{t}: missing trailing pipe"
        assert len(fields) - 1 == len(s), (
            f"{t}: {len(fields) - 1} fields vs {len(s)} schema columns")


def test_chunking_is_deterministic(tool, tmp_path):
    one = tmp_path / "one"
    four = tmp_path / "four"
    run_gen(tool, one, "-table", "customer")
    for c in "1234":
        run_gen(tool, four, "-parallel", "4", "-child", c, "-table", "customer")
    whole = (one / "customer_1_1.dat").read_text()
    parts = "".join(
        (four / f"customer_{c}_4.dat").read_text() for c in "1234")
    assert whole == parts


def test_seed_changes_content(tool, tmp_path):
    a = tmp_path / "a"
    b = tmp_path / "b"
    run_gen(tool, a, "-table", "item")
    run_gen(tool, b, "-table", "item", "-seed", "42")
    assert (a / "item_1_1.dat").read_text() != (b / "item_1_1.dat").read_text()


def test_referential_integrity_returns(tool, tmp_path):
    """store_returns rows must reference (ticket, item) pairs that exist in
    store_sales — the generator re-derives parent sale values."""
    run_gen(tool, tmp_path, "-table", "store_sales")
    run_gen(tool, tmp_path, "-table", "store_returns")
    sales = set()
    for line in (tmp_path / "store_sales_1_1.dat").read_text().splitlines():
        f = line.split("|")
        sales.add((f[9], f[2]))  # (ss_ticket_number, ss_item_sk)
    n = 0
    for line in (tmp_path / "store_returns_1_1.dat").read_text().splitlines():
        f = line.split("|")
        assert (f[9], f[2]) in sales  # (sr_ticket_number, sr_item_sk)
        n += 1
    assert n > 0


def test_date_dim_calendar(tool, tmp_path):
    run_gen(tool, tmp_path, "-table", "date_dim")
    lines = (tmp_path / "date_dim_1_1.dat").read_text().splitlines()
    assert len(lines) == 73049
    first = lines[0].split("|")
    assert first[0] == "2415022" and first[2] == "1900-01-02"
    assert first[14] == "Tuesday"
    # spot-check a known date: 2000-01-01 was a Saturday
    by_date = {l.split("|")[2]: l.split("|") for l in lines[36000:37500]}
    row = by_date["2000-01-01"]
    assert row[14] == "Saturday" and row[6] == "2000"


def test_update_set(tool, tmp_path):
    run_gen(tool, tmp_path, "-update", "1")
    for t in schema.MAINTENANCE_TABLE_NAMES:
        assert (tmp_path / f"{t}_1_1.dat").exists(), t
    # delete tables: 3 date ranges each, date1 <= date2
    for t in ("delete", "inventory_delete"):
        lines = (tmp_path / f"{t}_1_1.dat").read_text().splitlines()
        assert len(lines) == 3
        for line in lines:
            d1, d2, _ = line.split("|")
            assert d1 <= d2


def test_driver_cli(tool, tmp_path):
    out = tmp_path / "data"
    env = dict(os.environ, PYTHONPATH=os.getcwd())
    subprocess.run(
        ["python", "-m", "ndstpu.datagen.driver", "local", "0.01", "2",
         str(out)],
        check=True, env=env)
    # per-table dirs with chunk files inside
    assert (out / "store_sales" / "store_sales_1_2.dat").exists()
    assert (out / "store_sales" / "store_sales_2_2.dat").exists()
    assert (out / "date_dim" / "date_dim_1_2.dat").exists()
    # small tables may produce fewer chunks but the dir must exist
    assert (out / "warehouse").is_dir()


def test_driver_range_merge(tool, tmp_path):
    out = tmp_path / "data"
    env = dict(os.environ, PYTHONPATH=os.getcwd())
    for rng in ("1,2", "3,4"):
        subprocess.run(
            ["python", "-m", "ndstpu.datagen.driver", "local", "0.01", "4",
             str(out), "--range", rng],
            check=True, env=env)
    files = sorted(os.listdir(out / "customer"))
    assert files == [f"customer_{i}_4.dat" for i in (1, 2, 3, 4)]
    assert not [d for d in os.listdir(out) if d.startswith("_temp_")]


def test_pod_mode_byte_identical_to_local(tmp_path):
    """`pod` mode (host-list fan-out, GenTable.java analog) over a
    shared directory must produce byte-identical output to a local run
    with the same scale/parallel: chunks are position-deterministic, so
    the host assignment cannot matter. Uses `--launcher 'bash -c'` so
    both 'hosts' are this machine."""
    import filecmp

    env = dict(os.environ, PYTHONPATH=os.getcwd())
    local = tmp_path / "local"
    pod = tmp_path / "pod"
    subprocess.run(["python", "-m", "ndstpu.datagen.driver", "local",
                    "0.002", "4", str(local)], check=True, env=env,
                   stdout=subprocess.DEVNULL)
    subprocess.run(["python", "-m", "ndstpu.datagen.driver", "pod",
                    "0.002", "4", str(pod),
                    "--hosts", "hostA,hostB",
                    "--launcher", "bash -c"],
                   check=True, env=env, stdout=subprocess.DEVNULL)
    tables = sorted(os.listdir(local))
    assert sorted(os.listdir(pod)) == tables
    for table in tables:
        lfiles = sorted(os.listdir(local / table))
        pfiles = sorted(os.listdir(pod / table))
        assert pfiles == lfiles, table
        for f in lfiles:
            assert filecmp.cmp(local / table / f, pod / table / f,
                               shallow=False), f"{table}/{f} differs"


def test_pod_mode_failure_reports_slices(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.getcwd())
    r = subprocess.run(
        ["python", "-m", "ndstpu.datagen.driver", "pod", "0.002", "4",
         str(tmp_path / "x"), "--hosts", "h1",
         "--launcher", "false"],  # launcher that always fails
        env=env, capture_output=True, text=True)
    assert r.returncode != 0
    assert "re-run those slices" in r.stderr


def _sizes(tool, sf):
    out = subprocess.run([tool, "-sizes", str(sf)], capture_output=True,
                         text=True, check=True).stdout
    return {ln.split("|")[0]: int(ln.split("|")[1])
            for ln in out.strip().splitlines()}


def test_spec_step_table_cardinalities(tool):
    """Row counts follow the published TPC-DS step table (spec Table
    3-2) at SF 1/10/100 — dsdgen -scale semantics, wrapped by the
    reference at tpcds-gen/.../GenTable.java:49-167.  A lin/sqrt
    heuristic diverges from the NDS workload above SF1 (item must JUMP
    to 102,000 at SF10, not scale to ~57k)."""
    sf1 = _sizes(tool, 1)
    assert sf1["store_sales"] == 2880404
    assert sf1["store_returns"] == 287514
    assert sf1["catalog_sales"] == 1441548
    assert sf1["catalog_returns"] == 144067
    assert sf1["web_sales"] == 719384
    assert sf1["web_returns"] == 71763
    assert sf1["inventory"] == 11745000
    assert sf1["item"] == 18000
    assert sf1["customer"] == 100000
    assert sf1["customer_address"] == 50000
    assert sf1["store"] == 12
    assert sf1["warehouse"] == 5
    assert sf1["web_site"] == 30
    assert sf1["web_page"] == 60
    assert sf1["promotion"] == 300
    assert sf1["call_center"] == 6
    assert sf1["catalog_page"] == 11718
    assert sf1["reason"] == 35

    sf10 = _sizes(tool, 10)
    assert sf10["store_sales"] == 28800991
    assert sf10["store_returns"] == 2875432
    assert sf10["catalog_sales"] == 14401261
    assert sf10["catalog_returns"] == 1439749
    assert sf10["web_sales"] == 7197566
    assert sf10["web_returns"] == 719217
    assert sf10["inventory"] == 133110000
    assert sf10["item"] == 102000
    assert sf10["customer"] == 500000
    assert sf10["customer_address"] == 250000
    assert sf10["store"] == 102
    assert sf10["warehouse"] == 10
    assert sf10["web_site"] == 42
    assert sf10["web_page"] == 200
    assert sf10["promotion"] == 500
    assert sf10["call_center"] == 24
    assert sf10["catalog_page"] == 12000
    assert sf10["reason"] == 45

    sf100 = _sizes(tool, 100)
    assert sf100["store_sales"] == 287997024
    assert sf100["store_returns"] == 28795080
    assert sf100["catalog_sales"] == 143997065
    assert sf100["catalog_returns"] == 14404374
    assert sf100["web_sales"] == 72001237
    assert sf100["web_returns"] == 7197670
    assert sf100["inventory"] == 399330000
    assert sf100["item"] == 204000
    assert sf100["customer"] == 2000000
    assert sf100["customer_address"] == 1000000
    assert sf100["store"] == 402
    assert sf100["warehouse"] == 15
    # web_site is non-monotonic in the spec table: 42 at SF10, 24 at
    # SF100 — the canary that the model is table-driven, not a curve
    assert sf100["web_site"] == 24
    assert sf100["web_page"] == 2040
    assert sf100["promotion"] == 1000
    assert sf100["call_center"] == 30
    assert sf100["catalog_page"] == 20400
    assert sf100["reason"] == 55

    # fixed-size tables at every SF
    for z in (sf1, sf10, sf100):
        assert z["customer_demographics"] == 1920800
        assert z["date_dim"] == 73049
        assert z["time_dim"] == 86400
        assert z["household_demographics"] == 7200
        assert z["income_band"] == 20
        assert z["ship_mode"] == 20


def test_sub_sf1_scaling_keeps_proportions(tool):
    """Below SF1 (test datasets) facts shrink linearly and dims keep a
    damped fraction — generation at SF0.02 must stay tiny."""
    z = _sizes(tool, 0.02)
    assert z["store_sales"] == round(2880404 * 0.02)
    assert z["customer_demographics"] == 1920800  # fixed regardless
    assert 1 <= z["store"] <= 12
    assert z["item"] < 18000


# -- web orders at the source's structure (PR 33) ---------------------------
# sha1 (12 hex digits) of every file the generator writes at scale 0.01
# with its built-in seed, taken on the tree BEFORE web orders got the
# source's 8 to 16 lines and web_company_name dsdgen's syllable names
# (commit 4399ddd): whole files, and column by column for the three
# tables that moved.  MOVED names the three columns that did, with the
# checksum each has now.
SEED_TREE_FILES = {
    "customer_address": "43aff6f620eb",
    "customer_demographics": "8c0065ad9871",
    "date_dim": "a23224037df8",
    "warehouse": "e38f7f96f046",
    "ship_mode": "e6f11770a68f",
    "time_dim": "eea65b142216",
    "reason": "6e7616334ba5",
    "income_band": "7070d10f9567",
    "item": "78bdad94ac4d",
    "store": "e77a665e28fa",
    "call_center": "a36cecf7c930",
    "customer": "ba61196a24b5",
    "store_returns": "4d64f2055046",
    "household_demographics": "73d93a6af1d3",
    "web_page": "2e1d93f7f738",
    "promotion": "195982329ce8",
    "catalog_page": "e943d579521e",
    "inventory": "b3a6a828556c",
    "catalog_returns": "5e5c5ad5bf37",
    "catalog_sales": "63ac2da5fa89",
    "dbgen_version": "04dd9ded18f2",
    "store_sales": "622cc4163b1d",
}
SEED_TREE_UPDATE_FILES = {
    "delete": "00b79da7631f",
    "inventory_delete": "1e28ed540529",
    "s_catalog_order": "e1efdabd5102",
    "s_catalog_order_lineitem": "b82371688744",
    "s_catalog_returns": "2ca75b1f0927",
    "s_inventory": "71e22a15b656",
    "s_purchase": "80ec4de3367b",
    "s_purchase_lineitem": "4d553ae39c84",
    "s_store_returns": "8ae3a122a68d",
    "s_web_order": "b2415859615a",
    "s_web_order_lineitem": "26c8a2fbb86a",
    "s_web_returns": "f3a02179117b",
}
SEED_TREE_COLUMNS = {
    "web_site": {
        "web_site_sk": "9e17c0764566",
        "web_site_id": "3cbe86df613b",
        "web_rec_start_date": "caa7ccc6e7bf",
        "web_rec_end_date": "71853c6197a6",
        "web_name": "6305af66b984",
        "web_open_date_sk": "8fd0d91e58b9",
        "web_close_date_sk": "71853c6197a6",
        "web_class": "9743d93914fc",
        "web_manager": "bdcc0044572b",
        "web_mkt_id": "96b3a7f0aa76",
        "web_mkt_class": "7771a0c07928",
        "web_mkt_desc": "9a57bc8613a0",
        "web_market_manager": "6839741a26b1",
        "web_company_id": "d7152fc106d2",
        "web_company_name": "e399478512cb",
        "web_street_number": "800d8574a8b5",
        "web_street_name": "345aeea36643",
        "web_street_type": "c3d43e81b331",
        "web_suite_number": "8b3a055080ea",
        "web_city": "4f31b12527a1",
        "web_county": "eaafcd4c2000",
        "web_state": "4fe0124bb5e6",
        "web_zip": "eaa47b0c4df5",
        "web_country": "9a14597f9512",
        "web_gmt_offset": "e53a44d344ff",
        "web_tax_percentage": "8691caf1ea93",
    },
    "web_returns": {
        "wr_returned_date_sk": "d38da6ec4f56",
        "wr_returned_time_sk": "8bc8952a95d0",
        "wr_item_sk": "4389e09ea243",
        "wr_refunded_customer_sk": "4ed21f673e38",
        "wr_refunded_cdemo_sk": "2929fd64576c",
        "wr_refunded_hdemo_sk": "9f4254e64c6b",
        "wr_refunded_addr_sk": "f710feeba693",
        "wr_returning_customer_sk": "4ed21f673e38",
        "wr_returning_cdemo_sk": "2929fd64576c",
        "wr_returning_hdemo_sk": "9f4254e64c6b",
        "wr_returning_addr_sk": "f710feeba693",
        "wr_web_page_sk": "099a594e1a2e",
        "wr_reason_sk": "688454b1afcf",
        "wr_order_number": "c9f6ac7a04d8",
        "wr_return_quantity": "b53665965f36",
        "wr_return_amt": "0ae2352026c0",
        "wr_return_tax": "85eea5236df0",
        "wr_return_amt_inc_tax": "b032f420a7ff",
        "wr_fee": "c6c6a3c8c4a2",
        "wr_return_ship_cost": "6d12a8f16944",
        "wr_refunded_cash": "9ad49b33c328",
        "wr_reversed_charge": "821af2baf099",
        "wr_account_credit": "62ae4043cc35",
        "wr_net_loss": "7bb11af96ee9",
    },
    "web_sales": {
        "ws_sold_date_sk": "c12a2ca56e48",
        "ws_sold_time_sk": "d3326314de63",
        "ws_ship_date_sk": "36fcbf9354ce",
        "ws_item_sk": "10378b52afa8",
        "ws_bill_customer_sk": "2240ebb12822",
        "ws_bill_cdemo_sk": "2400fb6520e5",
        "ws_bill_hdemo_sk": "3ca9c1af7399",
        "ws_bill_addr_sk": "1307be0e1de5",
        "ws_ship_customer_sk": "2a266f55f4cb",
        "ws_ship_cdemo_sk": "22ad11ba7dbd",
        "ws_ship_hdemo_sk": "70a3c3e22cfd",
        "ws_ship_addr_sk": "3ee08b28b05e",
        "ws_web_page_sk": "50846706981c",
        "ws_web_site_sk": "8d15bc930016",
        "ws_ship_mode_sk": "8de73afe9927",
        "ws_warehouse_sk": "9c732751e894",
        "ws_promo_sk": "82d9168396e6",
        "ws_order_number": "bbee2fb54c21",
        "ws_quantity": "965e8e528743",
        "ws_wholesale_cost": "f151312c479f",
        "ws_list_price": "d21d872e841f",
        "ws_sales_price": "8aa333adbe2d",
        "ws_ext_discount_amt": "eb8be231792b",
        "ws_ext_sales_price": "fa833e6c7174",
        "ws_ext_wholesale_cost": "7fe1faa6c1c4",
        "ws_ext_list_price": "36e26e2500e4",
        "ws_ext_tax": "1b3adc8c2dff",
        "ws_coupon_amt": "754ce66722a8",
        "ws_ext_ship_cost": "d1a38d437192",
        "ws_net_paid": "d4f696e47356",
        "ws_net_paid_inc_tax": "61694dd1d25d",
        "ws_net_paid_inc_ship": "b37902a193b3",
        "ws_net_paid_inc_ship_tax": "f9f88b68d2b2",
        "ws_net_profit": "6b1e887253f9",
    },
}
MOVED = {
    ("web_site", "web_company_name"): "50510c3dcf8a",
    ("web_returns", "wr_order_number"): "18205e303a9e",
    ("web_sales", "ws_order_number"): "1b4528f40d10",
}


def _sha(data: bytes) -> str:
    import hashlib
    return hashlib.sha1(data).hexdigest()[:12]


def _columns(path):
    """The '|'-terminated file as a list of columns (tuples of bytes)."""
    with open(path, "rb") as f:
        return list(zip(*(ln.split(b"|")[:-1] for ln in f.read().splitlines())))


def test_only_the_web_order_numbers_and_company_name_moved(tool, tmp_path):
    """Every file of every table, refresh set included, is byte-equal to
    the seed tree's but for ws_order_number, wr_order_number and
    web_company_name: gen_sale draws for a row exactly what it drew, the
    order's length is a draw of the order's own."""
    run_gen(tool, tmp_path)
    assert set(SEED_TREE_FILES) | set(SEED_TREE_COLUMNS) \
        == set(schema.SOURCE_TABLE_NAMES)
    for t, want in SEED_TREE_FILES.items():
        assert _sha((tmp_path / f"{t}_1_1.dat").read_bytes()) == want, t
    for t, want in SEED_TREE_COLUMNS.items():
        names = schema.get_schemas()[t].column_names
        got = {n: _sha(b"\n".join(c)) for n, c in
               zip(names, _columns(tmp_path / f"{t}_1_1.dat"))}
        assert set(got) == set(want), t
        for n in names:
            assert got[n] == MOVED.get((t, n), want[n]), (t, n)
    assert all(MOVED[k] != SEED_TREE_COLUMNS[k[0]][k[1]] for k in MOVED)
    upd = tmp_path / "upd"
    run_gen(tool, upd, "-update", "1")
    assert {f.name[:-len("_1_1.dat")]: _sha(f.read_bytes())
            for f in upd.iterdir()} == SEED_TREE_UPDATE_FILES


def _int_column(path, index):
    import numpy as np
    return np.array([int(c) for c in _columns(path)[index]])


def test_web_orders_have_8_to_16_lines(tool, tmp_path):
    """Rows of one ws_order_number are adjacent, 8 to 16 of them,
    uniform (mean 12): dsdgen's web order.  The table's last order may
    be cut short by the table's end; chunks cut nothing else (a chunk
    boundary inside an order leaves its rows adjacent in the whole)."""
    import numpy as np
    for c in "123":
        run_gen(tool, tmp_path, "-parallel", "3", "-child", c,
                "-table", "web_sales")
    col = schema.get_schemas()["web_sales"].column_names.index(
        "ws_order_number")
    orders = np.concatenate([_int_column(
        tmp_path / f"web_sales_{c}_3.dat", col) for c in "123"])
    run_gen(tool, tmp_path / "one", "-table", "web_sales")
    assert (orders == _int_column(
        tmp_path / "one" / "web_sales_1_1.dat", col)).all()
    cuts = np.flatnonzero(np.diff(orders)) + 1
    assert (np.diff(orders)[cuts - 1] == 1).all() and orders[0] == 1
    runs = np.diff(np.concatenate([[0], cuts, [len(orders)]]))
    assert len(runs) > 500
    whole = runs[:-1]
    assert whole.min() == 8 and whole.max() == 16 and runs[-1] <= 16
    assert 11.5 <= whole.mean() <= 12.5
    # uniform: each of the nine lengths takes about a ninth
    share = np.bincount(whole, minlength=17)[8:] / len(whole)
    assert (abs(share - 1 / 9) < 0.04).all(), share


def test_a_web_return_carries_its_parent_sales_order(tool, tmp_path):
    """gen_web_returns re-derives its parent sale through gen_sale:
    every (wr_order_number, wr_item_sk) is a (ws_order_number,
    ws_item_sk) of web_sales."""
    run_gen(tool, tmp_path, "-table", "web_sales")
    run_gen(tool, tmp_path, "-table", "web_returns")
    ws = schema.get_schemas()["web_sales"].column_names
    wr = schema.get_schemas()["web_returns"].column_names
    sales = _columns(tmp_path / "web_sales_1_1.dat")
    sold = set(zip(sales[ws.index("ws_order_number")],
                   sales[ws.index("ws_item_sk")]))
    rets = _columns(tmp_path / "web_returns_1_1.dat")
    back = list(zip(rets[wr.index("wr_order_number")],
                    rets[wr.index("wr_item_sk")]))
    assert len(back) > 500 and all(r in sold for r in back)


def test_web_company_names_are_dsdgens(tool, tmp_path):
    """query94 / query95 ask for web_company_name = 'pri': the column
    draws dsdgen's syllable names, not "Company n"."""
    run_gen(tool, tmp_path, "-scale", "1", "-table", "web_site")
    col = schema.get_schemas()["web_site"].column_names.index(
        "web_company_name")
    names = {c.decode() for c in _columns(tmp_path / "web_site_1_1.dat")[col]}
    assert names <= {"ought", "able", "pri", "ese", "anti", "cally"}
    assert "pri" in names
