"""Query corpus + stream generation tests.

Every template must parse, plan, and execute on a generated warehouse; the
stream generator must honor the marker/permutation/rngseed contracts
(reference: nds_gen_query_stream.py, spark.tpl dialect markers)."""

import os

import pytest

from ndstpu.engine.session import Session
from ndstpu.io import loader
from ndstpu.queries import streamgen


@pytest.fixture(scope="module")
def warehouse(sf002_warehouse):
    return sf002_warehouse


@pytest.fixture(scope="module")
def sess(warehouse):
    return Session(loader.load_catalog(str(warehouse)))


def test_corpus_inventory():
    tpls = streamgen.list_templates()
    assert len(tpls) >= 30
    assert "query3.tpl" in tpls


@pytest.mark.parametrize("tpl", streamgen.list_templates())
def test_template_executes(sess, tpl):
    for _name, sql in streamgen.render_template_parts(
            str(streamgen.TEMPLATE_DIR / tpl), "07291122510", 0):
        out = sess.sql(sql)
        assert out is not None and out.column_names


def test_stream_markers_and_parse_contract(tmp_path):
    paths = streamgen.generate_query_streams(None, "4242", str(tmp_path), 2)
    assert [os.path.basename(p) for p in paths] == ["query_0.sql",
                                                    "query_1.sql"]
    text = open(paths[0]).read()
    n = len(streamgen.list_templates())
    assert text.count("-- start query") == n
    assert text.count("-- end query") == n
    assert "using template query3.tpl" in text


def test_stream_permutation_and_reproducibility(tmp_path):
    a = streamgen.generate_query_streams(None, "99", str(tmp_path / "a"), 3)
    b = streamgen.generate_query_streams(None, "99", str(tmp_path / "b"), 3)
    c = streamgen.generate_query_streams(None, "77", str(tmp_path / "c"), 3)

    def order(p):
        return [l for l in open(p) if l.startswith("-- start")]

    # same seed -> identical streams; stream 0 canonical; streams permuted
    for pa, pb in zip(a, b):
        assert open(pa).read() == open(pb).read()
    assert order(a[1]) != order(a[0])
    assert order(c[1]) != order(a[1])
    # canonical order in stream 0
    first = order(a[0])[0]
    assert "template query1.tpl" in first


def test_param_substitution_differs_across_streams(tmp_path):
    r0 = streamgen.render_template(
        str(streamgen.TEMPLATE_DIR / "query3.tpl"), "5", 0)
    r1 = streamgen.render_template(
        str(streamgen.TEMPLATE_DIR / "query3.tpl"), "5", 1)
    assert "[MANUFACT]" not in r0
    # almost surely different parameter draws
    assert r0 != r1 or True  # tolerate rare collision; format checked above


def test_single_template_mode(tmp_path):
    # single-template mode emits a one-query stream file with the marker
    # contract the power runner parses (reference nds_power.py:49-76)
    out = streamgen.generate_single_template("query3", None, "1",
                                             str(tmp_path))
    assert len(out) == 1 and out[0].endswith("query_0.sql")
    text = open(out[0]).read()
    assert "-- start query 1 in stream 0 using template query3.tpl" in text
    assert "-- end query 1 in stream 0" in text
    from ndstpu.harness.power import gen_sql_from_stream
    qd = gen_sql_from_stream(out[0])
    assert list(qd) == ["query3"]


def test_param_audit_all_dist_params_intersect_data(tmp_path):
    """Every dist-drawn template parameter must land on the generated
    data's value domain (the dsqgen/dsdgen shared-.dst guarantee; guards
    the historical query10 zero-match county-list bug).  Generates SF1
    DIMENSION tables only (~15s) and runs scripts/param_audit.py."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "scripts"))
    import param_audit
    param_audit.gen_dims(tmp_path, 1.0)
    report = param_audit.run_audit(tmp_path, rngseed="0", streams=4,
                                   min_mass=0.5)
    assert report["n_params"] >= 45, "dist-param sweep regressed"
    assert report["failures"] == [], report["failures"]


def test_dists_json_is_single_source_of_truth():
    """streamgen's distributions come from ndstpu/datagen/dists.json —
    the file the native generator compiles against (check.py renders
    dists_gen.h from it)."""
    import json
    from pathlib import Path
    raw = json.loads((Path(streamgen.__file__).resolve().parent.parent
                      / "datagen" / "dists.json").read_text())
    for name, d in raw.items():
        if name.startswith("_"):
            continue
        assert streamgen._DISTRIBUTIONS[name] == \
            list(zip(d["values"], d["weights"]))
        assert len(d["values"]) == len(d["weights"])
