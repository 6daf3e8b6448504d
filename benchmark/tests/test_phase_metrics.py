"""The per-layer metrics PR 25 added read the spans a request and a
replay time themselves with: each metric file, loaded as the harness
loads it, reduces the hand-counted dump in
``benchmark/fixtures/spans_serve_phases.json`` to the number worked out
there, and finds nothing in a dump of a program that lacks the spans
(the parent commit's)."""

import json
import os

import pytest

from benchmark.harness import readers, spec

FIX = os.path.join(spec.BENCH_DIR, "fixtures")
NEW = ["gate_wait_ms.serve", "gate_hold_ms.serve", "admit_wait_ms.serve",
       "reply_tail_ms.serve", "replay_device_wait_ms.serve",
       "replay_device_wait_ms.power", "replay_assemble_ms.power"]


def _load(name):
    with open(os.path.join(FIX, name)) as f:
        return json.load(f)


def _record(doc):
    return readers.RunRecord(spans=doc["spans"], counters=doc["counters"],
                             ops=doc["ops"], requests=doc["requests"])


def _metric_file(name):
    with open(os.path.join(spec.BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW)
def test_metric_file_gives_the_hand_number(name):
    doc = _load("spans_serve_phases.json")
    got = readers.read_metric(_metric_file(name), _record(doc))
    assert got == pytest.approx(doc["want_ms"][name])


@pytest.mark.parametrize("name", NEW)
def test_metric_file_finds_nothing_in_the_parents_spans(name):
    # spans_small.json is a dump from before these spans existed
    rec = _record(_load("spans_small.json"))
    assert readers.read_metric(_metric_file(name), rec) is None


def test_every_new_metric_is_an_entry_with_the_files_own_words():
    entries = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for name in NEW:
        f = _metric_file(name)
        e = entries[name]
        assert f["reader"] == "span_mean_ms"
        for key in ("name", "layer", "unit", "moves", "workloads"):
            assert f[key] == e[key], (name, key)
        assert e["source"] == "program_span" and e["better"] == "lower"


def test_the_phases_close_in_the_fixture():
    """What the acceptance check does on the chip, on the hand count:
    a hold is at least its pin + statement + row conversion, and a
    replay's four phases sum to its wall."""
    spans = _load("spans_serve_phases.json")["spans"]
    r0 = spans[:9]      # the first request's, in end order
    assert r0[-1]["name"] == "reply_tail" and r0[-1]["args"]["id"] == "r0"
    hold = next(e for e in r0 if e["name"] == "gate_hold")["wall_s"]
    inside = sum(e["wall_s"] for e in r0
                 if e["name"] in ("pin", "statement", "to_rows"))
    assert hold == pytest.approx(inside)
    for e in spans:
        if e["name"] == "replay":
            a = e["args"]
            assert a["host_prep_s"] + a["dispatch_s"] + a["device_wait_s"] \
                + a["assemble_s"] == pytest.approx(e["wall_s"])
