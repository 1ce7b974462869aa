"""`message_fold_share` (PR 44): the share of a run's sets that were verified
on the message axis, read from `jaxbls_dispatch_messages_total`
(`folded_sets` over `sent`). What is checked here is that the metric's file
is found by name, is declared in `BENCHMARK.json` for its three cells and
for nothing else, and resolves — through `layer_reader.evaluate`, as the
harness reads it — from the counters a rehearsal run of each cell's kind of
dispatch moves: the jax backend's own marshal on this CPU, one chip's batch
lane, the stage programs stubbed out (nothing compiles; no number read here
is a device metric). A program without the message axis (a parent commit)
has no `folded_sets` child: the reader finds nothing and the line leaves
the metric out."""

import json
import os

import layer_reader
import numpy as np
import pytest
import run as bench_run

from conftest import REPO_ROOT

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls381 import curve as cv
from lighthouse_tpu.crypto.jaxbls import backend as be
from lighthouse_tpu.utils.metrics import REGISTRY

NAME = "message_fold_share"
CELLS = ["subnet_flood_1key", "aggregate_flood", "gossip_flood"]

_PK = bls.SecretKey(0xBE9C).public_key()
_SIG = bls.Signature(cv.g2_mul(cv.G2_GEN, 0xBE9C))      # never verified here


def _msg(i: int) -> bytes:
    return i.to_bytes(4, "big") * 8


#: a dispatch of each cell's kind: (sets' messages, keys a set, folds)
DISPATCHES = {
    # 1,024 single-key attestations of 64 committees, a late vote in some
    "subnet_flood_1key": ([_msg(i % 64 + (64 if i % 50 == 0 else 0))
                           for i in range(1024)], 1, True),
    # 64 aggregates: 64 selection proofs on the slot's one message, 64
    # aggregate-and-proof messages, 64 attestations of ~41 committees
    "aggregate_flood": ([_msg(0)] * 64 + [_msg(1 + i) for i in range(64)]
                        + [_msg(100 + i % 41) for i in range(64)], 1, True),
    # 64 attestations, every one a message of its own
    "gossip_flood": ([_msg(i) for i in range(64)], 1, False),
}


@pytest.fixture
def one_chip_stubbed(monkeypatch):
    """One chip's batch lane (this process has one CPU device and so no
    mesh), the stage programs replaced by callables that compile nothing."""
    from lighthouse_tpu import parallel

    parallel.reset_mesh_cache()
    assert parallel.get_mesh() is None

    def stage(out):
        return lambda *args: out

    pairs = ("px", "py", "qxx", "qyy", "pair_mask")
    monkeypatch.setattr(be, "_get_stages", lambda mesh=None: (
        stage(("z_pk", "sig_acc", np.bool_(False))), stage("h_jac"),
        stage(pairs), stage(np.bool_(True))))
    monkeypatch.setattr(be, "_get_one_chip_variant",
                        lambda name: stage(pairs))


def _spec():
    return bench_run.load_layer_metrics()[NAME]


@pytest.mark.parametrize("cell", CELLS)
def test_it_resolves_from_the_counters_of_the_cells_kind_of_dispatch(
        cell, one_chip_stubbed):
    messages, keys, folds = DISPATCHES[cell]
    sets = [bls.SignatureSet(_SIG, [_PK] * keys, m) for m in messages]
    backend = be.JaxBackend()
    before = layer_reader.snapshot(REGISTRY)
    for _ in range(3):
        assert backend.verify_signature_sets_async(
            sets, [5] * len(sets)).result() is True     # the stub's verdict
    after = layer_reader.snapshot(REGISTRY)
    got = layer_reader.evaluate(_spec()["source"], before, after, {}, {})
    assert got == (100.0 if folds else 0.0)
    n = be.padding_bucket(len(sets), keys, single_chip=True)[0]
    distinct = len(set(messages))
    assert (be.message_lanes(distinct, n) < n) is folds
    lanes = layer_reader.evaluate(
        {"family": "jaxbls_dispatch_messages_total",
         "labels": {"kind": "lanes"}, "reduce": "sum"}, before, after, {}, {})
    assert lanes == 3 * (128 if folds else n)


def test_a_program_without_the_message_axis_leaves_the_metric_out():
    """The parent's counter has `sent` and `distinct` alone."""
    def snap(sent, distinct):
        return {"jaxbls_dispatch_messages_total": {
            "labelnames": ("kind",),
            "children": {("sent",): ("scalar", sent),
                         ("distinct",): ("scalar", distinct)}}}

    source = _spec()["source"]
    assert layer_reader.evaluate(
        source, snap(0.0, 0.0), snap(2048.0, 164.0), {}, {}) is None
    # and a run that sent nothing has nothing to divide by
    assert layer_reader.evaluate(source, {}, {}, {}, {}) is None


def test_the_file_is_found_by_name_and_matches_benchmark_json():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    spec = _spec()
    assert spec == bench_run.load_json("layer_metrics", NAME)
    entry = bench["per_layer"][-1]           # appended, nothing moved
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "marshal",
        "moves": "bls_verified_sets_per_s", "workloads": CELLS}
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"],
            spec["origin"], spec["cells"]) == (
        NAME, "%", "marshal", "bls_verified_sets_per_s", "program_counter",
        CELLS)
    assert [m["name"] for m in bench["per_layer"]].count(NAME) == 1
    # each of its cells reports the end-to-end metric it moves
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert set(CELLS) <= set(e2e["bls_verified_sets_per_s"]["workloads"])
    assert {w["name"] for w in bench["workloads"]} >= set(CELLS)
    # the counter it reads is the program's, under the kinds it names
    assert spec["source"]["num"]["family"] == spec["source"]["den"][
        "family"] == be._DISPATCH_MESSAGES.name
    assert be._DISPATCH_MESSAGES.labelnames == ("kind",)
