"""Device time by the program's named scopes (ISSUE 33): the reduction on
made-up intervals and on the trace recorded on a v5e, and the four readers
(``test_scope_of.py``: which scope an op's ``tf_op`` puts it in). No number
from here is a device number of a cell. (Seven tests a file at most:
``test_manifest.py`` says why.)"""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import family_of, xplane  # noqa: E402
from benchmark import run as bench  # noqa: E402

MANIFEST = bench.load_manifest()
SCOPES = family_of({"family": "mla_moe"}).DEVICE_SCOPES
SHARES = {"lm_head_device_share": "lm_head",
          "expert_layer_device_share": "expert_layer",
          "attention_device_share": "mla_attention",
          "outside_scopes_device_share": xplane.OUTSIDE}
ROUND = "jit(train_round)/client_scan/while/body/"


def reader(name):
    return importlib.import_module(f"benchmark.metrics.{name}")


def made_up(with_tf_op=True):
    """A ``while`` of 10 s that spans two scoped ops (3 s and 4 s, the second
    with an op of 1 s nested in it) and one unscoped (1 s), and an unscoped
    op of 2 s behind it: 12 s of self time in a window of 14 s."""
    def op(name, s, e, tf_op=None):
        return ("%" + name + " = f32[2,8]{1,0} fusion()", s, e,
                {"tf_op": tf_op, "flops": 1} if tf_op and with_tf_op
                else {"flops": 1})
    ops = [op("while", 1.0, 11.0, ROUND[:-6]),
           op("fusion.1", 1.5, 4.5, ROUND + "lm_head/dot_general"),
           op("fusion.2", 5.0, 9.0, ROUND + "transpose(jvp(expert_layer))"),
           op("fusion.3", 6.0, 7.0, ROUND + "layer_1/attn/mla_attention/exp"),
           op("fusion.4", 9.5, 10.5, ROUND + "sub"),
           op("copy.5", 11.0, 13.0)]
    return {"devices": {0: {"modules": [], "ops": ops}},
            "host": [(xplane.WINDOW_EVENT, 0.0, 14.0)]}


def test_a_while_keeps_its_own_time_and_the_scopes_sum_to_the_self_time():
    red = xplane.reduce(made_up(), scopes=SCOPES)
    assert red["scope_s"] == {"lm_head": 3.0, "expert_layer": 3.0,
                              "mla_attention": 1.0, xplane.OUTSIDE: 5.0}
    self_s = sum(s for _, _, _, s in xplane.self_times(
        made_up()["devices"][0]["ops"]))
    assert sum(red["scope_s"].values()) == self_s == red["busy_s"] == 12.0
    # the ten ops with most self time say which scope each is in
    assert red["breakdown"]["device_ops"] == [
        ["lm_head: fusion.1 f32[2,8]", 3.0],
        ["expert_layer: fusion.2 f32[2,8]", 3.0],
        ["while f32[2,8]", 2.0], ["copy.5 f32[2,8]", 2.0],
        ["mla_attention: fusion.3 f32[2,8]", 1.0],
        ["fusion.4 f32[2,8]", 1.0]]
    assert red["breakdown"]["device_scopes"] == [
        [xplane.OUTSIDE, 5.0], ["lm_head", 3.0], ["expert_layer", 3.0],
        ["mla_attention", 1.0]]
    # each scope's longest ops with the path that put them there, for the log
    assert red["scope_ops"]["mla_attention"] == [
        ["fusion.3 f32[2,8]", 1.0, ROUND + "layer_1/attn/mla_attention/exp"]]
    assert [op[:2] for op in red["scope_ops"][xplane.OUTSIDE]] == [
        ["while f32[2,8]", 2.0], ["copy.5 f32[2,8]", 2.0],
        ["fusion.4 f32[2,8]", 1.0]]
    shares = {name: reader(name).read({}, red, {}) for name in SHARES}
    assert shares == {"lm_head_device_share": 25.0,
                      "expert_layer_device_share": 25.0,
                      "attention_device_share": pytest.approx(100 / 12),
                      "outside_scopes_device_share": pytest.approx(500 / 12)}
    assert sum(shares.values()) == pytest.approx(100.0)
    # a name is still cut at 80 characters, prefix and all
    long = made_up()
    name, s, e, stats = long["devices"][0]["ops"][2]
    long["devices"][0]["ops"][2] = (name.replace("2,8", "2," * 60 + "8"),
                                    s, e, stats)
    top = xplane.reduce(long, scopes=SCOPES)["breakdown"]["device_ops"]
    assert all(len(n) <= 80 for n, _ in top)
    assert top[1][0].startswith("expert_layer: fusion.2 f32[2,2,2,")


def test_nothing_to_read_is_none_from_all_four_readers_never_nought():
    """A trace whose ops carry no ``tf_op``; events of three entries, as the
    reduction took them before it read metadata; a family that lists no
    scope (the resnet cell's); no trace; a scope under which nothing ran."""
    bare = made_up(with_tf_op=False)
    triples = {"devices": {0: {"modules": [], "ops": [
        ev[:3] for ev in bare["devices"][0]["ops"]]}}, "host": bare["host"]}
    resnet = bench.load_json("configs", "cifar10_resnet20.json")["arch"]
    no_scopes = getattr(family_of(resnet), "DEVICE_SCOPES", ())
    assert no_scopes == ()
    for red in (xplane.reduce(bare, scopes=SCOPES),
                xplane.reduce(triples, scopes=SCOPES),
                xplane.reduce(made_up(), scopes=no_scopes)):
        assert red["scope_s"] is None and red["scope_ops"] is None
        assert "device_scopes" not in red["breakdown"]
        assert [n for n, _ in red["breakdown"]["device_ops"]][:2] == [
            "fusion.1 f32[2,8]", "fusion.2 f32[2,8]"]
        assert red["busy_s"] == 12.0
        for name in SHARES:
            assert reader(name).read({}, red, {}) is None
    for name in SHARES:
        assert reader(name).read({}, None, {}) is None
    red = xplane.reduce(made_up(), scopes=("lm_head", "no_such_scope"))
    assert red["scope_s"]["no_such_scope"] == 0.0
    assert reader("lm_head_device_share").read({}, red, {}) == 25.0
    red["scope_s"]["lm_head"] = 0.0
    assert reader("lm_head_device_share").read({}, red, {}) is None


def test_the_resnet_cell_reports_none_of_the_shares_and_the_decoder_all():
    per_cell = {c["name"]: [m["name"] for m in bench.metrics_of(
        MANIFEST, "per_layer", c["name"])] for c in MANIFEST["workloads"]}
    assert not set(SHARES) & set(per_cell["resnet20.ifca_perround"])
    assert set(SHARES) <= set(per_cell["kanana2.ifca_perround"])
    assert "pairs_run_per_round" in per_cell["resnet20.ifca_perround"]
    assert "pairs_run_per_round" not in per_cell["kanana2.ifca_perround"]
    assert len(per_cell["resnet20.ifca_perround"]) == 12
    assert len(per_cell["kanana2.ifca_perround"]) == 18
    layers = {m["name"]: m["layer"] for m in MANIFEST["per_layer"]}
    assert [layers[n] for n in SHARES] == [
        "lm head", "expert layer", "latent attention", "round programs"]


def test_the_recorded_trace_by_the_scope_its_tf_op_names():
    """The fixture's program has one op with a ``tf_op``, the matmul inside
    the scan (``.../while/body/closed_call/dot_general``): sixteen of ~291 ns
    in four programs, eight of them in the window."""
    fix = os.path.join(ROOT, "benchmark", "fixtures", "v5e_small")
    with open(fix + ".host.json") as f:
        host = json.load(f)
    red = xplane.reduce(xplane.load(fix + ".xplane.pb"),
                        sync_wall=host["sync_wall"],
                        host_spans=[tuple(s) for s in host["spans"]],
                        rounds=4, scopes=("no_such_scope", "closed_call"))
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["closed_call: fusion.8 bf16[256,256]"] == pytest.approx(
        8 * 291e-9, rel=0.02) == red["scope_s"]["closed_call"]
    assert red["scope_s"]["no_such_scope"] == 0.0
    assert sum(red["scope_s"].values()) == pytest.approx(sum(ops.values()))
    assert sum(red["scope_s"].values()) == pytest.approx(red["busy_s"],
                                                         rel=0.02)
    assert red["breakdown"]["device_scopes"][0][0] in ("closed_call",
                                                       xplane.OUTSIDE)
    assert len(red["breakdown"]["device_scopes"]) == 3
