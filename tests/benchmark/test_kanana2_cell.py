"""Cell ``kanana2.ifca_perround`` (ISSUE 29) at the rehearsal's sizes on the
CPU: the command's rehearsal comes out correct and the program's own
lower-precision path does not, by the cell's own limits; the family's
operation count against a count made by hand; the configuration file against
the published one; the three new metrics' readers on made-up records and on
the fixture trace; the scope names of ``DEVICE_SCOPES`` in the lowered module. (``test_kanana2_faults.py`` plants the faults.) No number
from here is a device number."""

import importlib
import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_driving import MANIFEST, ROOT, drive, failed  # noqa: E402

from benchmark import flops, xplane  # noqa: E402
from benchmark import run as bench  # noqa: E402

CELL = "kanana2.ifca_perround"
CONFIG = bench.load_json("configs", "kanana2_30b_a3b.json")


def reader(name):
    return importlib.import_module(f"benchmark.metrics.{name}")


def test_the_commands_rehearsal_of_the_cell_is_correct(capfd):
    """``run.py --rehearse``: the same control flow as a run on the chip,
    held to the cell's own limits; exit code 0 says ``correct``."""
    rc = bench.main(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                     "--seconds", "0.5", "--rehearse"])
    err = capfd.readouterr().err
    assert rc == 0, err[-2000:]
    assert "rehearsal done: correct=True" in err
    numbers = json.loads(next(l for l in err.splitlines()
                              if l.startswith("numbers "))[len("numbers "):])
    limits = bench.load_json("cells", f"{CELL}.json")["limits"]
    # sgd keeps no moment: six numbers, and the limits are on some of them
    assert list(numbers) == ["train_loss_gap", "test_loss_gap",
                             "assign_regret", "change_gap",
                             "change_gap_median", "param_store_gap"]
    assert set(limits) <= set(numbers)


def test_one_precision_step_down_is_not_correct():
    """The control: the program's own ``bf16_mixed`` (parameters kept in
    bfloat16) fails by the store gap, which reads 1.0."""
    result = drive(CELL, program={"precision": "bf16_mixed"})
    assert not result["correct"]
    assert "param_store_gap" in failed(result)
    assert result["check"]["param_store_gap"]["value"] == pytest.approx(1.0)


def test_forward_macs_of_the_published_cut_against_a_count_made_by_hand():
    """A token, a layer: W_q 2,048 x 2 x 192, W_kva 2,048 x 576, W_kvb 512 x
    2 x 256, W_o 256 x 2,048: 2,752,512. The dense layer's MLP 3 x 2,048 x
    6,144. An expert layer: the router 2,048 x 128, the shared experts 3 x
    2,048 x 1,536, the routed ones 6 x 8 / 128 = 0.375 of 3 x 2,048 x 768.
    The head 2,048 x 16,032. A sequence, a layer: 2 heads x 2,048 x 2,049 /
    2 (query, key) pairs x (192 + 128)."""
    arch = CONFIG["arch"]
    proj = 2048 * 384 + 2048 * 576 + 512 * 512 + 256 * 2048
    assert proj == 2_752_512
    dense = 3 * 2048 * 6144
    expert = 2048 * 128 + 3 * 2048 * 1536 + (3 * 2048 * 768 * 6 * 8) // 128
    assert (dense, expert) == (37_748_736, 11_468_800)
    a_token = 5 * proj + dense + 4 * expert + 2048 * 16032
    attention = 5 * 2 * (2048 * 2049 // 2) * 320
    by_hand = 2048 * a_token + attention
    assert flops.forward_macs(arch) == by_hand == 273_404_788_736
    # 0.80 GFLOP a trained token (ISSUE 29 reckoned 0.8: 3 x 2 x 132 M)
    assert a_token == 130_220_032
    assert flops.train_flops_per_example(arch) / 2048 \
        == pytest.approx(0.801e9, rel=1e-3)
    assert flops.parameter_count(arch) == 306_996_224
    # the rehearsal's size: D 64, 2 heads of 8+8 and 8, rank 16, MLP 128,
    # experts of 32 (4 of 16 held, 2 a token), 64 rows, 16 tokens, 3 layers
    tiny = bench.overlay(CONFIG, CONFIG["rehearse"])["arch"]
    t_proj = 64 * 32 + 64 * 24 + 16 * 32 + 16 * 64
    t_expert = 64 * 16 + 3 * 64 * 64 + (3 * 64 * 32 * 2 * 4) // 16
    assert flops.forward_macs(tiny) == 16 * (
        3 * t_proj + 3 * 64 * 128 + 2 * t_expert + 64 * 64) \
        + 3 * 2 * (16 * 17 // 2) * 24


def test_the_configuration_file_is_the_published_one_cut_as_it_says():
    """Every number of the catalog's ``config`` under its own key, but the
    four that count what is held, which ``reduced`` lists beside the
    published counts; no width is cut; ``program`` asks the program for the
    same cut."""
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    held = {"num_hidden_layers": (48, 5), "n_routed_experts": (128, 8),
            "num_attention_heads": (32, 2), "vocab_size": (128256, 16032)}
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "kanana-2-30b-a3b-instruct-2601")
        assert CONFIG["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert CONFIG[k] == (held[k][1] if k in held else v), k
    arch, prog = CONFIG["arch"], CONFIG["program"]
    for k, (published, here) in held.items():
        assert CONFIG[k] == here and k in CONFIG["reduced"]
        assert CONFIG["published"][k] == arch["published"][k] == published
    for k, v in {"hidden_size": 2048, "intermediate_size": 6144,
                 "moe_intermediate_size": 768, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "num_experts_per_tok": 6,
                 "n_shared_experts": 2, "routed_scaling_factor": 2.448,
                 "rope_theta": 1000000}.items():
        assert CONFIG[k] == arch[k] == v, k
    assert arch["router_outputs"] == 128 and arch["experts_held"] == [0, 8]
    assert "16 chips share each layer" in CONFIG["deployment"]
    assert arch["deployment"] == {"chips_per_layer": 16, "index": 0}
    assert {k: prog[k] for k in (
        "model", "dataset", "client_axis", "client_optimizer", "precision",
        "remat", "text_seq_len", "batch_size", "epochs", "sample_num",
        "wd", "token_vocab")} == {
        "token_vocab": arch["vocab_size"],
        "model": "kanana2_30b_a3b_cut16", "dataset": "token_drift",
        "client_axis": "scan", "client_optimizer": "sgd",
        "precision": "auto", "remat": True, "text_seq_len": 2048,
        "batch_size": 2, "epochs": 5, "sample_num": 4, "wd": 0.0}
    assert arch["seq_len"] == prog["text_seq_len"]
    assert CONFIG["optimizer"] == {"kind": "sgd"}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "families",
                                       "mla_moe.py"))
    sizes = bench.load_json("cells", f"{CELL}.json")
    traffic = bench.load_json("traffic", "ifca_perround.json")
    assert sizes["clients_per_chip"] == 4
    assert sizes["program"]["train_iterations"] >= \
        traffic["warmup_time_steps"] + 1 + traffic["traced_time_steps"]


@pytest.fixture()
def ring(monkeypatch):
    """Two window time steps of two rounds whose guard spans carry what the
    scanned round counted, after a warm-up time step."""
    from feddrift_tpu.obs import spans
    rec = spans.SpanRecorder(None)
    monkeypatch.setattr(spans, "_recorder", rec)
    counted = {1: [(9, 99, 99)] * 2,
               2: [(4, 1000, 300), (4, 1000, 400)],
               3: [(3, 750, 350), (4, 1000, 450)]}
    for t, rounds in counted.items():
        for pairs, tokens, held in rounds:
            rec.record("guard", 0.0, 0.1, cat="round", iteration=t,
                       pairs_trained=pairs, expert_tokens=tokens,
                       expert_assignments_held=held)
            rec.record("device_compute", 0.0, 0.1, cat="round", iteration=t)
    return rec


def test_the_new_metrics_readers_on_made_up_records_and_the_fixture(ring):
    records = {"time_steps": [
        {"t": t, "wall_s": 1.0, "rounds": 2,
         "segments": {"device_compute": 0.5}} for t in (2, 3)]}
    cell = {"name": CELL}
    # 4 + 4 + 3 + 4 pairs in 4 rounds
    assert reader("pairs_trained_per_round").read(records, None, cell) == 3.75
    # 1,500 assignments held of 3,750 tokens through the expert layers
    assert reader("held_expert_assignments_per_token").read(
        records, None, cell) == pytest.approx(0.4)
    trace = {"rounds": 4, "module_s": {"jit__acc_matrix_jit(1)": 0.6,
                                       "jit__acc_matrix_jit(2)": 0.2,
                                       "jit__train_round_scan_jit": 3.0}}
    assert reader("eval_program_device_ms").read(records, trace, cell) \
        == pytest.approx(200.0)
    # the trace recorded on a v5e (PR 23) runs no evaluation program:
    # nothing to read; with its one program under the evaluation's name the
    # reader gives the reduction's own sum over the traced rounds
    fix = os.path.join(ROOT, "benchmark", "fixtures", "v5e_small")
    with open(fix + ".host.json") as f:
        host = json.load(f)
    red = xplane.reduce(xplane.load(fix + ".xplane.pb"),
                        sync_wall=host["sync_wall"],
                        host_spans=[tuple(s) for s in host["spans"]],
                        rounds=4)
    assert reader("eval_program_device_ms").read(records, red, cell) is None
    (name, secs), = red["module_s"].items()
    renamed = {**red, "module_s": {"jit__acc_matrix_jit": secs}}
    assert reader("eval_program_device_ms").read(records, renamed, cell) \
        == pytest.approx(1e3 * 4.590e-6 / 4, abs=1e-6)
    # nothing to read: no trace; no such program in it; a program whose
    # rounds count nothing (the vmap body, the parent commit); no time step
    assert reader("eval_program_device_ms").read(records, None, cell) is None
    assert reader("eval_program_device_ms").read(
        records, {"rounds": 4, "module_s": {"jit__train_round_jit": 3.0}},
        cell) is None
    ring.ring = type(ring.ring)(
        ({**s, "args": {"iteration": s["args"]["iteration"]}}
         for s in ring.spans()), maxlen=8192)
    for name in ("pairs_trained_per_round",
                 "held_expert_assignments_per_token"):
        assert reader(name).read(records, None, cell) is None
        assert reader(name).read({"time_steps": []}, None, cell) is None


def test_the_cells_entries_come_after_the_ones_that_were_there():
    """One configuration, one cell and three per-layer metrics (PR 29), then
    the four shares of device time by scope and the resnet cell's
    ``pairs_run_per_round`` (PR 33), each after the accepted ones; the cell
    reports eighteen of the nineteen metrics, the accepted cell the eleven
    it reported and its one."""
    assert [c["name"] for c in MANIFEST["configs"]] == [
        "cifar10_resnet20", "kanana2_30b_a3b"]
    assert [c["name"] for c in MANIFEST["workloads"]] == [
        "resnet20.ifca_perround", CELL]
    names = [m["name"] for m in MANIFEST["per_layer"]]
    new = ["pairs_trained_per_round", "held_expert_assignments_per_token",
           "eval_program_device_ms", "lm_head_device_share",
           "expert_layer_device_share", "attention_device_share",
           "outside_scopes_device_share"]
    assert names[11:] == new + ["pairs_run_per_round"] and len(names) == 19
    assert names[4] == "train_step_mfu"
    for m in MANIFEST["per_layer"][11:]:
        assert m["workloads"] == [
            "resnet20.ifca_perround" if m["name"] == "pairs_run_per_round"
            else CELL]
        assert m["moves"] == "train_examples_per_s"
    for m in MANIFEST["per_layer"][14:18]:
        assert (m["unit"], m["source"], m["better"]) == (
            "%", "device_trace", "lower")
    assert all("workloads" not in m for m in MANIFEST["per_layer"][:11])
    cell = MANIFEST["workloads"][1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana2_30b_a3b", "ifca_perround", 1)
    assert MANIFEST["configs"][1]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "num_attention_heads",
        "vocab_size", "comm_round", "train_iterations"]
    assert MANIFEST["configs"][1]["source"] == CONFIG["source"]
    reported = [m["name"] for m in bench.metrics_of(MANIFEST, "per_layer",
                                                    CELL)]
    assert reported == names[:18]                 # all but the resnet's
    old = bench.metrics_of(MANIFEST, "per_layer", "resnet20.ifca_perround")
    assert [m["name"] for m in old] == names[:11] + ["pairs_run_per_round"]


def test_the_programs_module_still_names_the_familys_device_scopes():
    """The shares of device time are read by ``jax.named_scope`` names that
    the program's module sets (``feddrift_tpu/models/mla_moe.py``) and the
    family's file lists: each occurs in the ``op_name`` metadata of the
    tiny preset's lowered forward and backward pass, so that a renamed scope
    fails here and not as a silent metric."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import family_of
    from benchmark.drivers.train import experiment_config
    from feddrift_tpu.data.drift_dataset import DriftDataset
    from feddrift_tpu.models import create_model
    _, config, traffic, sizes = bench.load_cell(MANIFEST, CELL, rehearse=True)
    family = family_of(config["arch"])
    shapes = family.sample_shapes(config["arch"])
    (x_shape, x_dtype), (y_shape, y_dtype) = shapes["x"], shapes["y"]
    module = create_model(config["program"]["model"], DriftDataset(
        x=np.zeros((1, 2, 1, *x_shape), x_dtype),
        y=np.zeros((1, 2, 1, *y_shape), y_dtype),
        num_classes=shapes["num_classes"],
        concepts=np.zeros((2, 1), np.int32), is_sequence=True),
        experiment_config(config, traffic, sizes, 0, 2))
    x = jnp.zeros((2, *x_shape), x_dtype)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)

    def loss(p):
        return module.apply(p, x).sum()
    text = jax.jit(jax.value_and_grad(loss)).lower(params).as_text(
        debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    assert family.DEVICE_SCOPES == ("lm_head", "expert_layer",
                                    "mla_attention")
    for scope in family.DEVICE_SCOPES:
        mine = [p for p in paths if f"/{scope}/" in p]
        assert any(p.startswith("jit(loss)/jvp(") for p in mine), scope
        assert any(p.startswith("jit(loss)/transpose(jvp(") for p in mine), \
            scope
        # the head is outside the blocks that are rematerialised
        assert any("rematted_computation" in p for p in mine) \
            == (scope != "lm_head"), scope
        assert xplane.scope_of(mine[0], family.DEVICE_SCOPES) == scope
