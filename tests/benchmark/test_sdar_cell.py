"""Cell ``sdar.ifca_perround`` at the rehearsal's sizes on the CPU: the
command's rehearsal comes out correct and the program's own lower-precision
path does not, by the cell's own limits; the configuration file against the
published one; the three new metrics' readers on made-up records; the scope
names of ``DEVICE_SCOPES`` in the lowered module; the manifest's new entries
after the accepted ones. (``test_sdar_faults.py`` plants the faults.) No
number from here is a device number."""

import importlib
import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_driving import MANIFEST, drive, failed  # noqa: E402

from benchmark import family_of, xplane  # noqa: E402
from benchmark import run as bench  # noqa: E402

CELL = "sdar.ifca_perround"
CONFIG = bench.load_json("configs", "sdar_30b_a3b_chat.json")
NEW = ["bd_attention_device_share", "bd_attention_roofline_share",
       "masked_positions_share"]


def reader(name):
    return importlib.import_module(f"benchmark.metrics.{name}")


def test_the_commands_rehearsal_of_the_cell_is_correct(capfd):
    rc = bench.main(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                     "--seconds", "0.5", "--rehearse"])
    err = capfd.readouterr().err
    assert rc == 0, err[-2000:]
    assert "rehearsal done: correct=True" in err
    numbers = json.loads(next(l for l in err.splitlines()
                              if l.startswith("numbers "))[len("numbers "):])
    limits = bench.load_json("cells", f"{CELL}.json")["limits"]
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


def test_the_configuration_file_is_the_published_one_cut_as_it_says():
    """Every number of the published ``config.json`` under its own key, but
    the five that count what is held, which ``reduced`` lists beside the
    published counts; no width is cut; ``program`` asks the program for the
    same cut."""
    held = {"num_hidden_layers": (48, 4), "num_experts": (128, 8),
            "num_attention_heads": (32, 2), "num_key_value_heads": (4, 1),
            "vocab_size": (151936, 18992)}
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_experts_per_tok": 8,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False,
        **{k: v[0] for k, v in held.items()}}
    assert CONFIG["source"] == ("https://huggingface.co/JetLM/"
                                "SDAR-30B-A3B-Chat/blob/main/config.json")
    for k, v in published.items():
        assert CONFIG[k] == (held[k][1] if k in held else v), k
    arch, prog = CONFIG["arch"], CONFIG["program"]
    for k, (published, here) in held.items():
        assert CONFIG[k] == here and k in CONFIG["reduced"]
        assert CONFIG["published"][k] == arch["published"][k] == published
    assert arch["num_hidden_layers"] == 4 and arch["experts_held"] == [0, 8]
    assert (arch["num_attention_heads"], arch["num_key_value_heads"],
            arch["vocab_size"], arch["router_outputs"]) == (2, 1, 18992, 128)
    for k, v in {"hidden_size": 2048, "moe_intermediate_size": 768,
                 "head_dim": 128, "num_experts_per_tok": 8,
                 "rope_theta": 1000000, "norm_topk_prob": True}.items():
        assert CONFIG[k] == arch[k] == v, k
    assert "16 chips share each layer" in CONFIG["deployment"]
    assert arch["deployment"] == {"chips_per_layer": 16, "index": 0}
    assert CONFIG["parameter_count"] == 236_145_664
    assert {k: prog[k] for k in (
        "model", "dataset", "client_axis",
        "client_optimizer", "precision", "remat", "text_seq_len",
        "batch_size", "epochs", "sample_num", "lr", "wd", "token_vocab")} \
        == {"model": "sdar_30b_a3b_chat", "dataset": "token_diffusion",
            "client_axis": "scan",
            "client_optimizer": "sgd", "precision": "auto", "remat": True,
            "text_seq_len": arch["seq_len"], "batch_size": 1, "epochs": 5,
            "sample_num": 2, "lr": 0.01, "wd": 0.0,
            "token_vocab": arch["vocab_size"]}
    assert (arch["seq_len"], arch["block_length"]) == (4096, 4)
    assert CONFIG["optimizer"] == {"kind": "sgd"}
    assert len(CONFIG["assumed"]) >= 8
    sizes = bench.load_json("cells", f"{CELL}.json")
    traffic = bench.load_json("traffic", "ifca_perround.json")
    assert sizes["clients_per_chip"] == 4
    assert sizes["program"]["train_iterations"] >= \
        traffic["warmup_time_steps"] + 1 + traffic["traced_time_steps"]


@pytest.fixture()
def ring(monkeypatch):
    """A warm-up time step, two window time steps and two traced ones, of two
    rounds each; each round's guard span carries what the scanned round
    counted, and the traced ones dispatch the evaluation twice a round."""
    from feddrift_tpu.obs import spans
    rec = spans.SpanRecorder(None)
    monkeypatch.setattr(spans, "_recorder", rec)
    for t in (1, 2, 3, 4, 5):
        for masked in (9_000, 11_000):
            rec.record("guard", 0.0, 0.1, cat="round", iteration=t,
                       pairs_trained=4, bd_noisy_positions=20 * 1_000,
                       bd_masked_positions=masked,
                       bd_live_attention_pairs=20)
            rec.record("device_compute", 0.0, 0.1, cat="round", iteration=t)
            if t >= 4:
                for _ in range(2):
                    rec.record("dispatch", 0.0, 0.1, cat="round",
                               iteration=t, fn="acc_matrix")
    return rec


def test_the_new_metrics_readers_on_made_up_records(ring):
    """The window is time steps 2 and 3, the traced ones 4 and 5: four
    traced rounds of 20,000 noisy positions each and eight evaluations of
    M x C x N = 3 x 4 x 2 sequences."""
    peaks = bench.load_json("peaks.json")
    records = {"time_steps": [
        {"t": t, "wall_s": 1.0, "rounds": 2,
         "segments": {"device_compute": 0.5}} for t in (2, 3)],
        "clients": 4, "device_kind": "TPU v5 lite", "peaks": peaks}
    cell = {"name": CELL}
    assert reader("masked_positions_share").read(records, None, cell) \
        == pytest.approx(50.0)
    trace = {"scope_s": {"lm_head": 1.0, "expert_layer": 2.0,
                         "bd_attention": 4.0, xplane.OUTSIDE: 3.0}}
    assert reader("bd_attention_device_share").read(records, trace, cell) \
        == pytest.approx(40.0)
    arch = CONFIG["arch"]
    flops, nbytes = family_of(arch).bd_attention_work(arch)
    forwards = 4 * (4 * 20_000) / arch["seq_len"] + 8 * 3 * 4 * 2
    peak = peaks["TPU v5 lite"]
    least = forwards * max(flops / peak["bf16_flops_per_s"],
                           nbytes / peak["hbm_bytes_per_s"])
    assert reader("bd_attention_roofline_share").read(records, trace, cell) \
        == pytest.approx(100.0 * least / 4.0)
    # nothing to read: no trace, a trace with no such scope, no time step,
    # and a program whose rounds count no noisy position (the parent's)
    for name in NEW[:2]:
        assert reader(name).read(records, None, cell) is None
        assert reader(name).read(records, {"scope_s": {"lm_head": 1.0}},
                                 cell) is None
    assert reader("bd_attention_roofline_share").read(
        {"time_steps": []}, trace, cell) is None
    assert reader("masked_positions_share").read({"time_steps": []}, None,
                                                 cell) is None
    ring.ring = type(ring.ring)(
        ({**s, "args": {"iteration": s["args"]["iteration"]}}
         for s in ring.spans()), maxlen=8192)
    assert reader("masked_positions_share").read(records, None, cell) is None
    assert reader("bd_attention_roofline_share").read(records, trace,
                                                      cell) is None


def test_the_programs_module_names_the_familys_device_scopes():
    """The shares read ``jax.named_scope`` names that the program's module
    sets (``feddrift_tpu/models/sdar_moe.py``) and the family's file lists:
    each occurs in the tiny preset's lowered forward and backward pass."""
    import jax
    import jax.numpy as jnp
    import numpy as np
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
                                    "bd_attention")
    for scope in family.DEVICE_SCOPES:
        mine = [p for p in paths if f"/{scope}/" in p]
        assert any(p.startswith("jit(loss)/jvp(") for p in mine), scope
        assert any(p.startswith("jit(loss)/transpose(jvp(") for p in mine), \
            scope
        assert any("rematted_computation" in p for p in mine) \
            == (scope != "lm_head"), scope
        assert xplane.scope_of(mine[0], family.DEVICE_SCOPES) == scope


def test_the_cells_entries_come_after_the_accepted_ones():
    """One configuration, one cell and three per-layer metrics, appended
    after the accepted entries (the first two configurations and cells and
    nineteen metrics, as ``test_kanana2_cell.py`` pins them); the cell
    reports the eleven metrics every cell reports and its three."""
    assert [c["name"] for c in MANIFEST["configs"][2:3]] == [CONFIG["name"]]
    cell = MANIFEST["workloads"][2]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "sdar_30b_a3b_chat", "ifca_perround", 1)
    new = MANIFEST["per_layer"][19:22]
    assert [m["name"] for m in new] == NEW
    for m in new:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_examples_per_s" and m["unit"] == "%"
    assert [m["layer"] for m in new] == ["block-diffusion attention"] * 2 \
        + ["data"]
    assert [m["source"] for m in new] == ["device_trace"] * 2 \
        + ["program_counter"]
    entry = MANIFEST["configs"][2]
    assert entry["source"] == CONFIG["source"]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    reported = [m["name"] for m in bench.metrics_of(MANIFEST, "per_layer",
                                                    CELL)]
    assert reported == [m["name"] for m in MANIFEST["per_layer"][:11]] + NEW
