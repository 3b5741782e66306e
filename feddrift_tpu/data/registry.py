"""Dataset registry: dataset <-> drift-algorithm composition is orthogonal.

The reference hardwires its drift pipeline to five datasets via a closed
switch (fedml_experiments/distributed/fedavg_cont_ens/main_fedavg.py:145-179);
FederatedEMNIST / fed_shakespeare only exist in the non-drift pipeline
(BASELINE.md). Here any registered dataset composes with any drift algorithm.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from feddrift_tpu.config import ExperimentConfig
from feddrift_tpu.data import changepoints as cp
from feddrift_tpu.data.drift_dataset import DriftDataset
from feddrift_tpu.data.prototype import generate_prototype_drift
from feddrift_tpu.data.synthetic import generate_synthetic
from feddrift_tpu.data.text import generate_text_drift, generate_word_drift

_REGISTRY: dict[str, Callable[..., DriftDataset]] = {}


def register_dataset(*names: str):
    """Register a builder ``(cfg, change_points) -> DriftDataset`` under names."""
    def deco(fn: Callable[[ExperimentConfig, np.ndarray], DriftDataset]):
        for n in names:
            _REGISTRY[n] = fn
        return fn
    return deco


def available_datasets() -> list[str]:
    return sorted(_REGISTRY)


def _resolve_change_points(cfg: ExperimentConfig) -> np.ndarray:
    if cfg.change_points == "rand":
        return cp.generate_random_change_points(
            cfg.train_iterations, cfg.client_num_in_total, cfg.drift_together,
            cfg.time_stretch, seed=cfg.seed)
    return cp.load_change_points(cfg.change_points)


for _name in ("sea", "sine", "circle"):
    @register_dataset(_name)
    def _mk(cfg: ExperimentConfig, change_points: np.ndarray, *, _n=_name) -> DriftDataset:
        return generate_synthetic(
            _n, change_points, cfg.train_iterations, cfg.client_num_in_total,
            cfg.sample_num, cfg.noise_prob, cfg.time_stretch, cfg.seed)

# fed_cifar100 is cifar100 with the TFF per-client partition (reference
# fed_cifar100/data_loader.py); under the drift pipeline's per-(client, step)
# slicing the two share one generator.
# Plain "<name>": real files under data_dir when present, else the hardened
# white-noise-basis prototypes. "<name>-smooth": the conv-learnable
# synthetic family — same label-swap drift and subspace geometry, but the
# class basis is Gaussian-smoothed over the image grid (prototype.py
# round-4 note: the white-noise basis is a global projection conv models
# cannot learn); always synthetic, real files deliberately ignored so the
# task is reproducible anywhere.
for _name in ("MNIST", "femnist", "cifar10", "cifar100", "cinic10",
              "fed_cifar100"):
    for _suffix, _smooth in (("", False), ("-smooth", True)):
        @register_dataset(_name + _suffix)
        def _mk_img(cfg: ExperimentConfig, change_points: np.ndarray,
                    *, _n=_name, _sm=_smooth) -> DriftDataset:
            return generate_prototype_drift(
                _n, change_points, cfg.train_iterations,
                cfg.client_num_in_total, cfg.sample_num, cfg.noise_prob,
                cfg.time_stretch, cfg.seed, cfg.data_dir,
                smooth_sigma=cfg.smooth_sigma if _sm else 0.0)


for _suffix, _smooth in (("", False), ("-smooth", True)):
    @register_dataset("fmow" + _suffix)
    def _mk_fmow(cfg: ExperimentConfig, change_points: np.ndarray,
                 *, _sm=_smooth) -> DriftDataset:
        from feddrift_tpu.data.fmow import generate_fmow_drift
        return generate_fmow_drift(
            change_points, cfg.train_iterations, cfg.client_num_in_total,
            cfg.sample_num, cfg.noise_prob, cfg.time_stretch, cfg.seed,
            cfg.data_dir, cfg.fmow_image_size, cfg.change_points,
            smooth_sigma=cfg.smooth_sigma if _sm else 0.0)


@register_dataset("shakespeare", "fed_shakespeare")
def _mk_text(cfg: ExperimentConfig, change_points: np.ndarray) -> DriftDataset:
    return generate_text_drift(
        change_points, cfg.train_iterations, cfg.client_num_in_total,
        cfg.sample_num, cfg.noise_prob, cfg.time_stretch, cfg.seed,
        seq_len=cfg.text_seq_len, data_dir=cfg.data_dir)


@register_dataset("token_drift")
def _mk_tokens(cfg: ExperimentConfig, change_points: np.ndarray) -> DriftDataset:
    # next-token data, a label per token, over cfg.token_vocab ids (a model
    # that holds another number of rows refuses the data set by name)
    from feddrift_tpu.data.text import generate_token_drift
    return generate_token_drift(
        change_points, cfg.train_iterations, cfg.client_num_in_total,
        cfg.sample_num, cfg.time_stretch, cfg.seed,
        seq_len=cfg.text_seq_len, vocab=cfg.token_vocab)


@register_dataset("susy", "ro")
def _mk_uci(cfg: ExperimentConfig, change_points: np.ndarray) -> DriftDataset:
    from feddrift_tpu.data.tabular import generate_uci_drift
    return generate_uci_drift(
        cfg.dataset, change_points, cfg.train_iterations,
        cfg.client_num_in_total, cfg.sample_num, cfg.noise_prob,
        cfg.time_stretch, cfg.seed, cfg.data_dir)


@register_dataset("stackoverflow_lr")
def _mk_so_lr(cfg: ExperimentConfig, change_points: np.ndarray) -> DriftDataset:
    from feddrift_tpu.data.tabular import generate_stackoverflow_lr_drift
    return generate_stackoverflow_lr_drift(
        change_points, cfg.train_iterations, cfg.client_num_in_total,
        cfg.sample_num, cfg.noise_prob, cfg.time_stretch, cfg.seed,
        vocab_size=cfg.so_vocab_size, tag_size=cfg.so_tag_size,
        data_dir=cfg.data_dir)


@register_dataset("stackoverflow", "stackoverflow_nwp")
def _mk_word(cfg: ExperimentConfig, change_points: np.ndarray) -> DriftDataset:
    # word-NWP keeps its own default seq len (reference StackOverflow
    # windows are ~20 tokens); cfg.text_seq_len governs the char datasets
    return generate_word_drift(
        change_points, cfg.train_iterations, cfg.client_num_in_total,
        cfg.sample_num, cfg.noise_prob, cfg.time_stretch, cfg.seed,
        data_dir=cfg.data_dir)


def make_dataset(cfg: ExperimentConfig) -> DriftDataset:
    if cfg.dataset not in _REGISTRY:
        raise KeyError(f"unknown dataset {cfg.dataset!r}; available: {available_datasets()}")
    if cfg.population_size > 0:
        # Population mode: the dataset covers every REGISTERED client, not
        # just the device-visible cohort. The builders read
        # cfg.client_num_in_total, so hand them a data-shaped clone; the
        # published 10-column change-point presets tile across the
        # population (member i drifts like preset column i mod 10 — the
        # canonical benchmark drift patterns, replicated at scale).
        import dataclasses
        data_cfg = dataclasses.replace(
            cfg, population_size=0,
            client_num_in_total=cfg.population_size,
            client_num_per_round=min(cfg.client_num_per_round,
                                     cfg.population_size))
        change_points = _resolve_change_points(data_cfg)
        if change_points.shape[1] < data_cfg.client_num_in_total:
            reps = -(-data_cfg.client_num_in_total // change_points.shape[1])
            change_points = np.tile(change_points, (1, reps))
        return _REGISTRY[cfg.dataset](data_cfg, change_points)
    change_points = _resolve_change_points(cfg)
    if change_points.shape[1] < cfg.client_num_in_total:
        raise ValueError(
            f"change-point matrix has {change_points.shape[1]} clients < "
            f"client_num_in_total={cfg.client_num_in_total}")
    return _REGISTRY[cfg.dataset](cfg, change_points)
