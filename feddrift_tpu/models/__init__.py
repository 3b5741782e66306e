"""Model zoo registry.

Mirrors the reference's ``create_model`` dispatch
(fedml_experiments/distributed/fedavg_cont_ens/main_fedavg.py:207-224) but as
flax modules returning logits. Every model is a pure function of
``(params, x)`` so the pool can be stacked on a leading ``[M]`` axis and
trained under ``vmap``.
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn

from feddrift_tpu.data.drift_dataset import DriftDataset
from feddrift_tpu.models.mlp import LogisticRegression, FeedForwardNN
from feddrift_tpu.models.cnn import CNNFedAvg, CNNDropout
from feddrift_tpu.models.resnet import ResNetCifar, ResNet18
from feddrift_tpu.models.rnn import CharLSTM, WordLSTM

_BUILDERS: dict[str, Callable[..., nn.Module]] = {}


def register_model(*names: str):
    def deco(fn):
        for n in names:
            _BUILDERS[n] = fn
        return fn
    return deco


def available_models() -> list[str]:
    return sorted(_BUILDERS)


@register_model("lr")
def _lr(ds: DriftDataset, cfg) -> nn.Module:
    return LogisticRegression(num_classes=ds.num_classes)


@register_model("fnn")
def _fnn(ds: DriftDataset, cfg) -> nn.Module:
    # Reference: FeedForwardNN(input_dim, output_dim, hidden) with hidden from
    # main_fedavg model wiring; hidden_dim configurable here.
    return FeedForwardNN(num_classes=ds.num_classes,
                        hidden_dim=getattr(cfg, "fnn_hidden_dim", 10))


@register_model("cnn")
def _cnn(ds: DriftDataset, cfg) -> nn.Module:
    return CNNFedAvg(num_classes=ds.num_classes)


@register_model("cnn_dropout")
def _cnnd(ds: DriftDataset, cfg) -> nn.Module:
    return CNNDropout(num_classes=ds.num_classes)


@register_model("resnet", "resnet20")
def _resnet20(ds: DriftDataset, cfg) -> nn.Module:
    return ResNetCifar(num_classes=ds.num_classes, depth=20)


@register_model("resnet8")
def _resnet8(ds, cfg):
    # GKT client-side extractor size (reference fedgkt resnet_client ResNet-8)
    return ResNetCifar(num_classes=ds.num_classes, depth=8)


@register_model("resnet56")
def _resnet56(ds: DriftDataset, cfg) -> nn.Module:
    return ResNetCifar(num_classes=ds.num_classes, depth=56)


@register_model("resnet110")
def _resnet110(ds: DriftDataset, cfg) -> nn.Module:
    return ResNetCifar(num_classes=ds.num_classes, depth=110)


@register_model("resnet56_gn")
def _resnet56gn(ds: DriftDataset, cfg) -> nn.Module:
    return ResNetCifar(num_classes=ds.num_classes, depth=56, norm="group")


@register_model("resnet18")
def _resnet18(ds: DriftDataset, cfg) -> nn.Module:
    return ResNet18(num_classes=ds.num_classes)


@register_model("mobilenet")
def _mobilenet(ds: DriftDataset, cfg) -> nn.Module:
    from feddrift_tpu.models.mobilenet import MobileNet
    return MobileNet(num_classes=ds.num_classes)


@register_model("mobilenet_gn")
def _mobilenet_gn(ds: DriftDataset, cfg) -> nn.Module:
    from feddrift_tpu.models.mobilenet import MobileNet
    return MobileNet(num_classes=ds.num_classes, norm="group")


@register_model("densenet", "densenet121")
def _densenet(ds: DriftDataset, cfg) -> nn.Module:
    from feddrift_tpu.models.mobilenet import DenseNet
    return DenseNet(num_classes=ds.num_classes)


@register_model("darts")
def _darts(ds: DriftDataset, cfg) -> nn.Module:
    from feddrift_tpu.models.darts import DARTSNetwork
    return DARTSNetwork(num_classes=ds.num_classes)


@register_model("transformer")
def _transformer(ds: DriftDataset, cfg) -> nn.Module:
    from feddrift_tpu.models.transformer import TransformerLM
    return TransformerLM(vocab_size=ds.num_classes,
                         max_len=max(ds.feature_shape[0]
                                     if ds.is_sequence else 128, 128))


@register_model("kanana2_30b_a3b_cut16", "mla_moe_tiny")
def _mla_moe(ds: DriftDataset, cfg) -> nn.Module:
    """A latent-attention, sparse-expert decoder preset (the model's name
    is the preset's: the published sizes and the cut held here); the data
    set's ids lie in the held rows of the vocabulary, a label per token."""
    from feddrift_tpu.models.mla_moe import PRESETS, MLAMoEDecoder
    rows = PRESETS[cfg.model]["vocab_rows_held"]
    if ds.num_classes != rows or ds.labels_per_sample < 2:
        raise ValueError(
            f"model {cfg.model!r} holds {rows} rows of its vocabulary and "
            f"takes a label per token; data set {ds.name!r} has "
            f"{ds.num_classes} ids and {ds.labels_per_sample} label(s) a "
            f"sample (use dataset 'token_drift')")
    return MLAMoEDecoder(preset=cfg.model, remat=cfg.remat)


@register_model("rnn")
def _rnn(ds: DriftDataset, cfg) -> nn.Module:
    return CharLSTM(vocab_size=ds.num_classes)


@register_model("rnn_stackoverflow")
def _rnn_so(ds: DriftDataset, cfg) -> nn.Module:
    return WordLSTM(vocab_size=ds.num_classes)


def create_model(name: str, ds: DriftDataset, cfg=None) -> nn.Module:
    if name not in _BUILDERS:
        raise KeyError(f"unknown model {name!r}; available: {available_models()}")
    return _BUILDERS[name](ds, cfg)
