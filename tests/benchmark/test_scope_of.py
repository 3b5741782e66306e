"""Which of a model family's ``DEVICE_SCOPES`` an op's ``tf_op`` puts it in
(ISSUE 33): forward, backward and rematerialised paths as JAX writes them
into the ``op_name`` of the decoder's round program."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import family_of, xplane  # noqa: E402

SCOPES = family_of({"family": "mla_moe"}).DEVICE_SCOPES
ROUND = "jit(train_round)/client_scan/while/body/"


@pytest.mark.parametrize("tf_op,scope", [
    # forward, backward as JAX writes it, backward as ISSUE 33 writes it
    (ROUND + "jvp(MLAMoEDecoder)/lm_head/dot_general", "lm_head"),
    (ROUND + "transpose(jvp(MLAMoEDecoder))/layer_2/moe/expert_layer/"
     "dot_general", "expert_layer"),
    (ROUND + "transpose(jvp(expert_layer))/mul", "expert_layer"),
    # rematerialised inside the backward
    (ROUND + "transpose(jvp(MLAMoEDecoder))/jvp(MLAMoEDecoder)/checkpoint/"
     "rematted_computation/layer_1/attn/mla_attention/while/body/exp",
     "mla_attention"),
    # two names: the first of the family's list, wherever it stands
    (ROUND + "layer_4/moe/expert_layer/lm_head/add", "lm_head"),
    (ROUND + "mla_attention/expert_layer/add", "expert_layer"),
])
def test_an_op_belongs_to_the_first_scope_its_tf_op_names(tf_op, scope):
    assert SCOPES == ("lm_head", "expert_layer", "mla_attention")
    assert xplane.scope_of(tf_op, SCOPES) == scope


def test_an_op_no_scope_names_or_a_family_without_scopes_is_outside():
    assert xplane.scope_of(ROUND + "sub", SCOPES) == xplane.OUTSIDE   # SGD
    assert xplane.scope_of(None, SCOPES) == xplane.OUTSIDE
    assert xplane.scope_of("", SCOPES) == xplane.OUTSIDE
    assert xplane.scope_of(ROUND + "lm_head/dot_general", ()) \
        == xplane.OUTSIDE
