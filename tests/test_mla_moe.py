"""The latent-attention, sparse-expert decoder (``models/mla_moe.py``)
against its plain reference (``benchmark/families/mla_moe.py``) on seeded
weights at the tiny preset; the chip's share against the uncut layer; no
token dropped; CPU, float32."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import weights                                   # noqa: E402
from benchmark.families import mla_moe as family                 # noqa: E402
from benchmark.run import load_json                              # noqa: E402
from feddrift_tpu.models import mla_moe                          # noqa: E402
from feddrift_tpu.models.mla_moe import (ExpertLayer, LatentAttention,  # noqa: E402
                                         MLAMoEDecoder, routed_experts)
from feddrift_tpu.parallel.ring_attention import blockwise_attention  # noqa: E402

CONFIG = load_json("configs", "kanana2_30b_a3b.json")
TINY = {**CONFIG["arch"], **CONFIG["rehearse"]["arch"]}


def _seeded(seed=3):
    flat = {k: v[0] for k, v in weights.make_weights(TINY, seed, 1).items()}
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (2, 16), 0, 64)
    return flat, tokens


def test_logits_agree_with_the_plain_reference():
    flat, tokens = _seeded()
    module = MLAMoEDecoder(preset="mla_moe_tiny", remat=True)
    tree = weights.to_program_tree(TINY, flat)
    mine, stats = module.apply({"params": tree}, tokens, return_stats=True)
    want = family.forward(TINY, flat, tokens)
    assert mine.shape == want.shape == (2, 16, 64)
    np.testing.assert_allclose(mine, want, rtol=2e-4, atol=2e-6)
    # the counts: 2 expert layers x 32 tokens; each token chose 2 of 16
    assert int(stats["expert_tokens"]) == 64
    assert 0 < int(stats["expert_load"].sum()) <= 64 * 2
    # the program's own init has the reference's parameters and shapes
    own = module.init(jax.random.PRNGKey(0), tokens)["params"]
    assert jax.tree_util.tree_structure(own) \
        == jax.tree_util.tree_structure(tree)
    assert [l.shape for l in jax.tree_util.tree_leaves(own)] \
        == [l.shape for l in jax.tree_util.tree_leaves(tree)]


def test_every_parameters_gradient_agrees_with_the_plain_reference():
    flat, tokens = _seeded(5)
    x, y = tokens[:, :-1], tokens[:, 1:]
    module = MLAMoEDecoder(preset="mla_moe_tiny", remat=True)

    def mine(tree):
        return family.nll(module.apply({"params": tree}, x), y).mean()

    def plain(p):
        return family.nll(family.forward(TINY, p, x), y).mean()
    got = weights.from_program_tree(
        TINY, jax.grad(mine)(weights.to_program_tree(TINY, flat)))
    want = jax.grad(plain)(flat)
    assert set(got) == set(want) == {n for n, _, _ in family.param_spec(TINY)}
    for name in want:
        scale = float(jnp.abs(want[name]).max())
        if name.endswith("router_bias"):    # enters the choice alone
            assert scale == 0.0 and float(jnp.abs(got[name]).max()) == 0.0
            continue
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=name)


def test_the_shares_add_up_to_the_uncut_layer():
    """Sixteen chips share a layer: each computes its two of 32 experts'
    part and its one of 16 heads' part. Their sum, with what every chip
    computes alike (the shared experts) and the residual counted once, is
    what the uncut layer gives."""
    D, E, K, F, S, SHARES = 32, 32, 4, 16, 24, 16
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 8, D))

    def layer(held):
        return ExpertLayer(E, K, held, F, S, 2.448)
    whole = layer((0, E))
    p = whole.init(jax.random.PRNGKey(1), u)["params"]
    p = {**p, "router_bias": 0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                                      (E,))}
    full, load, _ = whole.apply({"params": p}, u)
    assert int(load.sum()) == 16 * K
    shared_alone, _, _ = layer((0, 0)).apply(
        {"params": {k: v[:0] if k in ("w_gate", "w_up", "w_down") else v
                    for k, v in p.items()}}, u)
    total = shared_alone
    for i in range(SHARES):
        lo, hi = i * E // SHARES, (i + 1) * E // SHARES
        mine = {k: v[lo:hi] if k in ("w_gate", "w_up", "w_down") else v
                for k, v in p.items()}
        part, part_load, _ = layer((lo, hi)).apply({"params": mine}, u)
        np.testing.assert_array_equal(part_load, load[lo:hi])
        total = total + (part - shared_alone)
    np.testing.assert_allclose(total, full, rtol=1e-4, atol=1e-6)
    # attention: a share holds its heads' columns of W_q and W_kvb and rows
    # of W_o, and the whole latent projection
    H, N, R, V, rank = 16, 4, 4, 6, 8

    def attention(heads):
        return LatentAttention(heads, N, R, V, rank, 1e4, 1e-6)
    pa = attention(H).init(jax.random.PRNGKey(3), u)["params"]
    want = attention(H).apply({"params": pa}, u)
    got = 0.0
    for h in range(H):
        got = got + attention(1).apply({"params": {
            "wq": pa["wq"][:, h * (N + R):(h + 1) * (N + R)],
            "wkv_a": pa["wkv_a"], "kv_norm": pa["kv_norm"],
            "wkv_b": pa["wkv_b"][:, h * (N + V):(h + 1) * (N + V)],
            "wo": pa["wo"][h * V:(h + 1) * V]}}, u)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def _routed_case(skew, dtype=jnp.float32):
    T, D, F, G = 40, 16, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (T, D))
    wg, wu = (0.3 * jax.random.normal(k, (G, D, F)) for k in ks[1:3])
    wd = 0.3 * jax.random.normal(ks[3], (G, F, D))
    if skew == "one_expert":
        chosen = jnp.zeros((T, G), bool).at[:, 2].set(True)
    elif skew == "past_a_block":
        chosen = jnp.zeros((T, G), bool).at[3:36:3, 1].set(True) \
            .at[::4, 3].set(True)                   # 11 and 10 tokens
    else:
        chosen = jax.random.uniform(ks[4], (T, G)) < 0.15
    weight = jnp.where(chosen, jax.random.uniform(ks[5], (T, G)) + 0.5, 0.0)
    return chosen, (x.astype(dtype), weight, wg.astype(dtype),
                    wu.astype(dtype), wd.astype(dtype))


def _plain_routed(x, weight, wg, wu, wd):
    y = 0.0
    for g in range(wg.shape[0]):
        h = jax.nn.silu(x @ wg[g]) * (x @ wu[g])
        y = y + weight[:, g:g + 1] * (h @ wd[g])
    return y


@pytest.mark.parametrize("skew", ["one_expert", "past_a_block", "even"])
def test_no_token_is_dropped_whatever_the_router_does(skew):
    """A router skewed onto one held expert sends every token there (five
    blocks of 8 rows, three experts with none), one a little less skewed
    sends two experts a few tokens more than a block holds; the result is
    the plain masked sum over the held experts, for every token, and so are
    the gradients of the backward that is written out."""
    chosen, args = _routed_case(skew)

    def mine(x, weight, wg, wu, wd):
        return routed_experts(x, weight, chosen, wg, wu, wd, 8)
    out = mine(*args)
    np.testing.assert_allclose(out, _plain_routed(*args), rtol=1e-4,
                               atol=1e-5)
    if skew == "one_expert":      # every token got its expert's part
        assert (np.abs(np.asarray(out)).sum(-1) > 0).all()
    for i, (got, want) in enumerate(zip(
            jax.grad(lambda *a: (mine(*a) ** 2).sum(), (0, 1, 2, 3, 4))(*args),
            jax.grad(lambda *a: (_plain_routed(*a) ** 2).sum(),
                     (0, 1, 2, 3, 4))(*args))):
        if i == 1:      # a weight exists only where the token chose
            got, want = (jnp.where(chosen, g, 0) for g in (got, want))
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_the_written_out_backward_keeps_its_types_under_bfloat16():
    """As the apply boundary hands them over: rows and experts' weights in
    bfloat16, routing weights in float32. Every gradient comes back in its
    argument's type and within bfloat16's rounding of the float32 one, under
    ``jit`` (the loops' lengths are traced values there)."""
    chosen, args = _routed_case("past_a_block", jnp.bfloat16)
    _, exact = _routed_case("past_a_block")

    @jax.jit
    def grads(*a):
        return jax.grad(lambda *b: (routed_experts(
            b[0], b[1], chosen, *b[2:], 8) ** 2).sum(), (0, 1, 2, 3, 4))(*a)
    want = jax.grad(lambda *a: (_plain_routed(*a) ** 2).sum(),
                    (0, 1, 2, 3, 4))(*exact)
    for a, got, w in zip(args, grads(*args), want):
        assert got.dtype == a.dtype and got.shape == a.shape
        got = jnp.where(chosen, got, 0) if got.shape == chosen.shape else got
        w = jnp.where(chosen, w, 0) if w.shape == chosen.shape else w
        gap = jnp.linalg.norm(got.astype(jnp.float32) - w) / jnp.linalg.norm(w)
        assert float(gap) < 0.03


def test_blockwise_attention_takes_a_value_narrower_than_the_key():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k = (jax.random.normal(kk, (2, 2, 40, 12)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (2, 2, 40, 8))
    got = blockwise_attention(q, k, v, causal=True, block_size=16)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(12)
    s = jnp.where(jnp.arange(40)[None, :] <= jnp.arange(40)[:, None], s,
                  -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    assert got.shape == (2, 2, 40, 8)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_published_cut_is_the_issues_count_and_cuts_no_width():
    z = mla_moe.PRESETS["kanana2_30b_a3b_cut16"]
    arch = CONFIG["arch"]
    assert mla_moe.parameter_count("kanana2_30b_a3b_cut16") \
        == family.parameter_count(arch) == 306_996_224 \
        == sum(int(np.prod(s)) for _, s, _ in family.param_spec(arch))
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "first_k_dense_replace"):
        assert z[key] == arch[key] == CONFIG[key], key
    assert (z["hidden_size"], z["intermediate_size"],
            z["moe_intermediate_size"], z["kv_lora_rank"]) \
        == (2048, 6144, 768, 512)
    assert z["n_routed_experts"] == arch["router_outputs"] == 128
    assert tuple(z["experts_held"]) == tuple(arch["experts_held"]) == (0, 8)
    assert (z["heads_held"], z["layers_held"], z["vocab_rows_held"]) \
        == (arch["num_attention_heads"], arch["num_hidden_layers"],
            arch["vocab_size"]) == (2, 5, 16032)
    assert arch["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 128,
        "num_attention_heads": 32, "vocab_size": 128256}
    assert arch["deployment"]["chips_per_layer"] == 16


def test_the_embedding_tables_gradient_is_summed_in_float32_under_bfloat16():
    """Where the apply boundary hands the parameters over in bfloat16, the
    backward of the embedding adds an id's occurrences up in float32: with
    four tokens in five the same id (1,600 of 2,048), the table's gradient
    stays within 2 % of the float32 one as a vector. Summed in bfloat16 it
    was 55 % off and half as long here, and 23 % off at the published cut
    (PERF.md section 2)."""
    module = MLAMoEDecoder(preset="mla_moe_tiny", remat=True)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(np.where(rng.random((128, 16)) < 0.8, 3,
                                  rng.integers(0, 64, (128, 16))), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), tokens[:1])["params"]

    def loss(p, dtype):
        p = jax.tree_util.tree_map(lambda l: l.astype(dtype), p)
        logits = module.apply({"params": p}, tokens).astype(jnp.float32)
        return family.nll(logits, jnp.roll(tokens, -1, axis=1)).mean()

    want = jax.grad(loss)(params, jnp.float32)["embed"]
    got = jax.grad(loss)(params, jnp.bfloat16)["embed"]
    assert got.dtype == jnp.float32
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 0.02
    assert abs(float(jnp.linalg.norm(got) / jnp.linalg.norm(want)) - 1) < 0.01
