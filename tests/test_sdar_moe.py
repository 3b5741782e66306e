"""The block-diffusion, sparse-expert decoder (``models/sdar_moe.py``)
against its plain reference (``benchmark/families/sdar_moe.py``) on seeded
weights at the tiny preset: logits of the noisy copy, the 1/t-weighted
loss and every gradient; the block-diffusion mask rule by rule; the chip's
share against the uncut layer; the closed forms against counts made by
hand; the data and the objective. CPU, float32."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import weights                                   # noqa: E402
from benchmark.families import sdar_moe as family                # noqa: E402
from benchmark.run import load_json, overlay                     # noqa: E402
from feddrift_tpu.data.text import (generate_token_diffusion,    # noqa: E402
                                    generate_token_drift)
from feddrift_tpu.models import sdar_moe                          # noqa: E402
from feddrift_tpu.models.sdar_moe import (ATTENTION_TILE,  # noqa: E402
                                          BlockDiffusionAttention,
                                          SDARDecoder, SoftmaxExperts)
from feddrift_tpu.parallel.ring_attention import (  # noqa: E402
    _tile_lanes, block_diffusion_mask, blockwise_attention, clean_key_tiles,
    live_pairs, pairs_computed)

CONFIG = load_json("configs", "sdar_30b_a3b_chat.json")
TINY = overlay(CONFIG, CONFIG["rehearse"])["arch"]
L, BLOCK, ROWS = 16, 4, 64


def _seeded(seed=3):
    flat = {k: v[0] for k, v in weights.make_weights(TINY, seed, 1).items()}
    ds = generate_token_diffusion(np.zeros((2, 2), int), 1, 1, 2,
                                  seq_len=L, vocab=ROWS, seed=seed)
    return flat, jnp.asarray(ds.x[0, 0]), jnp.asarray(ds.y[0, 0])


def test_noisy_logits_and_counts_agree_with_the_plain_reference():
    flat, x, y = _seeded()
    module = SDARDecoder(preset="sdar_moe_tiny", remat=True)
    tree = weights.to_program_tree(TINY, flat)
    mine, stats = module.apply({"params": tree}, x, return_stats=True)
    want = family.forward(TINY, flat, x)
    assert x.shape == (2, 2 * L) and mine.shape == want.shape == (2, L, ROWS)
    np.testing.assert_allclose(mine, want, rtol=2e-4, atol=2e-6)
    # 2 layers x 2 sequences x 32 positions; each chose 4 of 16 experts
    assert int(stats["expert_tokens"]) == 128
    assert 0 < int(stats["expert_load"].sum()) <= 128 * 4
    assert int(stats["bd_noisy_positions"]) == 2 * L
    assert int(stats["bd_masked_positions"]) == int((y >= 0).sum()) \
        == int((x[:, :L] == ROWS - 1).sum())
    assert int(stats["bd_live_attention_pairs"]) == 2 * (L * L + L * BLOCK)
    # one tile of 16 positions: both copies' queries on the clean keys, and
    # the noisy copy's own blocks
    assert int(stats["bd_attention_pairs_computed"]) \
        == 2 * pairs_computed(L, BLOCK, ATTENTION_TILE) \
        == 2 * (2 * L * L + L * BLOCK)
    own = module.init(jax.random.PRNGKey(0), x)["params"]
    assert jax.tree_util.tree_structure(own) \
        == jax.tree_util.tree_structure(tree)
    assert [l.shape for l in jax.tree_util.tree_leaves(own)] \
        == [l.shape for l in jax.tree_util.tree_leaves(tree)]


def test_the_loss_and_every_parameters_gradient_agree_with_the_reference():
    """The program's objective and the family's ``nll`` averaged, as the
    reference job takes it; float32 on the CPU: the two differ in the
    order of their sums alone (blockwise against whole rows, a grouped
    product against a dense gate), so 2e-3 of each gradient's largest
    entry and 1e-5 of the loss."""
    flat, x, y = _seeded(7)
    module = SDARDecoder(preset="sdar_moe_tiny", remat=True)

    def mine(tree):
        return SDARDecoder.objective(module.apply({"params": tree}, x), y)

    def plain(p):
        return family.nll(family.forward(TINY, p, x), y).mean()
    tree = weights.to_program_tree(TINY, flat)
    np.testing.assert_allclose(mine(tree), plain(flat), rtol=1e-5)
    got = weights.from_program_tree(TINY, jax.grad(mine)(tree))
    want = jax.grad(plain)(flat)
    assert set(got) == set(want) == {n for n, _, _ in family.param_spec(TINY)}
    for name in want:
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=name)


# ----------------------------------------------------------------------
# the mask, rule by rule
def _rule(name, i, j):
    """None where the rule says nothing of (i, j), else whether j is seen."""
    noisy_i, noisy_j = i < L, j < L
    bi, bj = (i % L) // BLOCK, (j % L) // BLOCK
    if name == "noisy_sees_its_own_noisy_block":
        return (bj == bi) if noisy_i and noisy_j else None
    if name == "noisy_sees_earlier_clean_blocks":
        return (bj < bi) if noisy_i and not noisy_j else None
    if name == "clean_sees_its_own_and_earlier_clean_blocks":
        return (bj <= bi) if not noisy_i and not noisy_j else None
    if name == "clean_never_sees_noisy":
        return False if not noisy_i and noisy_j else None
    raise KeyError(name)


RULES = ("noisy_sees_its_own_noisy_block", "noisy_sees_earlier_clean_blocks",
         "clean_sees_its_own_and_earlier_clean_blocks",
         "clean_never_sees_noisy")


def _broken(name, mask) -> list:
    """(i, j) pairs of a [2L, 2L] mask that the rule ``name`` refuses."""
    return [(i, j) for i in range(2 * L) for j in range(2 * L)
            if _rule(name, i, j) is not None
            and bool(mask[i, j]) != _rule(name, i, j)]


def _program_mask():
    pos = jnp.arange(2 * L)
    return np.asarray(block_diffusion_mask(L, BLOCK)(pos[:, None],
                                                     pos[None, :]))


@pytest.mark.parametrize("rule", RULES)
def test_the_mask_keeps_each_rule_in_the_program_and_the_reference(rule):
    assert _broken(rule, _program_mask()) == []
    assert _broken(rule, np.asarray(family.live_mask(L, BLOCK))) == []


def test_a_mask_that_lets_noisy_queries_see_their_own_clean_block_fails():
    """Planted: noisy -> clean with blk(j) <= blk(i); the rule refuses it,
    and the attention under it is not the attention under the mask."""
    mask = _program_mask().copy()
    i, j = np.meshgrid(np.arange(2 * L), np.arange(2 * L), indexing="ij")
    mask[(i < L) & (j >= L) & ((j % L) // BLOCK <= (i % L) // BLOCK)] = True
    broken = _broken("noisy_sees_earlier_clean_blocks", mask)
    assert len(broken) == L * BLOCK
    assert all((j - L) // BLOCK == i // BLOCK for i, j in broken)
    for rule in RULES:
        if rule != "noisy_sees_earlier_clean_blocks":
            assert _broken(rule, mask) == [], rule
    q, k, v = (jax.random.normal(kk, (1, 2, 2 * L, 8))
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    got = blockwise_attention(q, k[:, :1], v[:, :1], diffusion_block=BLOCK,
                              block_size=8)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k[:, :1], 2, 1)) \
        / np.sqrt(8)
    planted = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(mask, s, -jnp.inf), -1), jnp.repeat(v[:, :1], 2, 1))
    assert float(jnp.abs(got - planted).max()) > 1e-2


def test_live_pairs_are_counted_as_the_mask_has_them():
    assert int(_program_mask().sum()) == live_pairs(L, BLOCK) \
        == L * BLOCK + L * L == family.live_pairs({"seq_len": L,
                                                   "block_length": BLOCK})
    # at the cell's length, a quarter of the (2L) ** 2 pairs and a little
    assert live_pairs(4096, 4) / (2 * 4096) ** 2 == pytest.approx(0.25024,
                                                                  abs=1e-5)


@pytest.mark.parametrize("kv,block_size", [(1, 8), (2, 16), (4, 12), (1, 6),
                                           (2, 10), (4, 3), (1, 2)])
def test_blockwise_attention_under_the_mask_with_grouped_heads(kv, block_size):
    """Query head h reads key/value head h // (H / KV); the forward and the
    gradients against whole rows under the dense mask. Tiles of 6, 10 and
    3 positions are no whole number of blocks of 4, so a block straddles
    two tiles and a query tile reads the key tile after its own; 3 and 2
    make an odd and an even number of tiles narrower than a block."""
    H, D = 4, 8
    ks = jax.random.split(jax.random.PRNGKey(kv), 3)
    q = jax.random.normal(ks[0], (2, H, 2 * L, D))
    k, v = (jax.random.normal(kk, (2, kv, 2 * L, D)) for kk in ks[1:])
    mask = family.live_mask(L, BLOCK)

    def dense(q, k, v):
        kk, vv = (jnp.repeat(a, H // kv, axis=1) for a in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / np.sqrt(D)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1),
                          vv)

    def mine(q, k, v):
        return blockwise_attention(q, k, v, diffusion_block=BLOCK,
                                   block_size=block_size)
    np.testing.assert_allclose(mine(q, k, v), dense(q, k, v), rtol=1e-5,
                               atol=1e-5)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, H, 2 * L, D))
    for g_mine, g_dense in zip(
            jax.grad(lambda *a: (mine(*a) * w).sum(), (0, 1, 2))(q, k, v),
            jax.grad(lambda *a: (dense(*a) * w).sum(), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g_mine, g_dense, rtol=1e-4, atol=1e-4)


def test_the_tiles_formed_hold_every_live_pair_at_the_cells_size():
    """The mask evaluated in numpy at the cell's size (L = 4,096, blocks of
    4, tiles of 512): a noisy query's live noisy keys are its own block's,
    which the block product forms; a clean query sees no noisy key; every
    (query tile, clean key tile) that holds a live pair is a live step of
    the scan's lanes, once, and no other is; the pairs formed are
    ``pairs_computed``, which the model counts."""
    half, block, tile = 4096, 4, ATTENTION_TILE
    n = half // tile
    t_q, t_k, start, live, ends = _tile_lanes(
        clean_key_tiles(half, block, tile))
    formed = sorted(zip(t_q[live].tolist(), t_k[live].tolist()))
    assert len(formed) == len(set(formed)) == n * (n + 1) // 2 == 36
    assert t_q.shape == (n + 1, n // 2) and live.all()
    key = np.arange(2 * half)
    needed = set()
    for t in range(2 * n):                   # a tile of either copy's queries
        i = np.arange(t * tile, (t + 1) * tile)[:, None]
        bi, bj = (i % half) // block, (key % half) // block
        noisy_i, noisy_j = i < half, key < half
        seen = np.where(noisy_i, np.where(noisy_j, bj == bi, bj < bi),
                        ~noisy_j & (bj <= bi))
        np.testing.assert_array_equal(seen[:, :half],
                                      noisy_i & (bj == bi)[:, :half])
        live_tiles = seen[:, half:].reshape(tile, n, tile).any(axis=(0, 2))
        needed |= {(t % n, u) for u in np.flatnonzero(live_tiles).tolist()}
    assert sorted(needed) == formed
    computed = half * block + 2 * tile * tile * len(formed)
    assert computed == pairs_computed(half, block, tile) == 18_890_752
    assert computed / (2 * half) ** 2 == pytest.approx(0.2815, abs=1e-4)
    assert computed / live_pairs(half, block) == pytest.approx(1.125,
                                                               abs=1e-3)


# ----------------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips share a layer: each computes its 4 of 16 experts' part,
    and its 2 of 8 query heads with the key/value head they read (2 heads of
    key/value, 4 query heads each): the partial sums of its rows of W_o.
    The shares' sum is what the uncut layer gives."""
    D, E, K, F, SHARES = 32, 16, 4, 16, 4
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 2 * L, D))

    def experts(held):
        return SoftmaxExperts(E, K, held, F, True)
    whole = experts((0, E))
    p = whole.init(jax.random.PRNGKey(1), u)["params"]
    full, load, _ = whole.apply({"params": p}, u)
    assert int(load.sum()) == 2 * 2 * L * K
    total = 0.0
    for i in range(SHARES):
        lo, hi = i * E // SHARES, (i + 1) * E // SHARES
        mine = {k: v[lo:hi] if k != "router" else v for k, v in p.items()}
        part, part_load, _ = experts((lo, hi)).apply({"params": mine}, u)
        np.testing.assert_array_equal(part_load, load[lo:hi])
        total = total + part
    np.testing.assert_allclose(total, full, rtol=1e-4, atol=1e-6)
    # attention: 8 query heads over 2 key/value heads of 16
    H, KV, d = 8, 2, 16

    def attention(heads, kv):
        return BlockDiffusionAttention(heads, kv, d, 1e4, 1e-6, BLOCK)
    pa = attention(H, KV).init(jax.random.PRNGKey(3), u)["params"]
    want = attention(H, KV).apply({"params": pa}, u)
    got = 0.0
    group = H // KV
    for i in range(SHARES):
        heads = slice(i * 2 * d, (i + 1) * 2 * d)
        kv = (2 * i) // group
        got = got + attention(2, 1).apply({"params": {
            "wq": pa["wq"][:, heads], "wo": pa["wo"][heads],
            "wk": pa["wk"][:, kv * d:(kv + 1) * d],
            "wv": pa["wv"][:, kv * d:(kv + 1) * d],
            "q_norm": pa["q_norm"], "k_norm": pa["k_norm"]}}, u)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_the_published_cut_counted_by_hand():
    """A layer: W_q 2,048 x 256, W_k and W_v 2,048 x 128, W_o 256 x 2,048,
    the router 2,048 x 128, 8 experts of 3 x 2,048 x 768, four norms
    (2 x 2,048 + 2 x 128); the embedding and the head 2 x 18,992 x 2,048;
    the final norm."""
    arch = CONFIG["arch"]
    layer = 524_288 + 2 * 262_144 + 524_288 + 262_144 + 37_748_736 + 4_352
    assert layer == 39_588_096
    by_hand = 4 * layer + 77_791_232 + 2_048
    assert by_hand == 236_145_664 == CONFIG["parameter_count"]
    assert sdar_moe.parameter_count("sdar_30b_a3b_chat") \
        == family.parameter_count(arch) == by_hand \
        == sum(int(np.prod(s)) for _, s, _ in family.param_spec(arch))
    z = sdar_moe.PRESETS["sdar_30b_a3b_chat"]
    for key in ("hidden_size", "moe_intermediate_size", "head_dim",
                "num_experts_per_tok", "rms_norm_eps", "norm_topk_prob"):
        assert z[key] == arch[key] == CONFIG[key], key
    assert (z["num_experts"], z["num_attention_heads"],
            z["num_key_value_heads"], z["vocab_size"]) \
        == tuple(arch["published"][k] for k in (
            "num_experts", "num_attention_heads", "num_key_value_heads",
            "vocab_size")) == (128, 32, 4, 151936)
    assert (z["heads_held"], z["kv_heads_held"], z["layers_held"],
            z["vocab_rows_held"], tuple(z["experts_held"])) \
        == (2, 1, 4, 18992, (0, 8)) and 18992 * 8 == 151936
    sdar_moe.check_heads("sdar_30b_a3b_chat")
    sdar_moe.check_heads("sdar_moe_tiny")


def test_forward_macs_by_hand():
    """A sequence: 8,192 positions through 4 layers of projections
    (1,572,864), router (262,144) and held experts at 0.5 assignments
    (2,359,296): 137.4 G; the two products over the live pairs, 2 heads x
    (4,096 ** 2 + 4,096 x 4) x 256 x 4 layers: 34.4 G; the head over the
    noisy half, 4,096 x 2,048 x 18,992: 159.3 G."""
    arch = CONFIG["arch"]
    per_position = 1_572_864 + 262_144 + 2_359_296
    assert per_position == 4_194_304
    layers = 4 * 8192 * per_position
    attention = 4 * 2 * (4096 ** 2 + 4096 * 4) * 256
    head = 4096 * 2048 * 18992
    assert (layers, attention, head) == (137_438_953_472, 34_393_292_800,
                                         159_316_443_136)
    assert family.forward_macs(arch) == layers + attention + head \
        == 331_148_689_408
    # the scope's share: projections and products, a forward of 4 layers
    flops, nbytes = family.bd_attention_work(arch)
    assert flops == 2 * (4 * 8192 * 1_572_864 + attention)
    assert nbytes > 0
    # the tiny size: 32 positions, 2 layers, 4 of 16 experts, 4 a token
    tiny_pos = (2 * 64 * 32 + 2 * 64 * 16) + 64 * 16 \
        + 3 * 64 * 32 * 4 * 4 // 16
    assert family.forward_macs(TINY) == 2 * (32 * tiny_pos + 2 * (
        16 * 16 + 16 * 4) * 32) + 16 * 64 * 64


# ----------------------------------------------------------------------
def test_the_data_is_token_drifts_law_with_the_noise_of_each_block():
    cp = np.array([[0, 0], [0, 1], [1, 1]])
    ds = generate_token_diffusion(cp, 2, 2, 3, seq_len=L, vocab=ROWS,
                                  seed=2 ** 31 + 11)
    clean = generate_token_drift(cp, 2, 2, 3, seq_len=L, vocab=ROWS - 1,
                                 seed=2 ** 31 + 11)
    assert ds.x.shape == (2, 3, 3, 2 * L) and ds.y.shape == (2, 3, 3, L)
    np.testing.assert_array_equal(ds.x[..., L:], clean.x)
    np.testing.assert_array_equal(ds.concepts, clean.concepts)
    masked, ids, t = (np.asarray(a) for a in
                      sdar_moe.unpack_labels(jnp.asarray(ds.y), ROWS))
    assert (masked == (ds.x[..., :L] == ROWS - 1)).all()
    assert (ds.x[..., :L][~masked] == clean.x[~masked]).all()
    assert (ids[masked] == clean.x[masked]).all()
    assert clean.x.max() < ROWS - 1
    # one noise level a block, on the grid of 1,000 in [1e-3, 1]
    tb = t.reshape(t.shape[:-1] + (L // BLOCK, BLOCK))
    mb = masked.reshape(tb.shape)
    top = np.where(mb, tb, 0.0).max(-1, keepdims=True)
    assert ((tb == top) | ~mb).all()
    assert t[masked].min() >= 1e-3 and t[masked].max() <= 1.0
    big = generate_token_diffusion(cp, 2, 2, 8, seq_len=256, vocab=ROWS,
                                   seed=7)
    assert 0.45 < (big.y >= 0).mean() < 0.55
    np.testing.assert_array_equal(
        big.y, generate_token_diffusion(cp, 2, 2, 8, seq_len=256, vocab=ROWS,
                                        seed=7).y)


def test_the_data_set_by_name_and_the_block_the_model_takes():
    """Dataset ``token_diffusion`` states text.py's block in its meta, the
    block of both presets; the model refuses next-token data and data of
    another block."""
    import dataclasses

    from feddrift_tpu.config import ExperimentConfig
    from feddrift_tpu.data.registry import make_dataset
    from feddrift_tpu.data.text import DIFFUSION_BLOCK
    from feddrift_tpu.models import create_model
    cfg = ExperimentConfig(model="sdar_moe_tiny", dataset="token_diffusion",
                           text_seq_len=L, token_vocab=ROWS, sample_num=2,
                           train_iterations=2, client_num_in_total=2,
                           client_num_per_round=2, change_points="0 0;0 1;1 1")
    ds = make_dataset(cfg)
    assert ds.name == "token_diffusion"
    assert ds.meta["diffusion_block"] == DIFFUSION_BLOCK == BLOCK
    assert {z["block_length"] for z in sdar_moe.PRESETS.values()} == {BLOCK}
    assert isinstance(create_model(cfg.model, ds, cfg), SDARDecoder)
    for bad in (make_dataset(dataclasses.replace(cfg, dataset="token_drift")),
                dataclasses.replace(ds, meta={**ds.meta,
                                              "diffusion_block": 2 * BLOCK})):
        with pytest.raises(ValueError, match="token_diffusion"):
            create_model(cfg.model, bad, cfg)


def test_the_objective_by_hand():
    """Two sequences of 4 positions over 3 rows: the masked ones carry CE /
    t, the sum divided by all positions; hits and labels are the masked
    positions'."""
    logits = jnp.log(jnp.array([[[0.5, 0.25, 0.25]] * 4,
                                [[0.2, 0.6, 0.2]] * 4]))
    level = jnp.array([[499, 499, 0, 0], [999, 999, 999, 999]])
    clean = jnp.array([[0, 1, 2, 0], [1, 1, 0, 2]])
    masked = jnp.array([[True, True, False, False],
                        [True, False, True, False]])
    y = jnp.where(masked, clean + 3 * level, -1)
    assert y.tolist() == [[1497, 1498, -1, -1], [2998, -1, 2997, -1]]
    want = (-np.log(0.5) / 0.5 - np.log(0.25) / 0.5 - np.log(0.6)
            - np.log(0.2)) / 8
    assert float(sdar_moe.diffusion_loss(logits, y)) == pytest.approx(want)
    assert float(family.nll(logits, y).mean()) == pytest.approx(want)
    hits, loss, labels = sdar_moe.diffusion_counts(logits, y)
    assert (int(hits), int(labels)) == (2, 4)
    assert int(family.hits(logits, y).sum()) == 2
    assert float(loss) == pytest.approx(8 * want)


# ----------------------------------------------------------------------
def _experiment(**kw):
    from feddrift_tpu.config import ExperimentConfig
    from feddrift_tpu.simulation.runner import Experiment
    base = dict(model="sdar_moe_tiny", dataset="token_diffusion",
                text_seq_len=L, token_vocab=ROWS,
                sample_num=4, batch_size=2, epochs=2, client_optimizer="sgd",
                lr=0.05, wd=0.0, client_axis="scan", remat=True,
                concept_drift_algo="softclusterwin-1",
                concept_drift_algo_arg="hard-r", concept_num=2, comm_round=2,
                frequency_of_the_test=2, train_iterations=2,
                client_num_in_total=2, client_num_per_round=2,
                change_points="0 0;0 1;1 1", cost_model="off",
                checkpoint_every_iteration=False)
    base.update(kw)
    exp = Experiment(ExperimentConfig(**base))
    exp.run()
    return exp


def test_the_experiment_trains_and_evaluates_on_masked_positions():
    """Through ``Experiment`` on the scanned client axis: the round returns
    the model's counts, the labels of an evaluation are the masked
    positions of the data it reads (the test half's of time step t + 1),
    and the vmap body gives the same series and pool."""
    from feddrift_tpu import obs
    obs.registry().reset()
    exp = _experiment()
    snap = obs.registry().snapshot()
    assert snap["bd_noisy_positions"] > 0
    share = snap["bd_masked_positions"] / snap["bd_noisy_positions"]
    assert 0.2 < share < 0.8
    # 2 time steps x 2 rounds x 2 clients x 2 local steps x 2 sequences
    assert snap["bd_live_attention_pairs"] == 32 * live_pairs(L, BLOCK)
    assert snap["bd_attention_pairs_computed"] \
        == 32 * pairs_computed(L, BLOCK, ATTENTION_TILE)
    assert not exp.step.fuses_rounds
    correct, _, total = exp.step.acc_matrix(
        exp.pool.params, exp.x[:, 2], exp.y[:, 2],
        exp.algo._ones_feat_mask)
    np.testing.assert_array_equal(
        np.asarray(total)[:2], (exp.ds.y[:, 2] >= 0).sum(axis=(1, 2)))
    assert (np.asarray(correct) <= np.asarray(total)[None]).all()
    acc = exp.logger.series("Test/Acc")
    assert acc and all(0.0 <= a[-1] <= 1.0 for a in acc)
    # the last evaluation's test half: hits over the masked positions of
    # time step 2, by the model each client tests with
    idx = np.asarray(exp.algo.test_model_idx(1))[:2]
    hits = np.asarray(correct)[idx, np.arange(2)].sum()
    assert acc[-1][-1] == pytest.approx(hits / np.asarray(total)[:2].sum())
    other = _experiment(client_axis="vmap")
    for name in ("Test/Acc", "Train/Acc", "Train/Loss", "Test/Loss"):
        np.testing.assert_allclose(
            [a[-1] for a in other.logger.series(name)],
            [a[-1] for a in exp.logger.series(name)], rtol=1e-4, atol=1e-6)
