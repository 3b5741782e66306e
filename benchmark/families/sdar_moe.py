"""Family ``sdar_moe``: a block-diffusion decoder (``model_type: sdar_moe``,
a Qwen3-MoE layer) of grouped-query attention and softmax-routed experts
with no shared expert, trained on the masked-diffusion objective over
blocks, cut to one chip's share of each layer.

The plain reference, from the published description and independent of the
program's ``models/``: float32 ``jax.numpy`` at ``Precision.HIGHEST``, the
attention over whole key rows under the dense [2L, 2L] mask built from the
three rules below, a dense loop over the held experts with a gate that is
nought where a token did not choose the expert, no cache, no kernel.
``arch`` is the block of that name in the configuration's file: the model's
own keys as this chip runs them (``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``vocab_size`` and
``experts_held`` are what is HELD here; ``router_outputs`` is the published
number of experts), the published counts under ``published`` and, under
``deployment``, the chips that share each layer and this chip's index.

A sample is [x_t ; x_0]: the noisy copy (positions 0..L-1) and the clean
copy (L..2L-1), both at rotary position ``i mod L``; the block of position i
is ``(i mod L) // block_length``.

    x  = E[tokens]                                  E: [V_held, hidden]
    h  = x + Attn(RMSNorm(x));  x' = h + MoE(RMSNorm(h))
    logits = RMSNorm(x'_noisy) W_head               [hidden, V_held]

``Attn``: q = W_q u -> H x d, k = W_k u, v = W_v u -> KV x d; q and k each
through RMSNorm over d with a learned scale, then the rotary embedding with
the halves turned (channel i with i + d/2); query head h reads key/value
head h // (published H / published KV); softmax of q k / sqrt(d) over the
keys that the mask lets through: a noisy query sees the noisy keys of its
own block and the clean keys of earlier blocks, a clean query the clean
keys of its own and earlier blocks; W_o over the H x d values. ``MoE``: p =
softmax(W_g u) over all ``router_outputs`` experts in float32, the
``num_experts_per_tok`` largest, weights p_e / (sum of the chosen p), y =
sum over the chosen AND HELD e of w_e SwiGLU_e(u).

The labels are packed: at a masked noisy position ``clean id + V_held x
level`` with the block's noise level t = (level + 1) / 1000, and -1 at the
others. ``nll`` is CE / t at a masked position and nought elsewhere, so its
mean over a batch is the masked-diffusion bound a noisy position.

Departures from the published description, each ``assumed`` in the
configuration's file:
- block length, noise schedule and the mask id are set here (``config.json``
  names none of them); the noise of a sequence is drawn with the data, once,
  and not afresh at each step (the reference job hands a family no key);
- the router's weight is drawn on bfloat16's grid, so that the program's
  apply boundary, which rounds every parameter to bfloat16 before the
  router's float32 product, starts from the same numbers;
- weights are normal(0, 0.02) from the seed, unit norm scales;
- the chip's share: the absent heads' part of ``o W_o`` and the absent
  experts' part of the routed sum are left out; ids and logits are over the
  held rows of the vocabulary.
Each layer is under ``jax.checkpoint``; the numbers are those of the plain
pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LEVELS = 1000

# The ``jax.named_scope`` names of the program's module of this family
# (``feddrift_tpu/models/sdar_moe.py``), in the order ``xplane.scope_of``
# tries them.
DEVICE_SCOPES = ("lm_head", "expert_layer", "bd_attention")


# ----------------------------------------------------------------------
# parameters
def _attn_shapes(arch: dict):
    D, d = arch["hidden_size"], arch["head_dim"]
    H, KV = arch["num_attention_heads"], arch["num_key_value_heads"]
    return [("wq", (D, H * d), "weight"), ("wk", (D, KV * d), "weight"),
            ("wv", (D, KV * d), "weight"), ("wo", (H * d, D), "weight"),
            ("q_norm", (d,), "scale"), ("k_norm", (d,), "scale")]


def _moe_shapes(arch: dict):
    D, F = arch["hidden_size"], arch["moe_intermediate_size"]
    G = arch["experts_held"][1] - arch["experts_held"][0]
    return [("router", (D, arch["router_outputs"]), "router"),
            ("w_gate", (G, D, F), "weight"), ("w_up", (G, D, F), "weight"),
            ("w_down", (G, F, D), "weight")]


def param_spec(arch: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """(name, shape, role) of every parameter in forward order; role is
    weight | scale | router. The router keeps the published number of
    experts."""
    D = arch["hidden_size"]
    spec = [("embed", (arch["vocab_size"], D), "weight")]
    for l in range(arch["num_hidden_layers"]):
        spec.append((f"l{l}/attn_norm", (D,), "scale"))
        spec += [(f"l{l}/{n}", s, r) for n, s, r in _attn_shapes(arch)]
        spec.append((f"l{l}/ffn_norm", (D,), "scale"))
        spec += [(f"l{l}/{n}", s, r) for n, s, r in _moe_shapes(arch)]
    return spec + [("final_norm", (D,), "scale"),
                   ("head", (D, arch["vocab_size"]), "weight")]


def draw(role: str, key, shape: tuple[int, ...], num_models: int):
    """[num_models, *shape] float32: normal(0, 0.02) weights, unit scales;
    the router's weight on bfloat16's grid."""
    if role == "scale":
        return jnp.ones((num_models, *shape), jnp.float32)
    w = 0.02 * jax.random.normal(key, (num_models, *shape), jnp.float32)
    if role == "router":
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    return w


def parameter_count(arch: dict) -> int:
    """The closed form, which the tests hold ``param_spec`` and a count
    made by hand to."""
    D, d, F = arch["hidden_size"], arch["head_dim"], \
        arch["moe_intermediate_size"]
    H, KV = arch["num_attention_heads"], arch["num_key_value_heads"]
    G = arch["experts_held"][1] - arch["experts_held"][0]
    layer = 2 * D * H * d + 2 * D * KV * d + 2 * d + 2 * D \
        + D * arch["router_outputs"] + G * 3 * D * F
    return arch["num_hidden_layers"] * layer \
        + 2 * arch["vocab_size"] * D + D


# ----------------------------------------------------------------------
# the forward pass
def _mm(a, b, dtype=None):
    if dtype is not None:
        a, b = a.astype(dtype), b.astype(dtype)
    return jnp.matmul(a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta: float, half: int):
    """x [N, 2L, heads, d]: channels i and i + d/2 turned by (position mod
    L) x theta^(-2i/d)."""
    P, d = x.shape[1], x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = (jnp.arange(P) % half).astype(jnp.float32)[:, None] * freq[None]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def live_mask(half: int, block: int):
    """[2L, 2L] bool: whether query i may see key j, by the three rules."""
    i = jnp.arange(2 * half)[:, None]
    j = jnp.arange(2 * half)[None, :]
    bi, bj = (i % half) // block, (j % half) // block
    noisy_i, noisy_j = i < half, j < half
    return (noisy_i & noisy_j & (bj == bi)) \
        | (noisy_i & ~noisy_j & (bj < bi)) \
        | (~noisy_i & ~noisy_j & (bj <= bi))


def _attention(arch, p, pre, u, dtype):
    B, P, _ = u.shape
    H, KV, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                arch["head_dim"])
    eps, theta = arch["rms_norm_eps"], float(arch["rope_theta"])
    half = P // 2
    q = _mm(u, p[f"{pre}/wq"], dtype).reshape(B, P, H, d)
    k = _mm(u, p[f"{pre}/wk"], dtype).reshape(B, P, KV, d)
    v = _mm(u, p[f"{pre}/wv"], dtype).reshape(B, P, KV, d)
    q = _rope(_rms_norm(q, p[f"{pre}/q_norm"], eps), theta, half)
    k = _rope(_rms_norm(k, p[f"{pre}/k_norm"], eps), theta, half)
    group = arch["published"]["num_attention_heads"] \
        // arch["published"]["num_key_value_heads"]
    kv_of = jnp.asarray([h // group for h in range(H)])
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / math.sqrt(d)
    mask = live_mask(half, arch["block_length"])
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    return _mm(o.reshape(B, P, H * d), p[f"{pre}/wo"], dtype)


def _swiglu(u, w_gate, w_up, w_down, dtype):
    return _mm(jax.nn.silu(_mm(u, w_gate, dtype)) * _mm(u, w_up, dtype),
               w_down, dtype)


def _expert_layer(arch, p, pre, u, dtype):
    B, P, D = u.shape
    x = u.reshape(B * P, D)
    lo, hi = arch["experts_held"]
    # the router in float32 as published, whatever ``dtype`` lowers
    probs = jax.nn.softmax(_mm(x, p[f"{pre}/router"]), axis=-1)
    p_top, top = jax.lax.top_k(probs, arch["num_experts_per_tok"])
    if arch["norm_topk_prob"]:
        p_top = p_top / p_top.sum(-1, keepdims=True)
    y = 0.0
    for g in range(hi - lo):        # every token, times its gate
        gate = (p_top * (top == lo + g)).sum(-1, keepdims=True)
        y = y + gate * _swiglu(x, p[f"{pre}/w_gate"][g], p[f"{pre}/w_up"][g],
                               p[f"{pre}/w_down"][g], dtype)
    return y.reshape(B, P, D)


def _layer(arch, l, dtype, p, x):
    pre, eps = f"l{l}", arch["rms_norm_eps"]
    h = x + _attention(arch, p, pre,
                       _rms_norm(x, p[f"{pre}/attn_norm"], eps), dtype)
    return h + _expert_layer(arch, p, pre,
                             _rms_norm(h, p[f"{pre}/ffn_norm"], eps), dtype)


def forward(arch: dict, p: dict, x, dtype=None):
    """Logits [N, L, held rows] of the noisy copy, from token ids [N, 2L].
    ``dtype`` lowers the operands of the projections', the experts' and the
    head's products (the look at the configuration's compute precision);
    the router and the attention's own products stay in float32."""
    h = p["embed"].astype(jnp.float32)[x]
    for l in range(arch["num_hidden_layers"]):
        mine = {k: v for k, v in p.items() if k.startswith(f"l{l}/")}
        h = jax.checkpoint(
            lambda q, z, l=l: _layer(arch, l, dtype, q, z))(mine, h)
    half = x.shape[1] // 2
    return _mm(_rms_norm(h[:, :half], p["final_norm"], arch["rms_norm_eps"]),
               p["head"], dtype)


def _unpack(y, rows: int):
    masked = y >= 0
    ids = jnp.where(masked, y % rows, 0)
    t = (jnp.where(masked, y // rows, 0) + 1).astype(jnp.float32) / LEVELS
    return masked, ids, t


def nll(logits, y):
    """[N, L] of logits [N, L, rows] and packed labels [N, L]: CE / t at a
    masked position, nought at the others."""
    masked, ids, t = _unpack(y, logits.shape[-1])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ce = -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
    return jnp.where(masked, ce / t, 0.0)


def hits(logits, y):
    """One 0/1 per noisy position: masked, and the first choice is the
    clean id."""
    masked, ids, _ = _unpack(y, logits.shape[-1])
    return masked & (logits.argmax(-1) == ids)


# ----------------------------------------------------------------------
# operations
def live_pairs(arch: dict) -> int:
    """(query, key) pairs of a sequence that the mask lets through, a head
    and a layer: L x block in the noisy copy's own blocks, L ** 2 on the
    clean blocks (of the (2L) ** 2, about a quarter)."""
    L, b = arch["seq_len"], arch["block_length"]
    return L * b + L * L


def _projection_macs(arch: dict) -> int:
    """A position, a layer: W_q, W_k, W_v, W_o."""
    D, d = arch["hidden_size"], arch["head_dim"]
    H, KV = arch["num_attention_heads"], arch["num_key_value_heads"]
    return 2 * D * H * d + 2 * D * KV * d


def forward_macs(arch: dict) -> int:
    """Multiply-accumulates of one sequence's forward pass (``seq_len``
    tokens, 2 x ``seq_len`` positions through the stack): the projections,
    the router, the attention's two products over the LIVE (query, key)
    pairs only, the held experts in expectation under even routing
    (``num_experts_per_tok`` x held / ``router_outputs`` assignments a
    position: 0.5 at 8 x 8 / 128) and the head over the noisy copy."""
    L, D = arch["seq_len"], arch["hidden_size"]
    F, E, K = (arch["moe_intermediate_size"], arch["router_outputs"],
               arch["num_experts_per_tok"])
    G = arch["experts_held"][1] - arch["experts_held"][0]
    layers, P = arch["num_hidden_layers"], 2 * L
    per_position = _projection_macs(arch) + D * E
    attn = arch["num_attention_heads"] * live_pairs(arch) \
        * 2 * arch["head_dim"]
    routed = P * 3 * D * F * K * G // E
    return layers * (P * per_position + attn + routed) \
        + L * D * arch["vocab_size"]


def bd_attention_work(arch: dict) -> tuple[int, int]:
    """(FLOPs, bytes) of the scope ``bd_attention`` in one forward pass of
    one sequence over the held layers: the four projections and the two
    products over the live pairs; the layer's input read and its output
    written in bfloat16, the four weights read in bfloat16, and q, k, v and
    the attention's output o written and read once in float32."""
    D, d = arch["hidden_size"], arch["head_dim"]
    H, KV = arch["num_attention_heads"], arch["num_key_value_heads"]
    layers, P = arch["num_hidden_layers"], 2 * arch["seq_len"]
    macs = P * _projection_macs(arch) + H * live_pairs(arch) * 2 * d
    qkvo = P * (2 * H + 2 * KV) * d * 4 * 2
    weights = (2 * D * H * d + 2 * D * KV * d) * 2
    return 2 * layers * macs, layers * (qkvo + weights + 2 * P * D * 2)


# ----------------------------------------------------------------------
# the program's side: its nesting of the parameters, one sample's shapes
def to_program_tree(arch: dict, flat: dict) -> dict:
    """The flat parameter dict in the nesting of the program's
    ``models/sdar_moe.py::SDARDecoder``."""
    tree = {"embed": flat["embed"], "final_norm": flat["final_norm"],
            "head": flat["head"]}
    for l in range(arch["num_hidden_layers"]):
        pre = f"l{l}"
        tree[f"layer_{l}"] = {
            "attn_norm": flat[f"{pre}/attn_norm"],
            "ffn_norm": flat[f"{pre}/ffn_norm"],
            "attn": {n: flat[f"{pre}/{n}"] for n, _, _ in _attn_shapes(arch)},
            "moe": {n: flat[f"{pre}/{n}"] for n, _, _ in _moe_shapes(arch)}}
    return tree


def sample_shapes(arch: dict) -> dict:
    """Shapes and types of one sample (the noisy and clean copies, and a
    packed label a noisy position), for lowering a round program with
    shapes only."""
    L = arch["seq_len"]
    return {"x": ((2 * L,), "int32"), "y": ((L,), "int32"),
            "num_classes": arch["vocab_size"]}
