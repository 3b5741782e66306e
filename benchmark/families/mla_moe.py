"""Family ``mla_moe``: a decoder (``model_type: deepseek_v3``) of latent
attention with no query rank, leading dense layers and sparse-expert layers
with shared experts, cut to one chip's share of each layer; a label per
token.

The plain reference, from the published description and independent of the
program's ``models/``: float32 ``jax.numpy`` at ``Precision.HIGHEST``, masked
attention over whole key rows, a dense loop over the held experts with a
mask (no sorting, no grouped product: gathering each expert's own rows was
tried and ran no faster on the chip, 302 s against 271 s a comparison; my
chip runs, PR 29), no cache, no kernel. ``arch`` is the
block of that name in the configuration's file: the model's own keys as this
chip runs them (``num_hidden_layers``, ``num_attention_heads``,
``vocab_size`` and ``experts_held`` are what is HELD here; ``router_outputs``
is the published number of routed experts), the published counts under
``published``, and under ``deployment`` the number of chips that share each
layer and this chip's index among them.

    x  = E[tokens]                                  E: [V_held, hidden]
    h  = x + Attn(RMSNorm(x));  x' = h + FFN(RMSNorm(h))
    logits = RMSNorm(x_last) W_head                 [hidden, V_held], untied

``Attn``: q = W_q u -> H x (nope + rope); [c; k_r] = W_kva u (one k_r for
all heads); c <- RMSNorm(c); [k_n; v] = W_kvb c -> H x (nope + v); rotary
embedding on q's rope channels and on k_r; k = [k_n; k_r]; causal softmax of
q k / sqrt(nope + rope); W_o over H x v. ``FFN``: SwiGLU of
``intermediate_size`` in the first ``first_k_dense_replace`` layers; after
them s = sigmoid(W_g u) over all ``router_outputs`` experts in float32, the
``num_experts_per_tok`` largest of s + b, weights s_e / (sum of the chosen s
+ 1e-20) x ``routed_scaling_factor``, y = sum over the chosen AND HELD e of
w_e SwiGLU_e(u) + SwiGLU_shared(u).

Departures from the published description, each ``assumed`` in the
configuration's file:
- the rotary embedding turns interleaved channel pairs (2i, 2i + 1) in
  place; the release first re-orders them into two halves
  (``rope_interleave``), which permutes q's and k's rope channels alike and
  leaves every score as it is;
- ``n_group`` = ``topk_group`` = 1: the group-limited choice is the plain
  choice of the k largest;
- the selection bias b (``e_score_correction_bias``, which the release
  starts at nought and moves outside the gradient) is drawn small and not
  nought, and no step moves it: it enters the choice alone;
- the router's weight and b are drawn on bfloat16's grid, so that the
  program's apply boundary, which rounds every parameter to bfloat16 before
  the router's float32 product, starts from the same numbers;
- weights are normal(0, 0.02) from the seed; no multi-token-prediction head;
- the chip's share: the absent heads' part of ``o W_o`` and the absent
  experts' part of the routed sum are left out (a token whose chosen experts
  are all elsewhere gets the shared experts' part alone), ids and logits are
  over the held rows of the vocabulary.
Each layer is under ``jax.checkpoint``: without it the float32 backward of
4,096 tokens takes 12.6 GB of temporaries (CPU compile-time analysis, PR 29)
and does not fit beside three pool models and their starting points; the
numbers are those of the plain pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# The ``jax.named_scope`` names that the program's module of this family
# (``feddrift_tpu/models/mla_moe.py``) puts around its parts, in the order
# ``xplane.scope_of`` tries them: the trace's reduction books an op's device
# time to the first that occurs in its ``tf_op``
# (``tests/benchmark/test_device_scopes.py`` lowers the module and finds
# each name there).
DEVICE_SCOPES = ("lm_head", "expert_layer", "mla_attention")


# ----------------------------------------------------------------------
# parameters
def _attn_shapes(arch: dict):
    D, H = arch["hidden_size"], arch["num_attention_heads"]
    N, R, V = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
               arch["v_head_dim"])
    rank = arch["kv_lora_rank"]
    return [("wq", (D, H * (N + R)), "weight"),
            ("wkv_a", (D, rank + R), "weight"),
            ("kv_norm", (rank,), "scale"),
            ("wkv_b", (rank, H * (N + V)), "weight"),
            ("wo", (H * V, D), "weight")]


def _is_dense(arch: dict, layer: int) -> bool:
    return layer < arch["first_k_dense_replace"]


def param_spec(arch: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """(name, shape, role) of every parameter in forward order; role is
    weight | scale | router | router_bias. The router keeps the published
    number of experts."""
    D, F = arch["hidden_size"], arch["moe_intermediate_size"]
    G = arch["experts_held"][1] - arch["experts_held"][0]
    S = arch["n_shared_experts"] * F
    spec = [("embed", (arch["vocab_size"], D), "weight")]
    for l in range(arch["num_hidden_layers"]):
        spec.append((f"l{l}/attn_norm", (D,), "scale"))
        spec += [(f"l{l}/{n}", s, r) for n, s, r in _attn_shapes(arch)]
        spec.append((f"l{l}/ffn_norm", (D,), "scale"))
        if _is_dense(arch, l):
            I = arch["intermediate_size"]
            spec += [(f"l{l}/w_gate", (D, I), "weight"),
                     (f"l{l}/w_up", (D, I), "weight"),
                     (f"l{l}/w_down", (I, D), "weight")]
        else:
            E = arch["router_outputs"]
            spec += [(f"l{l}/router", (D, E), "router"),
                     (f"l{l}/router_bias", (E,), "router_bias"),
                     (f"l{l}/w_gate", (G, D, F), "weight"),
                     (f"l{l}/w_up", (G, D, F), "weight"),
                     (f"l{l}/w_down", (G, F, D), "weight"),
                     (f"l{l}/shared_gate", (D, S), "weight"),
                     (f"l{l}/shared_up", (D, S), "weight"),
                     (f"l{l}/shared_down", (S, D), "weight")]
    return spec + [("final_norm", (D,), "scale"),
                   ("head", (D, arch["vocab_size"]), "weight")]


def draw(role: str, key, shape: tuple[int, ...], num_models: int):
    """[num_models, *shape] float32: normal(0, 0.02) weights, unit scales;
    the router's weight the same and its selection bias normal(0, 0.01),
    both on bfloat16's grid (see the departures above)."""
    if role == "scale":
        return jnp.ones((num_models, *shape), jnp.float32)
    std = 0.01 if role == "router_bias" else 0.02
    w = std * jax.random.normal(key, (num_models, *shape), jnp.float32)
    if role in ("router", "router_bias"):
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    return w


def parameter_count(arch: dict) -> int:
    """The closed form, which the tests hold ``param_spec`` and the
    issue's count to."""
    D, F = arch["hidden_size"], arch["moe_intermediate_size"]
    H, rank = arch["num_attention_heads"], arch["kv_lora_rank"]
    N, R, V = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
               arch["v_head_dim"])
    attn = D * H * (N + R) + D * (rank + R) + rank + rank * H * (N + V) \
        + H * V * D
    G = arch["experts_held"][1] - arch["experts_held"][0]
    E = arch["router_outputs"]
    dense = attn + 2 * D + 3 * D * arch["intermediate_size"]
    expert = attn + 2 * D + D * E + E + 3 * D * F * arch["n_shared_experts"] \
        + G * 3 * D * F
    k = min(arch["first_k_dense_replace"], arch["num_hidden_layers"])
    return k * dense + (arch["num_hidden_layers"] - k) * expert \
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


def _rope(x, theta: float):
    """x [N, L, ..., R]: pair (2i, 2i + 1) turned by pos x theta^(-2i/R)."""
    L, R = x.shape[1], x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = ang.reshape((1, L) + (1,) * (x.ndim - 3) + (R // 2,))
    pairs = x.reshape(x.shape[:-1] + (R // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return turned.reshape(x.shape)


def _swiglu(u, w_gate, w_up, w_down, dtype):
    return _mm(jax.nn.silu(_mm(u, w_gate, dtype)) * _mm(u, w_up, dtype),
               w_down, dtype)


def _attention(arch, p, pre, u, dtype):
    B, L, _ = u.shape
    H = arch["num_attention_heads"]
    N, R, V = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
               arch["v_head_dim"])
    rank, theta = arch["kv_lora_rank"], float(arch["rope_theta"])
    q = _mm(u, p[f"{pre}/wq"], dtype).reshape(B, L, H, N + R)
    kv = _mm(u, p[f"{pre}/wkv_a"], dtype)
    c = _rms_norm(kv[..., :rank], p[f"{pre}/kv_norm"], arch["rms_norm_eps"])
    k_r = _rope(kv[..., rank:], theta)                         # [B, L, R]
    kn_v = _mm(c, p[f"{pre}/wkv_b"], dtype).reshape(B, L, H, N + V)
    q = jnp.concatenate([q[..., :N], _rope(q[..., N:], theta)], axis=-1)
    k = jnp.concatenate(
        [kn_v[..., :N], jnp.broadcast_to(k_r[:, :, None], (B, L, H, R))],
        axis=-1)
    v = kn_v[..., N:]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / math.sqrt(N + R)
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    return _mm(o.reshape(B, L, H * V), p[f"{pre}/wo"], dtype)


def _expert_layer(arch, p, pre, u, dtype):
    B, L, D = u.shape
    x = u.reshape(B * L, D)
    lo, hi = arch["experts_held"]
    # the router in float32 as published, whatever ``dtype`` lowers
    s = jax.nn.sigmoid(_mm(x, p[f"{pre}/router"]))
    _, top = jax.lax.top_k(
        s + p[f"{pre}/router_bias"].astype(jnp.float32),
        arch["num_experts_per_tok"])
    s_top = jnp.take_along_axis(s, top, axis=-1)
    w_top = s_top * arch["routed_scaling_factor"]
    if arch["norm_topk_prob"]:
        w_top = w_top / (s_top.sum(-1, keepdims=True) + 1e-20)
    # [T, G]: a token's weight on each held expert, nought where not chosen
    w = jnp.stack([(w_top * (top == lo + g)).sum(-1)
                   for g in range(hi - lo)], axis=1)

    y = 0.0
    for g in range(hi - lo):        # every token, times the mask
        y = y + w[:, g:g + 1] * _swiglu(
            x, p[f"{pre}/w_gate"][g], p[f"{pre}/w_up"][g],
            p[f"{pre}/w_down"][g], dtype)
    y = y + _swiglu(x, p[f"{pre}/shared_gate"], p[f"{pre}/shared_up"],
                    p[f"{pre}/shared_down"], dtype)
    return y.reshape(B, L, D)


def _layer(arch, l, dtype, p, x):
    pre, eps = f"l{l}", arch["rms_norm_eps"]
    h = x + _attention(arch, p, pre,
                       _rms_norm(x, p[f"{pre}/attn_norm"], eps), dtype)
    u = _rms_norm(h, p[f"{pre}/ffn_norm"], eps)
    if _is_dense(arch, l):
        return h + _swiglu(u, p[f"{pre}/w_gate"], p[f"{pre}/w_up"],
                           p[f"{pre}/w_down"], dtype)
    return h + _expert_layer(arch, p, pre, u, dtype)


def forward(arch: dict, p: dict, x, dtype=None):
    """Logits [N, L, held rows] of token ids [N, L]. ``dtype`` lowers the
    operands of the projections', the MLPs', the experts' and the head's
    products (the look at the configuration's compute precision); the
    router and the attention's own products stay in float32."""
    h = p["embed"].astype(jnp.float32)[x]
    for l in range(arch["num_hidden_layers"]):
        mine = {k: v for k, v in p.items() if k.startswith(f"l{l}/")}
        h = jax.checkpoint(
            lambda q, z, l=l: _layer(arch, l, dtype, q, z))(mine, h)
    return _mm(_rms_norm(h, p["final_norm"], arch["rms_norm_eps"]),
               p["head"], dtype)


def nll(logits, y):
    """One loss per label: [N, L] of logits [N, L, rows] and ids [N, L]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]


def hits(logits, y):
    """One 0/1 per token: whether the model's first choice is the next id."""
    return logits.argmax(-1) == y


# ----------------------------------------------------------------------
# operations
def forward_macs(arch: dict) -> int:
    """Multiply-accumulates of one sequence's forward pass (``seq_len``
    tokens): every projection, the attention products over the causal half,
    the dense MLP, the router, the shared experts, the head, and the routed
    experts in expectation under even routing: ``num_experts_per_tok`` x
    held / ``router_outputs`` assignments a token (0.375 at 6 x 8 / 128;
    the program's counter ``expert_assignments_held`` says what the router
    did)."""
    L, D = arch["seq_len"], arch["hidden_size"]
    H, rank = arch["num_attention_heads"], arch["kv_lora_rank"]
    N, R, V = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
               arch["v_head_dim"])
    F, E = arch["moe_intermediate_size"], arch["router_outputs"]
    G = arch["experts_held"][1] - arch["experts_held"][0]
    proj = D * H * (N + R) + D * (rank + R) + rank * H * (N + V) + H * V * D
    # query i sees keys 0..i: L (L + 1) / 2 pairs, each N + R for the score
    # and V for the value
    attn = H * (L * (L + 1) // 2) * (N + R + V)
    dense = 3 * D * arch["intermediate_size"]
    expert = D * E + 3 * D * F * arch["n_shared_experts"]
    routed = 3 * D * F * arch["num_experts_per_tok"] * G
    layers = arch["num_hidden_layers"]
    k = min(arch["first_k_dense_replace"], layers)
    per_token = layers * proj + k * dense + (layers - k) * expert \
        + D * arch["vocab_size"]
    # routed: an exact fraction (held / router outputs) of whole products
    return L * per_token + layers * attn + (layers - k) * L * routed // E


# ----------------------------------------------------------------------
# the program's side: its nesting of the parameters, one sample's shapes
def to_program_tree(arch: dict, flat: dict) -> dict:
    """The flat parameter dict in the nesting of the program's
    ``models/mla_moe.py::MLAMoEDecoder``."""
    tree = {"embed": flat["embed"], "final_norm": flat["final_norm"],
            "head": flat["head"]}
    for l in range(arch["num_hidden_layers"]):
        pre = f"l{l}"
        ffn = ("w_gate", "w_up", "w_down") if _is_dense(arch, l) else (
            "router", "router_bias", "w_gate", "w_up", "w_down",
            "shared_gate", "shared_up", "shared_down")
        tree[f"layer_{l}"] = {
            "attn_norm": flat[f"{pre}/attn_norm"],
            "ffn_norm": flat[f"{pre}/ffn_norm"],
            "attn": {n: flat[f"{pre}/{n}"] for n, _, _ in _attn_shapes(arch)},
            "mlp" if _is_dense(arch, l) else "moe":
                {n: flat[f"{pre}/{n}"] for n in ffn}}
    return tree


def sample_shapes(arch: dict) -> dict:
    """Shapes and types of one sample (a sequence and its shifted copy),
    for lowering a round program with shapes only (``sizing.py``)."""
    L = arch["seq_len"]
    return {"x": ((L,), "int32"), "y": ((L,), "int32"),
            "num_classes": arch["vocab_size"]}
