"""A decoder with GATED ATTENTION over window and global layers beside
sparse experts with a shared one (the block Arcee's Trinity family
publishes its keys for, `model_type` "afmoe").

Every layer has a KIND read from the published `layer_types`
(`window_layout[l]` 1: sliding, 0: full; the published period is three
sliding layers, then a full one), and the kind decides two things: a
sliding layer's token t attends s <= t with t - s < `window` only and its
q and k take the rotary embedding (rotate-half over the whole head); a
full layer attends every s <= t and reads NOTHING positional. The first
`n_dense_layers` layers' feed-forward is a dense SwiGLU, every later one
an expert layer. For layer l, no bias anywhere:

    x0 = E[token] * embed_scale                  sqrt(dim): `mup_enabled`
    h  = RMSNorm(x; ln1)
    q, k, v, g = h W_q, h W_k, h W_v, h W_g      ONE product, w_qkvg
    q, k = RMSNorm_Hd(q; q_norm), RMSNorm_Hd(k; k_norm)   a head at a time,
                                                 one weight for all heads,
                                                 BEFORE any rotation
    a  = Attn(q, k, v) * sigmoid(g)              THE GATE: elementwise on
                                                 [heads x head_dim], before W_o
    x  = x + RMSNorm(a W_o; ln1_post)            a norm on BOTH sides of
    h2 = RMSNorm(x; ln2)                         each branch
    f  = SwiGLU(h2)                              a dense layer, or
         shared(h2) + sum_{e in S} w_e expert_e(h2)
    x  = x + RMSNorm(f; ln2_post)

The expert branch is models/latent_moe.py's, IMPORTED (`moe_branch`:
sigmoid scores over all `n_routed_experts`, the `n_experts_per_tok`
largest of score + `router_bias`, which the SELECTION alone reads,
weights normalised over the selected and scaled, a shared expert, the
routed part for the `experts_held` experts from `expert_offset` on);
the layer plan and the prompt's attention are models/window_attn_moe.py's
(`layer_plan`, `attend_prompt`). serving/kv_cache.py builds a WindowPool
from `cfg.window_rows`, as for that model.

A PROMPT's token-wise parts (the fused product, the gate, the dense
layer's feed-forward, the experts) walk its rows PREFILL_MOE_ROWS at a
time (`row_chunks`), q, k and v written head-major where the attention
reads them and the gate's input made where it is used: a 20,480-row
prompt's [rows, 2 x 12,288] product is a gigabyte, and a second copy of
q, g and the heads' output three quarters of one, that the pools leave no
room for.

Parameters: `tok_emb`, `ln_f`, `lm_head`; `dense` (the leading layers,
stacked) and `layers` (the expert layers, stacked), each with `ln1
w_qkvg q_norm k_norm wo ln1_post ln2 w_gate w_up w_down ln2_post` (the
dense feed-forward, or the shared expert); an expert layer adds `router`
[L, D, n_routed_experts], `router_bias` [L, n_routed_experts] float32 and
the held experts' `we_gate_up` [L, E, D, 2 * Me], `we_down` [L, E, Me, D].
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models import latent_moe
from generativeaiexamples_tpu.models.llama import (
    add_branch, rms_norm, rope, swiglu)
from generativeaiexamples_tpu.models.sparse_attn_moe import (
    PREFILL_MOE_ROWS, PREFILL_TILE_ROWS)
# (`layer_plan` is taken from here by serving/served_gated_window.py)
from generativeaiexamples_tpu.models.window_attn_moe import (
    GLOBAL, WINDOW, WindowRows, attend_prompt, layer_plan)
from generativeaiexamples_tpu.ops import moe
from generativeaiexamples_tpu.ops.quant import QuantizedTensor, mm

Params = Dict[str, Any]

take_layer = latent_moe.take_layer
split_experts = latent_moe.split_experts

# The seeded initialiser's choices (a checkpoint's own values replace all
# of them; the mathematics is the same whatever they are). Under the
# sqrt(dim) multiplier an embedding drawn at dim ** -0.5 enters the stream
# at an rms of one; every branch ends in a norm, so what a branch adds is
# its post-norm's gain whatever its projections' scale, and the gains are
# drawn at (2 * depth) ** -0.5 (the family's "depth-scaled" norm, the
# GPT-2 convention linear_attn_moe.residual_init_gain follows), `depth`
# the PUBLISHED model's where a configuration is a cut of it
# (`init_depth`): a layer adds what it would add there, 0.091 of the
# embedding at 60 layers. READ ON THE CHIP (PERF.md section 6, PR 52): at
# the cut's own depth (0.236 at nine layers) a slot's attention branches,
# which change little from step to step, outweigh its current token in the
# router's input, a slot asks for the SAME experts step after step, and
# which of the 32 held experts a step hits is ONE draw that the seed makes
# for a whole run: 32.6 to 35.8 % of them by seed, 2.5 % of the step. At
# 0.091 the current token decides, the draw is made anew every step and
# the share reads 38.4-39.3 % (uniform routing: 39.6). The routed experts'
# down-projections at a quarter gain (latent_moe.ROUTED_INIT_GAIN: a
# near-tie among the router's scores that falls the other way than in the
# float32 reference steps the branch by one whole expert BEFORE its norm).
# The selection's bias at 0.005: a score near the fourth largest of 256
# moves by 0.09 a unit of its logit, so 0.005 is a twentieth of the
# logits' spread: it changes the choice in a few tokens of a hundred and
# an expert's load by a tenth (linear_attn_moe's 0.02 makes an expert's
# load vary by half, and the share of held experts hit fall to 36 %); a
# trained bias BALANCES the load.
ROUTER_BIAS_STD = 0.005


def post_norm_init_gain(cfg) -> float:
    return (2 * (cfg.init_depth or cfg.n_layers)) ** -0.5


@dataclass(frozen=True)
class GatedWindowMoeConfig:
    vocab_size: int = 200192
    dim: int = 3072
    n_layers: int = 60           # dense + expert layers
    n_dense_layers: int = 6      # HF num_dense_layers
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 4096
    # a layer's kind: 1 = sliding (window, rotated). None: [1, 1, 1, 0]
    # repeated, the published layer_types.
    window_layout: Optional[Tuple[int, ...]] = None
    mlp_dim: int = 12288         # the dense layers' feed-forward
    moe_mlp_dim: int = 3072      # every expert's, the shared one's too
    n_routed_experts: int = 256  # the router's width
    n_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.448  # HF route_scale
    norm_topk_prob: bool = True           # HF route_norm
    # expert parallelism's share: the experts whose weights live here
    experts_held: int = 256
    expert_offset: int = 0
    embed_scale: float = 3072 ** 0.5      # HF mup_enabled: sqrt(dim)
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    max_seq_len: int = 262144
    # the depth the seeded gains of the branch-ending norms are scaled by
    # (post_norm_init_gain); None: n_layers. A cut of a deeper model names
    # the published depth, so that a layer adds what it would add there.
    init_depth: Optional[int] = None
    dtype: Any = jnp.bfloat16

    # what serving/ reads of any model configuration
    n_passes = 1
    post_norms = True  # llama.add_branch norms a branch's output

    def __post_init__(self):
        layout = self.window_layout
        if layout is None:
            layout = tuple((WINDOW, WINDOW, WINDOW, GLOBAL)[l % 4]
                           for l in range(self.n_layers))
        object.__setattr__(self, "window_layout",
                           tuple(int(k) for k in layout))
        if len(layout) != self.n_layers or set(layout) - {GLOBAL, WINDOW}:
            raise ValueError(f"window_layout: one 0 or 1 a layer, "
                             f"{self.n_layers} of them; got {layout}")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2 \
                or self.window < 1:
            raise ValueError("query heads in whole groups a KV head, rotary "
                             "pairs and a window of at least the token "
                             "itself are what is written")
        if not 0 < self.experts_held <= self.n_routed_experts \
                - self.expert_offset:
            raise ValueError(
                f"experts_held {self.experts_held} from expert_offset "
                f"{self.expert_offset} on: the router has "
                f"{self.n_routed_experts} experts")
        if self.n_shared_experts != 1 \
                or not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError("one shared expert and at least one expert "
                             "layer are what is written")

    @property
    def rope_layout(self) -> Tuple[int, ...]:
        """A sliding layer is rotated, a full layer reads no position."""
        return self.window_layout

    @property
    def cache_rows(self) -> int:
        return self.n_layers

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def residual_dtype(self):
        return self.dtype

    @property
    def window_rows(self) -> WindowRows:
        """serving/kv_cache.py builds the WindowPool from this."""
        n_window = sum(self.window_layout)
        return WindowRows(self.window, self.n_layers - n_window, n_window)

    @staticmethod
    def tiny(vocab_size: int = 256, **kw) -> "GatedWindowMoeConfig":
        """Hermetic-test geometry: every mechanism, nothing wide (one
        dense sliding layer, then a period of expert layers; a quarter
        of the experts held, from the second quarter on)."""
        base = dict(
            vocab_size=vocab_size, dim=64, n_layers=5, n_dense_layers=1,
            n_heads=4, n_kv_heads=2, head_dim=16, window=8,
            window_layout=(1, 1, 1, 1, 0), mlp_dim=128, moe_mlp_dim=32,
            n_routed_experts=16, n_experts_per_tok=4, experts_held=4,
            expert_offset=4, embed_scale=8.0, max_seq_len=128,
            dtype=jnp.float32)
        base.update(kw)
        return GatedWindowMoeConfig(**base)


def _block_shapes(cfg: GatedWindowMoeConfig, L: int, mlp: int):
    """(int8-able weights, norms before a branch and on q and k, norms
    that end a branch) of `L` stacked layers whose dense feed-forward (or
    shared expert) is `mlp` wide."""
    D, H, KH, Hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    weights = {"w_qkvg": (L, D, 2 * (H + KH) * Hd), "wo": (L, H * Hd, D),
               "w_gate": (L, D, mlp), "w_up": (L, D, mlp),
               "w_down": (L, mlp, D)}
    norms = {"ln1": (L, D), "ln2": (L, D), "q_norm": (L, Hd),
             "k_norm": (L, Hd)}
    post = {"ln1_post": (L, D), "ln2_post": (L, D)}
    return weights, norms, post


def init_params_on_device(cfg: GatedWindowMoeConfig, seed: int = 0, *,
                          quantize: bool = False) -> Params:
    """Seeded random parameters drawn leaf by leaf on the device, each in
    the type it is served in (latent_moe.init_params_on_device's recipe:
    uniform int8 codes, the per-column scale giving fan_in ** -0.5; the
    norms before a branch and on q and k of one, the norms after a branch
    of post_norm_init_gain; embedding and router in cfg.dtype, the
    selection's bias float32)."""
    root = jax.random.key(seed)
    leaf_ids = itertools.count(1)

    def draw(fn):
        return jax.jit(fn)(jax.random.fold_in(root, next(leaf_ids)))

    def normal(*shape, scale, dtype=cfg.dtype):
        return draw(lambda k: jax.random.normal(k, shape, dtype)
                    * jnp.asarray(scale, dtype))

    def weight(*shape, gain=1.0):
        scale = gain * shape[-2] ** -0.5
        if not quantize:
            return normal(*shape, scale=scale)

        def codes(k, shape=shape[1:]):
            return jnp.maximum(jax.lax.bitcast_convert_type(
                jax.random.bits(k, shape, jnp.uint8), jnp.int8), -127)

        # a layer at a time: temporaries of ONE layer's slice
        q = draw(lambda k: jax.lax.map(codes, jax.random.split(k, shape[0])))
        s = jnp.full(shape[:-2] + shape[-1:], scale * 3 ** 0.5 / 127.0,
                     jnp.float32)
        return QuantizedTensor(q, s)

    def block(L, mlp):
        weights, norms, post = _block_shapes(cfg, L, mlp)
        out = {k: weight(*shape) for k, shape in weights.items()}
        out.update({k: jnp.ones(shape, cfg.dtype)
                    for k, shape in norms.items()})
        out.update({k: jnp.full(shape, post_norm_init_gain(cfg), cfg.dtype)
                    for k, shape in post.items()})
        return out

    D, Lm = cfg.dim, cfg.n_moe_layers
    E, Me = cfg.experts_held, cfg.moe_mlp_dim
    layers = block(Lm, Me)
    layers.update(
        router=normal(Lm, D, cfg.n_routed_experts, scale=D ** -0.5),
        router_bias=normal(Lm, cfg.n_routed_experts, scale=ROUTER_BIAS_STD,
                           dtype=jnp.float32),
        we_gate_up=weight(Lm, E, D, 2 * Me),
        we_down=weight(Lm, E, Me, D, gain=latent_moe.ROUTED_INIT_GAIN))
    head = weight(1, D, cfg.vocab_size)
    head = QuantizedTensor(head.q[0], head.s[0]) if quantize else head[0]
    return {"tok_emb": normal(cfg.vocab_size, D, scale=D ** -0.5),
            "ln_f": jnp.ones((D,), cfg.dtype), "lm_head": head,
            "dense": block(cfg.n_dense_layers, cfg.mlp_dim),
            "layers": layers}


def embed(cfg: GatedWindowMoeConfig, params: Params, tokens):
    x = params["tok_emb"][tokens].astype(jnp.float32) * cfg.embed_scale
    return x.astype(cfg.residual_dtype)


def row_chunks(B: int, S: int) -> int:
    """Chunks along S a prompt batch [B, S] is walked in: the fewest that
    divide S into pieces of PREFILL_MOE_ROWS rows or fewer over the B
    prompts."""
    return next(n for n in range(1, S + 1)
                if S % n == 0 and B * (S // n) <= PREFILL_MOE_ROWS)


def _columns(w, a: int, b: Optional[int]):
    """Columns a..b of a weight (an int8 one stays codes and scales)."""
    if isinstance(w, QuantizedTensor):
        return QuantizedTensor(w.q[..., a:b], w.s[..., a:b])
    return w[..., a:b]


# -- attention -------------------------------------------------------------

def gate_columns(cfg: GatedWindowMoeConfig) -> int:
    """Where the gate's columns start in `w_qkvg`: behind q, k and v."""
    return (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim


def project(cfg: GatedWindowMoeConfig, x, w, positions, rotate):
    """The block up to its attention, from the stream x [B, S, D]: q
    [B, S, H, Hd], k, v [B, S, KH, Hd] and the gate's input g [B, S, H *
    Hd] out of ONE product with `w["w_qkvg"]` (a prompt hands over its q,
    k and v columns alone and takes g from `gate_input` where it is used:
    g comes back empty); q and k normed a head at a time, THEN rotated at
    `positions` [B, S] where `rotate` (a Python bool or a traced scalar:
    the layer's kind) and left as normed where not."""
    B, S, _ = x.shape
    H, KH, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    a, b, c = H * Hd, (H + KH) * Hd, gate_columns(cfg)
    h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
    with jax.named_scope("attn.qkv"):
        y = mm(h, w["w_qkvg"])
    with jax.named_scope("attn.qk_norm"):
        q = rms_norm(y[..., :a].reshape(B, S, H, Hd), w["q_norm"],
                     cfg.rms_eps)
        k = rms_norm(y[..., a:b].reshape(B, S, KH, Hd), w["k_norm"],
                     cfg.rms_eps)
    v = y[..., b:c].reshape(B, S, KH, Hd)

    def turned(t):  # llama.rope takes [B, heads, S, Hd]
        return rope(t.transpose(0, 2, 1, 3), positions,
                    cfg.rope_theta).transpose(0, 2, 1, 3)

    if isinstance(rotate, (bool, int)):
        if rotate:
            q, k = turned(q), turned(k)
    else:
        q, k = jnp.where(rotate, turned(q), q), jnp.where(rotate, turned(k),
                                                          k)
    return q, k, v, y[..., c:]


def gate_input(cfg: GatedWindowMoeConfig, x, w, w_g):
    """g = RMSNorm(x; ln1) W_g alone [B, S, H * Hd], `w_g` the fused
    product's last columns: for a prompt's rows where the gate is
    applied."""
    h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
    with jax.named_scope("attn.qkv"):
        return mm(h, w_g)


def gate(cfg: GatedWindowMoeConfig, out, g):
    """THE GATE: the heads' output `out` [..., H * Hd] times sigmoid(g),
    elementwise, in float32."""
    with jax.named_scope("attn.gate"):
        return (out.astype(jnp.float32)
                * jax.nn.sigmoid(g.astype(jnp.float32))).astype(cfg.dtype)


def gated_out(cfg: GatedWindowMoeConfig, x, out, g, w):
    """The attention branch's end: the gated heads through the output
    projection and its norm, added to x."""
    a = gate(cfg, out, g)
    with jax.named_scope("attn.out"):
        y = mm(a, w["wo"])
        return add_branch(cfg, x, y.astype(x.dtype), w, "ln1_post",
                          "attn.post_norm")


# -- the feed-forward ------------------------------------------------------

def feed_forward(cfg: GatedWindowMoeConfig, x, w, experts=None, layer=None,
                 use_pallas=None, mask=None):
    """The block from its attention's residual add on: norm, the dense
    SwiGLU (experts None) or latent_moe's expert layer, the branch's own
    norm, added to x [B, S, D]. Returns (x, pair counts [E] or None, the
    router's choice [B, S, k] or None)."""
    B, S, D = x.shape
    h = rms_norm(x, w["ln2"], cfg.rms_eps).astype(cfg.dtype)
    counts = idx = None
    if experts is None:
        y = swiglu(h, w)
    else:
        pairs = B * S * cfg.n_experts_per_tok
        y, counts, idx = latent_moe.moe_branch(
            cfg, h.reshape(B * S, D), w, experts, layer, use_pallas, mask,
            tile_rows=min(moe.tile_rows(pairs), PREFILL_TILE_ROWS))
        y, idx = y.reshape(B, S, D), idx.reshape(B, S, -1)
    return add_branch(cfg, x, y.astype(x.dtype), w, "ln2_post",
                      "mlp.post_norm"), counts, idx


def logits_of(cfg: GatedWindowMoeConfig, params: Params, x):
    x = rms_norm(x, params["ln_f"], cfg.rms_eps)
    with jax.named_scope("lm_head"):
        return mm(x, params["lm_head"]).astype(jnp.float32)


# -- the walk --------------------------------------------------------------

def walk_prompt(params: Params, cfg: GatedWindowMoeConfig, tokens,
                lengths=None, use_pallas=None, encode=None):
    """Token ids [B, S] through every block in its prompt form, one
    causal pass with no cache: the leading dense layers unrolled, the
    expert layers one scanned body (a layer's kind rides the scan and
    picks its attention and its rotation; the held experts are read where
    they lie). Returns (the stream [B, S, D], what each layer caches in
    layer order: `encode(k, v)` of k, v [B, KH, S, Hd] stacked over the
    layers, the pair as it is where `encode` is None; the router's
    choices [n_moe_layers, B, S, k])."""
    B, S = tokens.shape
    H, KH, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    n = row_chunks(B, S)
    r = S // n
    keep = (lambda k, v: (k, v)) if encode is None else encode

    def rows(t, i, axis=1):  # chunk i of t along its S axis
        return jax.lax.dynamic_slice_in_dim(t, i * r, r, axis)

    def block(x, w, kind, rotate, experts=None, layer=None):
        c = gate_columns(cfg)  # the fused weight cut ONCE a layer
        w_qkv = dict(w, w_qkvg=_columns(w["w_qkvg"], 0, c))
        w_g = _columns(w["w_qkvg"], c, None)

        def heads(bufs, i):  # q, k, v of chunk i into [B, heads, S, Hd]
            pos = jnp.broadcast_to(i * r + jnp.arange(r)[None, :], (B, r))
            new = project(cfg, rows(x, i), w_qkv, pos, rotate)[:3]
            return tuple(jax.lax.dynamic_update_slice_in_dim(
                buf, t.transpose(0, 2, 1, 3), i * r, 2)
                for buf, t in zip(bufs, new)), None

        (q, k, v), _ = jax.lax.scan(heads, tuple(
            jnp.zeros((B, h, S, Hd), cfg.dtype) for h in (H, KH, KH)),
            jnp.arange(n))
        out = attend_prompt(cfg, q, k, v, lengths, kind, use_pallas)

        def rest(x_new, i):  # the gate, W_o and the feed-forward of chunk i
            xc = rows(x, i)
            oc = rows(out, i, 2).transpose(0, 2, 1, 3).reshape(B, r, -1)
            xc = gated_out(cfg, xc, oc, gate_input(cfg, xc, w, w_g), w)
            xc, _, idx = feed_forward(cfg, xc, w, experts, layer, use_pallas)
            return jax.lax.dynamic_update_slice_in_dim(
                x_new, xc, i * r, 1), idx

        x, idx = jax.lax.scan(rest, jnp.zeros_like(x), jnp.arange(n))
        if idx is not None:  # [n, B, r, k] -> [B, S, k]
            idx = idx.transpose(1, 0, 2, 3).reshape(B, S, -1)
        return (x, idx), keep(k, v)

    x = embed(cfg, params, tokens)
    kinds = jnp.asarray(cfg.window_layout, bool)
    cached = []
    for l in range(cfg.n_dense_layers):
        (x, _), kv = block(x, take_layer(params["dense"], l), kinds[l],
                           bool(cfg.rope_layout[l]))
        cached.append(jax.tree.map(lambda t: t[None], kv))
    sliced, experts = split_experts(params["layers"])

    def body(x, lw):
        l, w, kind = lw
        (x, idx), kv = block(x, w, kind, kind, experts, l)
        return x, (kv, idx)

    x, (kv, choices) = jax.lax.scan(
        body, x, (jnp.arange(cfg.n_moe_layers), sliced,
                  kinds[cfg.n_dense_layers:]))
    kv = jax.tree.map(lambda *t: jnp.concatenate(t, axis=0), *cached, kv)
    return x, kv, choices


def forward(params: Params, cfg: GatedWindowMoeConfig, tokens, *,
            lengths=None, use_pallas=None):
    """Token ids [B, S] -> (logits [B, S, V] float32, the router's
    choices): the whole model with no cache (tests, offline use)."""
    x, _, choices = walk_prompt(params, cfg, tokens, lengths, use_pallas)
    return logits_of(cfg, params, x), choices
