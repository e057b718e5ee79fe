"""A decoder with latent attention (MLA) and sparse experts with a shared
one: the DeepSeek-V3 family's block, as A.X-K1 publishes its keys.

What differs from models/llama.py's block, and nothing else:

- ATTENTION caches one vector per token, shared by all heads:
  `[c_kv ; k_rope]` (cfg.latent_row = (kv_lora_rank, qk_rope_head_dim)),
  where the Llama block caches K and V per head. Queries go through a
  low-rank bottleneck with a norm of its own (`q_lora_rank` None: one
  `w_q`, no bottleneck); only the `qk_rope_head_dim` last dimensions of a
  query or key head are rotated, with YaRN frequencies
  (llama.YarnScaling), or none is (`rotary` False: a model that takes
  its positions from other layers). A prefill builds keys and values from
  the latent (`attend_prompt`); a decode step never does: it absorbs the
  key up-projection into the query and the value up-projection into the
  output (`attend_cached`), which is the same function.
- FEED-FORWARD: the first `n_dense_layers` blocks are llama's SwiGLU;
  every later one routes each token over `n_routed_experts` experts
  (sigmoid scores, the `n_experts_per_tok` largest, by score plus a
  block's `router_bias` where it has one, weights normalised over the
  selected and scaled), adds the shared expert, and computes the
  routed part for the `experts_held` experts from `expert_offset` on that
  live HERE: expert parallelism's share of the layer. What the experts
  elsewhere would add is left out; on one chip the layer runs with no
  exchange (`moe_branch`).
- THE RESIDUAL is not written here: a branch (the attention up to
  `heads_out`, `feed_forward`) takes its input and returns its OUTPUT,
  and models/hyper_connections.py says how the stream goes in and comes
  out (`residual.open` before a branch, `residual.close` after it). With
  `hc_mult` 1 that is `x` and `x + y`; with `hc_mult` n > 1 the stream
  is n wide and a block holds the mixing's leaves (`hc_attn_*`,
  `hc_ffn_*`: that module's docstring).

Parameters: `tok_emb`, `ln_f`, `lm_head`; `dense` (the leading blocks,
stacked) and `layers` (the expert blocks, stacked), each with the
attention's leaves (`w_qa q_norm w_qb`, or `w_q`; `w_kva kv_norm w_kvb wo`), `ln1`,
`ln2` and llama's `w_gate w_up w_down` (the dense feed-forward, or the
shared expert); an expert block adds `router` [L, D, n_routed_experts]
and the held experts' `we_gate_up` [L, E, D, 2 * Me] (gate ; up) and
`we_down` [L, E, Me, D], and `router_bias` [L, n_routed_experts] float32
where cfg.router_bias says the selection reads one.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models import hyper_connections as residual
from generativeaiexamples_tpu.models.llama import (
    YarnScaling, rms_norm, rope, swiglu)
from generativeaiexamples_tpu.ops import attention as attn_ops
from generativeaiexamples_tpu.ops import moe
from generativeaiexamples_tpu.ops.quant import QuantizedTensor, mm

Params = Dict[str, Any]

EXPERT_WEIGHTS = ("we_gate_up", "we_down")


@dataclass(frozen=True)
class LatentMoeConfig:
    vocab_size: int = 163840
    dim: int = 7168
    n_layers: int = 61           # dense + expert blocks
    n_dense_layers: int = 1      # HF first_k_dense_replace
    n_heads: int = 64
    q_lora_rank: Optional[int] = 1536  # None: queries projected directly
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 18432         # the dense blocks' feed-forward
    moe_mlp_dim: int = 2048      # every expert's, the shared one's too
    n_routed_experts: int = 192  # the router's width
    n_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # expert parallelism's share: the experts whose weights live here
    experts_held: int = 192
    expert_offset: int = 0
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnScaling] = None
    rotary: bool = True  # False: the row's and a query's last R as they are
    rms_eps: float = 1e-6
    max_seq_len: int = 131072
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # the block's correction bias, which the selection alone reads
    # (`topk_method` "noaux_tc"): a float32 `router_bias` leaf a block
    router_bias: bool = False
    # the residual path (models/hyper_connections.py): 1 is x + y
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)

    # what serving/ reads of any model configuration
    n_passes = 1
    post_norms = False

    def __post_init__(self):
        if not 0 < self.experts_held <= self.n_routed_experts \
                - self.expert_offset:
            raise ValueError(
                f"experts_held {self.experts_held} from expert_offset "
                f"{self.expert_offset} on: the router has "
                f"{self.n_routed_experts} experts")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is what is written")
        if self.hc_mult < 1:
            raise ValueError(f"hc_mult {self.hc_mult}: a stream or several")

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
    def latent_row(self) -> Tuple[int, int]:
        """A cache row is [c_kv ; k_rope], one for all heads
        (serving/kv_cache.py builds a LatentPagePool from this)."""
        return self.kv_lora_rank, self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = self.rope_scaling.softmax_mscale if self.rope_scaling else 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @staticmethod
    def tiny(vocab_size: int = 256, **kw) -> "LatentMoeConfig":
        """Hermetic-test geometry: every mechanism, nothing wide."""
        base = dict(
            vocab_size=vocab_size, dim=64, n_layers=3, n_dense_layers=1,
            n_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, mlp_dim=128, moe_mlp_dim=32,
            n_routed_experts=16, n_experts_per_tok=4, experts_held=4,
            expert_offset=4, max_seq_len=128, dtype=jnp.float32,
            rope_scaling=YarnScaling(factor=4.0,
                                     original_max_position_embeddings=32))
        base.update(kw)
        return LatentMoeConfig(**base)


# What the seeded initialiser scales a routed expert's down-projection
# by, beside fan_in ** -0.5. Picking the 8 largest of 192 scores is
# discontinuous: with random weights the 8th and 9th scores of a token
# lie within a bf16 rounding of each other in about one (token, layer) in
# five, two bf16 programs (or a bf16 program and the float32 reference)
# then pick different experts, and the output steps by one whole expert.
# At a gain of one such a step is 8 % of the stream and begets further
# steps (logits 15-25 % off the reference on the chip, PERF.md, PR 33);
# at a quarter it is the size of bf16's own noise. A checkpoint's own
# weights replace these; the mathematics is the same whatever the gain.
ROUTED_INIT_GAIN = 0.25
# The seeded correction bias (cfg.router_bias), as linear_attn_moe draws
# it: large enough to change the selection in about one token in ten,
# the weights never.
ROUTER_BIAS_STD = 0.02


def attention_shapes(cfg, L: int):
    """(int8-able weights, norms of one) of `L` stacked attention mixers."""
    D, H = cfg.dim, cfg.n_heads
    C, R = cfg.latent_row
    Dn, Dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    weights = {"w_qa": (L, D, cfg.q_lora_rank),
               "w_qb": (L, cfg.q_lora_rank, H * (Dn + R)),
               "w_kva": (L, D, C + R), "w_kvb": (L, C, H * (Dn + Dv)),
               "wo": (L, H * Dv, D)}
    norms = {"ln1": (L, D), "q_norm": (L, cfg.q_lora_rank),
             "kv_norm": (L, C)}
    if cfg.q_lora_rank is None:
        del weights["w_qa"], weights["w_qb"], norms["q_norm"]
        weights["w_q"] = (L, D, H * (Dn + R))
    return weights, norms


def _block_shapes(cfg: LatentMoeConfig, L: int, mlp: int):
    D = cfg.dim
    weights, norms = attention_shapes(cfg, L)
    weights.update({"w_gate": (L, D, mlp), "w_up": (L, D, mlp),
                    "w_down": (L, mlp, D)})
    norms["ln2"] = (L, D)
    return weights, norms


def init_params_on_device(cfg: LatentMoeConfig, seed: int = 0, *,
                          quantize: bool = False,
                          depth_gain: bool = False) -> Params:
    """Seeded random parameters drawn leaf by leaf on the device, each
    in the type it is served in (llama.init_params_on_device's recipe:
    uniform int8 codes with the per-column scale that gives the leaf its
    standard deviation, fan_in ** -0.5; norms of one; the router and
    the embedding in cfg.dtype). `depth_gain`: the convention
    linear_attn_moe.py draws by, for a deep stack: the embedding at a
    standard deviation of 1 and every branch's last projection at
    (2 * n_layers) ** -0.5 of its fan-in scale, so that no block's input
    is another block's output alone and a rounding does not double a
    layer (40 layers: PERF.md, PR 57)."""
    root = jax.random.key(seed)
    leaf_ids = itertools.count(1)

    def draw(fn):
        return jax.jit(fn)(jax.random.fold_in(root, next(leaf_ids)))

    def normal(*shape, scale):
        return draw(lambda k: jax.random.normal(k, shape, cfg.dtype)
                    * jnp.asarray(scale, cfg.dtype))

    def weight(*shape, gain=1.0):
        scale = shape[-2] ** -0.5 * gain
        if not quantize:
            return normal(*shape, scale=scale)
        def codes(k, shape=shape[1:]):
            return jnp.maximum(jax.lax.bitcast_convert_type(
                jax.random.bits(k, shape, jnp.uint8), jnp.int8), -127)

        # a layer at a time: the generator's temporaries are a few
        # times ONE layer's slice, not the 4.9 GB stack of experts'
        q = draw(lambda k: jax.lax.map(codes, jax.random.split(k, shape[0])))
        s = jnp.full(shape[:-2] + shape[-1:], scale * 3 ** 0.5 / 127.0,
                     jnp.float32)
        return QuantizedTensor(q, s)

    out_gain = (2 * cfg.n_layers) ** -0.5 if depth_gain else 1.0
    gains = {"wo": out_gain, "w_down": out_gain}

    def block(L, mlp):
        weights, norms = _block_shapes(cfg, L, mlp)
        out = {k: weight(*shape, gain=gains.get(k, 1.0))
               for k, shape in weights.items()}
        out.update({k: jnp.ones(shape, cfg.dtype)
                    for k, shape in norms.items()})
        return out

    D, V, Lm = cfg.dim, cfg.vocab_size, cfg.n_moe_layers
    E, Me = cfg.experts_held, cfg.moe_mlp_dim
    layers = block(Lm, Me)
    layers["router"] = normal(Lm, D, cfg.n_routed_experts, scale=D ** -0.5)
    layers["we_gate_up"] = weight(Lm, E, D, 2 * Me)
    layers["we_down"] = weight(Lm, E, Me, D,
                               gain=ROUTED_INIT_GAIN * out_gain)
    params: Params = {
        "tok_emb": normal(V, D, scale=1.0 if depth_gain else 0.02),
        "ln_f": jnp.ones((D,), cfg.dtype),
        "dense": block(cfg.n_dense_layers, cfg.mlp_dim),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = weight(D, V)
    # leaves of later PRs are drawn LAST: a seed's older leaves stay put
    if cfg.router_bias:
        layers["router_bias"] = draw(lambda k: jax.random.normal(
            k, (Lm, cfg.n_routed_experts), jnp.float32) * ROUTER_BIAS_STD)
    if cfg.hc_mult > 1:
        for stack, L in (("dense", cfg.n_dense_layers), ("layers", Lm)):
            params[stack].update(residual.init_leaves(cfg, L, normal))
    return params


def take_layer(tree: Params, l, skip=()) -> Params:
    """Block `l`'s slice of a stacked tree (an int8 weight stays codes
    and scales); the leaves named in `skip` stay whole."""
    def at(t):
        if isinstance(t, QuantizedTensor):
            return QuantizedTensor(t.q[l], t.s[l])
        return t[l]
    return {k: (v if k in skip else at(v)) for k, v in tree.items()}


# -- attention -------------------------------------------------------------

def project_latent(cfg: LatentMoeConfig, h, w, positions):
    """The block up to its attention, from the normed stream h [B, S, D]:
    q_nope [B, S, H, Dn], q_rope [B, S, H, R] (rotated) and the row the
    cache keeps, [c_kv ; k_rope] [B, S, C + R] (normed ; rotated)."""
    B, S, _ = h.shape
    H, Dn = cfg.n_heads, cfg.qk_nope_head_dim
    C, R = cfg.latent_row
    with jax.named_scope("attn.q_latent"):
        if cfg.q_lora_rank is None:
            q = mm(h, w["w_q"]).reshape(B, S, H, Dn + R)
        else:
            cq = rms_norm(mm(h, w["w_qa"]), w["q_norm"], cfg.rms_eps)
            q = mm(cq.astype(cfg.dtype), w["w_qb"]).reshape(B, S, H, Dn + R)
        q_rope = q[..., Dn:]
        if cfg.rotary:
            q_rope = rope(q_rope.transpose(0, 2, 1, 3), positions,
                          cfg.rope_theta,
                          cfg.rope_scaling).transpose(0, 2, 1, 3)
    with jax.named_scope("attn.kv_latent"):
        ckv = mm(h, w["w_kva"])
        c = rms_norm(ckv[..., :C], w["kv_norm"], cfg.rms_eps)
        if cfg.rotary:
            k_rope = rope(ckv[..., None, :, C:], positions, cfg.rope_theta,
                          cfg.rope_scaling)[:, 0]  # ONE head, shared by all
        else:
            k_rope = ckv[..., C:]
        row = jnp.concatenate([c, k_rope], axis=-1).astype(cfg.dtype)
    return q[..., :Dn], q_rope, row


def _up_projection(cfg: LatentMoeConfig, w_kvb):
    """w_kvb [C, H * (Dn + Dv)] as (codes or weights [C, H, Dn + Dv],
    per-column scales [H, Dn + Dv] or None)."""
    H = cfg.n_heads
    if isinstance(w_kvb, QuantizedTensor):
        return (w_kvb.q.reshape(w_kvb.q.shape[0], H, -1),
                w_kvb.s.reshape(H, -1).astype(cfg.dtype))
    return w_kvb.reshape(w_kvb.shape[0], H, -1), None


def attend_cached(cfg: LatentMoeConfig, q_nope, q_rope, w, attend):
    """Decode attention, absorbed: q_nope, q_rope [B, H, *] of one new
    token a slot; `attend(q [B, H, C + R]) -> [B, H, C]` is the paged
    kernel over the latent pool (the caller's). Returns [B, H, Dv]."""
    Dn = cfg.qk_nope_head_dim
    wq, ws = _up_projection(cfg, w["w_kvb"])
    with jax.named_scope("attn.absorb_k"):
        if ws is not None:  # the scale of a key column, on the query side
            q_nope = q_nope * ws[None, :, :Dn]
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope,
                           wq[..., :Dn].astype(cfg.dtype))
    o_lat = attend(jnp.concatenate([q_lat, q_rope], axis=-1))
    with jax.named_scope("attn.absorb_v"):
        out = jnp.einsum("bhc,chv->bhv", o_lat.astype(cfg.dtype),
                         wq[..., Dn:].astype(cfg.dtype))
        if ws is not None:
            out = out * ws[None, :, Dn:]
    return out


def attend_prompt(cfg: LatentMoeConfig, q_nope, q_rope, row, w, lengths,
                  use_pallas=None):
    """Prefill attention, un-absorbed: keys [*, Dn + R] and values
    [*, Dv] of every head built from the prompt's own latent rows
    `row` [B, S, C + R]; causal flash attention. Returns [B, H, S, Dv]."""
    B, S, H, Dn = q_nope.shape
    C, R = cfg.latent_row
    with jax.named_scope("attn.kv_up"):
        kv = mm(row[..., :C], w["w_kvb"]).reshape(B, S, H, -1)
        k_rope = jnp.broadcast_to(row[:, :, None, C:], (B, S, H, R))
        k = jnp.concatenate([kv[..., :Dn], k_rope], -1).transpose(0, 2, 1, 3)
        v = kv[..., Dn:].transpose(0, 2, 1, 3)
        q = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)
    on_chip = attn_ops.on_tpu() if use_pallas is None else use_pallas
    spare = -(Dn + R) % 128
    if on_chip and spare:  # the flash kernel's q and k blocks: whole lanes
        pad = [(0, 0)] * 3 + [(0, spare)]
        q, k = jnp.pad(q, pad), jnp.pad(k, pad)
    return attn_ops.attention(q, k, v, causal=True, lengths=lengths,
                              scale=cfg.softmax_scale, use_pallas=use_pallas)


def heads_out(out, w):
    """The attention branch's end: heads `out` [B, H, S, Dv] through the
    output projection. -> the branch's output [B, S, D]."""
    B, _, S, _ = out.shape
    with jax.named_scope("attn.out"):
        return mm(out.transpose(0, 2, 1, 3).reshape(B, S, -1), w["wo"])


# -- the expert layer ------------------------------------------------------

def route(cfg: LatentMoeConfig, h, router, bias=None):
    """Scores over ALL experts for tokens h [T, D]: sigmoid of a product
    accumulated in float32, the n_experts_per_tok largest, their weights
    normalised over the selected and scaled. No group limit. `bias`
    [experts] float32, a correction the SELECTION alone reads: the
    largest of score + bias are chosen and weighed by their scores (None,
    `topk_method` "none": by the scores). Returns (experts [T, k] int32,
    weights [T, k] float32)."""
    s = jax.nn.sigmoid(jnp.dot(h, router,
                               preferred_element_type=jnp.float32))
    if bias is None:
        top, idx = jax.lax.top_k(s, cfg.n_experts_per_tok)
    else:
        _, idx = jax.lax.top_k(s + bias, cfg.n_experts_per_tok)
        top = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), top * cfg.routed_scaling_factor


def moe_branch(cfg: LatentMoeConfig, h, w, experts, layer, use_pallas=None,
               mask=None, tile_rows=None):
    """The feed-forward of an expert block for the normed stream
    h [T, D]: the shared expert plus THIS chip's part of the routed sum.
    `w`: the block's router and shared expert; `experts`: the stacked
    held experts (EXPERT_WEIGHTS, [L, E, ...]) with `layer` the block's
    index in the stack, a Python int or a traced scalar; `mask` [T]
    leaves tokens out (a decode step's idle slots); `tile_rows`: the
    dispatch plan's (None: ops/moe.py's for the pairs). Returns (y [T, D],
    pairs each held expert took [E], the router's choice [T, k])."""
    E, Me = cfg.experts_held, cfg.moe_mlp_dim
    with jax.named_scope("moe.router"):
        idx, weights = route(cfg, h, w["router"], w.get("router_bias"))
    with jax.named_scope("moe.dispatch"):
        local = idx - cfg.expert_offset
        here = (local >= 0) & (local < E)
        if mask is not None:
            here &= mask[:, None]
        plan = moe.dispatch_plan(jnp.where(here, local, E), E, tile_rows)
        x = h[plan.rows]
    with jax.named_scope("moe.experts"):
        gu = moe.grouped_matmul_int8(x, experts["we_gate_up"], layer, plan,
                                     use_pallas)
        act = jax.nn.silu(gu[:, :Me]) * gu[:, Me:]
        yb = moe.grouped_matmul_int8(act, experts["we_down"], layer, plan,
                                     use_pallas)
    with jax.named_scope("moe.shared"):
        y = swiglu(h, w)
    with jax.named_scope("moe.combine"):
        M = yb.shape[0]
        mine = plan.pos < M  # the rows of unused tiles are never read
        part = yb[jnp.minimum(plan.pos, M - 1)].astype(jnp.float32)
        routed = jnp.sum(jnp.where(mine[..., None],
                                   part * weights[..., None], 0.0), axis=1)
        y = y + routed.astype(y.dtype)
    return y, plan.counts, idx


def feed_forward(cfg: LatentMoeConfig, u, w, experts=None, layer=None,
                 use_pallas=None, mask=None):
    """The block's second branch for its input u [B, S, D]: norm, then
    the dense SwiGLU (experts None) or the expert layer. Returns (the
    branch's OUTPUT y [B, S, D], which `residual.close` puts into the
    stream; pair counts [E] or None; the router's choice or None)."""
    B, S, D = u.shape
    h = rms_norm(u, w["ln2"], cfg.rms_eps).astype(cfg.dtype)
    if experts is None:
        return swiglu(h, w), None, None
    y, counts, idx = moe_branch(cfg, h.reshape(B * S, D), w, experts, layer,
                                use_pallas, mask)
    return y.reshape(B, S, D), counts, idx.reshape(B, S, -1)


def split_experts(layers: Params):
    """(the stacked leaves a scan may slice, the held experts' stacks,
    which the grouped matmul reads where they lie)."""
    return ({k: v for k, v in layers.items() if k not in EXPERT_WEIGHTS},
            {k: layers[k] for k in EXPERT_WEIGHTS})


def logits_of(cfg: LatentMoeConfig, params: Params, x):
    """The stream x [.., D] (hc_mult streams: [.., n, D], summed) through
    ln_f and the head."""
    x = rms_norm(residual.leave(cfg, x), params["ln_f"], cfg.rms_eps)
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            return (x @ params["tok_emb"].T.astype(x.dtype)
                    ).astype(jnp.float32)
        return mm(x, params["lm_head"]).astype(jnp.float32)


def embed(cfg: LatentMoeConfig, params: Params, tokens):
    """Token ids [..] -> the stream they enter as ([.., D], or hc_mult
    copies [.., n, D])."""
    return residual.enter(
        cfg, params["tok_emb"][tokens].astype(cfg.residual_dtype))


def walk_prompt(params: Params, cfg: LatentMoeConfig, tokens, lengths=None,
                use_pallas=None, mix=None):
    """Token ids [B, S] through every block in its prompt form, one
    causal pass with no cache: the leading dense blocks unrolled, the
    expert blocks one scanned body (their held experts' weights stay
    where they lie). `mix`: who mixes hc_mult > 1 streams (None:
    models/hyper_connections.py's jax.numpy form). Returns (the stream
    [B, S, D] or [B, S, n, D], the latent rows a cache would keep
    [cache_rows, B, S, C + R], the router's choices [n_moe_layers, B, S,
    k])."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    x = embed(cfg, params, tokens)

    def block(x, w, experts=None, l=None):
        u, carry = residual.open(cfg, x, w, "attn", mix)
        h = rms_norm(u, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
        q_nope, q_rope, row = project_latent(cfg, h, w, positions)
        out = attend_prompt(cfg, q_nope, q_rope, row, w, lengths, use_pallas)
        x = residual.close(cfg, x, heads_out(out, w), carry)
        u, carry = residual.open(cfg, x, w, "ffn", mix)
        y, _, idx = feed_forward(cfg, u, w, experts, l, use_pallas)
        return residual.close(cfg, x, y, carry), row, idx

    rows = []
    for l in range(cfg.n_dense_layers):
        x, row, _ = block(x, take_layer(params["dense"], l))
        rows.append(row[None])
    sliced, experts = split_experts(params["layers"])

    def body(carry, w):
        x, l = carry
        x, row, idx = block(x, w, experts, l)
        return (x, l + 1), (row, idx)

    (x, _), (moe_rows, choices) = jax.lax.scan(
        body, (x, jnp.int32(0)), sliced)
    return x, jnp.concatenate(rows + [moe_rows], axis=0), choices


def forward(params: Params, cfg: LatentMoeConfig, tokens, *, lengths=None,
            use_pallas=None):
    """Token ids [B, S] -> (logits [B, S, V] float32, the router's
    choices): the whole model with no cache (tests, offline use)."""
    x, _, choices = walk_prompt(params, cfg, tokens, lengths, use_pallas)
    return logits_of(cfg, params, x), choices
