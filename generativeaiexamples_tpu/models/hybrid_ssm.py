"""A hybrid decoder: Mamba-2 state-space layers beside an attention layer
now and then, and in EVERY layer a feed-forward of whole sparse experts
plus a shared MLP (the `granitemoehybrid` / `bamba` family's block, as
granite-4.0-h-small publishes its keys).

What differs from models/llama.py's block:

- `layer_types` says, layer by layer, which MIXER a block has. An
  "attention" block is llama's q/k/v/o with NO positional term and a
  softmax scale that is a published multiplier (not head_dim ** -0.5); it
  caches K and V per head in the page pool, ONE cache row per attention
  layer (cfg.cache_rows). A "mamba" block caches nothing per token: it
  carries, per SEQUENCE, a float32 state [heads, head_dim, state] and the
  last `ssm_conv - 1` inputs of its depthwise convolution
  (cfg.recurrent_state; serving/kv_cache.py keeps them per decode slot).
- The Mamba-2 mixer has two forms that are the same function: a chunked
  scan over a prompt (`ssm_scan`: matmuls inside a chunk, a recurrence
  across chunks; padding past `lengths` leaves the state where the last
  real token left it) and one recurrence step for a decoded token
  (`ssm_step`, whose state update is serving/ssm_state_update.py).
- Every block's feed-forward routes each token over all `n_experts`
  experts (the `n_experts_per_tok` largest router logits, gates = softmax
  over THOSE in float32) and adds a shared MLP; every expert is held here.
- Three published multipliers: the embedding's, every branch's
  (`residual_multiplier`) and 1 / `logits_scaling` on the tied head.

Parameters: `tok_emb`, `ln_f`; three stacks, each in layer order:
`ssm` [Ls, ...] (`ln1 w_in conv_w conv_b dt_bias A_log D norm w_out`),
`attn` [La, ...] (`ln1 wq wk wv wo`) and `ffn` [L, ...] (`ln2 router
ws_in ws_out we_gate_up we_down`; `*_in` is [gate ; up]).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models.llama import rms_norm
from generativeaiexamples_tpu.ops import attention as attn_ops
from generativeaiexamples_tpu.ops import moe
from generativeaiexamples_tpu.ops.quant import (
    QuantizedTensor, mm, quantize_tensor)

Params = Dict[str, Any]

EXPERT_WEIGHTS = ("we_gate_up", "we_down")
MAMBA, ATTENTION = "mamba", "attention"
_HIGHEST = jax.lax.Precision.HIGHEST


class RecurrentState(NamedTuple):
    """What a model carries per sequence beside its cache rows
    (serving/kv_cache.py builds the per-slot pool from this)."""

    layers: int      # state-space layers
    heads: int
    head_dim: int
    state: int       # d_state
    tail: int        # convolution inputs kept: d_conv - 1
    conv_width: int  # channels the convolution runs over
    tail_itemsize: int  # the tail is in the model's type, the state float32

    @property
    def bytes_per_slot(self) -> int:
        return self.layers * (
            self.heads * self.head_dim * self.state * 4
            + self.tail * self.conv_width * self.tail_itemsize)


@dataclass(frozen=True)
class HybridSsmConfig:
    vocab_size: int = 100352
    dim: int = 4096
    layer_types: Tuple[str, ...] = ((MAMBA,) * 5 + (ATTENTION,)
                                    + (MAMBA,) * 4) * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    # tokens a chunk of the prompt scan; any chunk that divides the
    # bucket gives the same sums (the published mamba_chunk_size is 256)
    ssm_chunk: int = 128
    n_experts: int = 72
    n_experts_per_tok: int = 10
    moe_mlp_dim: int = 768
    shared_mlp_dim: int = 1536
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_eps: float = 1e-5
    max_seq_len: int = 131072
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16

    # what serving/ reads of any model configuration
    n_passes = 1
    post_norms = False
    expert_offset = 0

    def __post_init__(self):
        bad = set(self.layer_types) - {MAMBA, ATTENTION}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types {sorted(bad)}: a block's mixer "
                             f"is '{MAMBA}' or '{ATTENTION}'")
        if not self.tie_embeddings:
            raise ValueError("a tied head is what is written")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_ssm_layers(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def cache_rows(self) -> int:
        """Rows of the page pool: one per ATTENTION layer."""
        return self.layer_types.count(ATTENTION)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers

    @property
    def experts_held(self) -> int:
        return self.n_experts

    @property
    def residual_dtype(self):
        return self.dtype

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.ssm_state  # x ; B ; C, one group

    @property
    def recurrent_state(self) -> RecurrentState:
        return RecurrentState(self.n_ssm_layers, self.ssm_heads,
                              self.ssm_head_dim, self.ssm_state,
                              self.ssm_conv - 1, self.conv_width,
                              jnp.dtype(self.dtype).itemsize)

    @staticmethod
    def tiny(vocab_size: int = 256, **kw) -> "HybridSsmConfig":
        """Hermetic-test geometry: every mechanism, nothing wide."""
        base = dict(
            vocab_size=vocab_size, dim=64,
            layer_types=(MAMBA, ATTENTION, MAMBA), n_heads=4, n_kv_heads=2,
            head_dim=16, ssm_heads=4, ssm_head_dim=8, ssm_state=16,
            ssm_chunk=8, n_experts=8, n_experts_per_tok=3, moe_mlp_dim=32,
            shared_mlp_dim=48, max_seq_len=128, dtype=jnp.float32)
        base.update(kw)
        return HybridSsmConfig(**base)


# Two choices of the seeded initialiser; a checkpoint's own weights
# replace both, and the mathematics is the same whatever the draw. The
# embedding's standard deviation: the head is the same matrix (tied) and
# the stream starts at embedding_multiplier x E[token], so at the usual
# 0.02 a token's OWN logit is 1.5 beside 0.08 for every other and random
# weights answer every prompt with its last token; at 0.003 it is one
# candidate among the largest. The routed experts' down-projections (as
# latent_moe.ROUTED_INIT_GAIN): the 10th and 11th of 72 random router
# logits lie within bf16's noise in a (token, layer) in five, the float32
# reference picks another expert, and at a gain of one each step begets
# more (greedy tokens up to 3.4 % short on the chip; PERF.md, PR 35).
EMBED_INIT_STD = 0.003
ROUTED_INIT_GAIN = 0.25


def _stack_shapes(cfg: HybridSsmConfig):
    """(int8-able weights, model-type leaves of one) of the three
    stacks, by stack and name."""
    D, H, KH, Hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Ls, La, L = cfg.n_ssm_layers, cfg.cache_rows, cfg.n_layers
    E, Me, Ms = cfg.n_experts, cfg.moe_mlp_dim, cfg.shared_mlp_dim
    in_width = cfg.d_inner + cfg.conv_width + cfg.ssm_heads  # z ; xBC ; dt
    weights = {
        "ssm": {"w_in": (Ls, D, in_width), "w_out": (Ls, cfg.d_inner, D)},
        "attn": {"wq": (La, D, H * Hd), "wk": (La, D, KH * Hd),
                 "wv": (La, D, KH * Hd), "wo": (La, H * Hd, D)},
        "ffn": {"ws_in": (L, D, 2 * Ms), "ws_out": (L, Ms, D),
                "we_gate_up": (L, E, D, 2 * Me), "we_down": (L, E, Me, D)},
    }
    ones = {"ssm": {"ln1": (Ls, D), "norm": (Ls, cfg.d_inner)},
            "attn": {"ln1": (La, D)}, "ffn": {"ln2": (L, D)}}
    return weights, ones


def init_params_on_device(cfg: HybridSsmConfig, seed: int = 0, *,
                          quantize: bool = False) -> Params:
    """Seeded random parameters drawn leaf by leaf on the device, each in
    the type it is served in (latent_moe.init_params_on_device's recipe:
    uniform int8 codes, the per-column scale giving fan_in ** -0.5; norms
    of one; embedding, router, convolution in cfg.dtype). The state-space
    vectors are float32 and the family's: dt_bias the inverse softplus of
    a step log-uniform in 0.001-0.1, -A uniform in 1-16, D of one."""
    root = jax.random.key(seed)
    leaf_ids = itertools.count(1)

    def draw(fn):
        return jax.jit(fn)(jax.random.fold_in(root, next(leaf_ids)))

    def normal(*shape, scale):
        return draw(lambda k: jax.random.normal(k, shape, cfg.dtype)
                    * jnp.asarray(scale, cfg.dtype))

    def uniform(*shape, lo, hi):
        return draw(lambda k: jax.random.uniform(
            k, shape, jnp.float32, lo, hi))

    def weight(*shape, gain=1.0):
        scale = gain * shape[-2] ** -0.5
        if not quantize:
            return normal(*shape, scale=scale)

        def codes(k, shape=shape[1:]):
            return jnp.maximum(jax.lax.bitcast_convert_type(
                jax.random.bits(k, shape, jnp.uint8), jnp.int8), -127)

        # a layer at a time: temporaries of ONE layer's slice, not 6.8 GB
        q = draw(lambda k: jax.lax.map(codes, jax.random.split(k, shape[0])))
        s = jnp.full(shape[:-2] + shape[-1:], scale * 3 ** 0.5 / 127.0,
                     jnp.float32)
        return QuantizedTensor(q, s)

    weights, ones = _stack_shapes(cfg)
    gains = {"we_down": ROUTED_INIT_GAIN}
    params: Params = {
        "tok_emb": normal(cfg.vocab_size, cfg.dim, scale=EMBED_INIT_STD),
        "ln_f": jnp.ones((cfg.dim,), cfg.dtype),
    }
    for stack in ("ssm", "attn", "ffn"):
        params[stack] = {k: weight(*shape, gain=gains.get(k, 1.0))
                         for k, shape in weights[stack].items()}
        params[stack].update({k: jnp.ones(shape, cfg.dtype)
                              for k, shape in ones[stack].items()})
    Ls, Hs, W = cfg.n_ssm_layers, cfg.ssm_heads, cfg.conv_width
    step = jnp.exp(uniform(Ls, Hs, lo=jnp.log(0.001), hi=jnp.log(0.1)))
    params["ssm"].update(
        conv_w=normal(Ls, cfg.ssm_conv, W, scale=cfg.ssm_conv ** -0.5),
        conv_b=normal(Ls, W, scale=0.02),
        dt_bias=step + jnp.log(-jnp.expm1(-step)),
        A_log=jnp.log(uniform(Ls, Hs, lo=1.0, hi=16.0)),
        D=jnp.ones((Ls, Hs), jnp.float32))
    params["ffn"]["router"] = normal(cfg.n_layers, cfg.dim, cfg.n_experts,
                                     scale=cfg.dim ** -0.5)
    if quantize:  # the tied head as the matmul reads it: int8 E^T
        params["lm_head"] = jax.jit(lambda e: quantize_tensor(e.T))(
            params["tok_emb"])
    return params


def take_layer(tree: Params, l, skip=()) -> Params:
    """Block `l`'s slice of a stacked tree (an int8 weight stays codes
    and scales); the leaves named in `skip` stay whole."""
    def at(t):
        if isinstance(t, QuantizedTensor):
            return QuantizedTensor(t.q[l], t.s[l])
        return t[l]
    return {k: (v if k in skip else at(v)) for k, v in tree.items()}


def layer_plan(cfg):
    """[(mixer, index in its own stack)] in layer order, for any
    configuration with `layer_types`."""
    seen: Dict[str, int] = {}
    plan = []
    for kind in cfg.layer_types:
        plan.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return plan


def branch(cfg: HybridSsmConfig, x, y):
    """x + residual_multiplier * y, in the stream's type."""
    return x + (y * cfg.residual_multiplier).astype(x.dtype)


def embed(cfg: HybridSsmConfig, params: Params, tokens):
    return (params["tok_emb"][tokens] * cfg.embedding_multiplier
            ).astype(cfg.residual_dtype)


# -- the attention mixer ---------------------------------------------------

def project_qkv(cfg: HybridSsmConfig, h, w):
    """q, k, v of the normed stream `h` [B, S, D] as [B, heads, S, Hd]:
    llama.project_qkv with NO rotation (position_embedding_type "nope")."""
    B, S, _ = h.shape
    H, KH, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn.qkv"):
        q = mm(h, w["wq"]).reshape(B, S, H, Hd).transpose(0, 2, 1, 3)
        k = mm(h, w["wk"]).reshape(B, S, KH, Hd).transpose(0, 2, 1, 3)
        v = mm(h, w["wv"]).reshape(B, S, KH, Hd).transpose(0, 2, 1, 3)
    return q, k, v


def attn_out(cfg: HybridSsmConfig, x, out, w):
    """Heads `out` [B, H, S, Hd] through the output projection, added to
    the stream."""
    B, S, _ = x.shape
    with jax.named_scope("attn.out"):
        return branch(cfg, x, mm(out.transpose(0, 2, 1, 3).reshape(B, S, -1),
                                 w["wo"]))


# -- the state-space mixer -------------------------------------------------

def ssm_project(cfg: HybridSsmConfig, h, w):
    """[z | xBC | dt] = h W_in: z [..., d_inner], the convolution's input
    [..., conv_width] and dt [..., heads], all in the model's type."""
    with jax.named_scope("ssm.in_proj"):
        zxd = mm(h, w["w_in"])
    d, c = cfg.d_inner, cfg.conv_width
    return zxd[..., :d], zxd[..., d:d + c], zxd[..., d + c:]


def step_and_decay(w, dt):
    """D_t = softplus(dt + dt_bias) and log a_t = D_t * A, float32."""
    step = jax.nn.softplus(dt.astype(jnp.float32) + w["dt_bias"])
    return step, step * -jnp.exp(w["A_log"])


def split_xbc(cfg: HybridSsmConfig, xbc):
    """The convolution's output -> x [..., heads, head_dim], B, C
    [..., state]."""
    d, n = cfg.d_inner, cfg.ssm_state
    x = xbc[..., :d].reshape(xbc.shape[:-1] + (cfg.ssm_heads,
                                               cfg.ssm_head_dim))
    return x, xbc[..., d:d + n], xbc[..., d + n:]


def gate_and_project(cfg: HybridSsmConfig, y, z, w):
    """RMSNorm(y * silu(z)) over all d_inner (one group, the gate before
    the norm), then W_out. y [..., heads, head_dim] float32."""
    with jax.named_scope("ssm.gate_norm"):
        y = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm(y, w["norm"], cfg.rms_eps).astype(cfg.dtype)
    with jax.named_scope("ssm.out_proj"):
        return mm(y, w["w_out"])


def conv_prompt(cfg: HybridSsmConfig, xbc, w, lengths):
    """The causal depthwise convolution over prompts xbc [B, S, W] (zeros
    before the sequence), silu; and the tail a decode step continues
    from: the last `ssm_conv - 1` REAL inputs of each row [B, tail, W]."""
    T = cfg.ssm_conv - 1
    with jax.named_scope("ssm.conv"):
        padded = jnp.pad(xbc, ((0, 0), (T, 0), (0, 0)))
        S = xbc.shape[1]
        acc = w["conv_b"].astype(jnp.float32)
        for j in range(cfg.ssm_conv):
            acc = acc + padded[:, j:j + S].astype(jnp.float32) \
                * w["conv_w"][j].astype(jnp.float32)
        # padded[:, lengths + j] is input lengths - T + j
        at = lengths[:, None] + jnp.arange(T)[None, :]
        tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    return jax.nn.silu(acc).astype(cfg.dtype), tail


def conv_step(cfg: HybridSsmConfig, xbc, tail, w):
    """One token: xbc [B, W], tail [T, B, W] (oldest first, as the pool
    keeps it) -> (silu(conv) [B, W], the new tail [T, B, W])."""
    with jax.named_scope("ssm.conv"):
        window = jnp.concatenate([tail, xbc[None].astype(tail.dtype)], 0)
        acc = jnp.sum(window.astype(jnp.float32)
                      * w["conv_w"].astype(jnp.float32)[:, None], axis=0) \
            + w["conv_b"].astype(jnp.float32)
    return jax.nn.silu(acc).astype(cfg.dtype), window[1:]


def ssm_scan(cfg: HybridSsmConfig, x, Bm, Cm, step, log_a, lengths,
             state=None):
    """The recurrence S_t = a_t S_(t-1) + D_t x_t (x) B_t, y_t = S_t C_t
    over prompts, in chunks of cfg.ssm_chunk: inside a chunk the sums are
    matmuls, across chunks the state is carried. Positions at or past
    `lengths` do not advance it (their step is 0, their decay 1), so the
    state returned is the one after each row's last real token.

    x [B, S, H, P], Bm, Cm [B, S, N], step, log_a [B, S, H] float32.
    Returns (y [B, S, H, P] float32, state [B, H, P, N] float32)."""
    B, S0, H, P = x.shape
    N, Q = Bm.shape[-1], min(cfg.ssm_chunk, S0)
    real = (jnp.arange(S0)[None, :] < lengths[:, None])[..., None]
    step = jnp.where(real, step, 0.0)
    log_a = jnp.where(real, log_a, 0.0)
    spare = -S0 % Q  # whole chunks: more positions that advance nothing
    if spare:
        x, Bm, Cm, step, log_a = (
            jnp.pad(t, ((0, 0), (0, spare)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, Bm, Cm, step, log_a))
    S = S0 + spare
    f32 = jnp.float32

    def chunks(t):  # [B, S, ...] -> [S // Q, B, Q, ...]
        return jnp.moveaxis(t.reshape((B, S // Q, Q) + t.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]

    def one(S0, c):
        xc, Bc, Cc, dc, lc = c
        cum = jnp.cumsum(lc, axis=1)                       # [B, Q, H]
        # tokens j <= i of the chunk: decay from j to i, times D_j C_i.B_j
        cb = jnp.einsum("bin,bjn->bij", Cc, Bc, precision=_HIGHEST)
        decay = jnp.exp(jnp.where(
            causal, cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))
        wgt = cb[..., None] * decay * dc[:, None, :, :]    # [B, i, j, H]
        y = jnp.einsum("bijh,bjhp->bihp", wgt, xc, precision=_HIGHEST)
        # what the chunk's start state still gives token i
        y += jnp.einsum("bhpn,bin->bihp", S0, Cc, precision=_HIGHEST) \
            * jnp.exp(cum)[..., None]
        to_end = jnp.exp(cum[:, -1:, :] - cum) * dc        # [B, Q, H]
        S1 = S0 * jnp.exp(cum[:, -1])[:, :, None, None] + jnp.einsum(
            "bjh,bjhp,bjn->bhpn", to_end, xc, Bc, precision=_HIGHEST)
        return S1, y

    if state is None:
        state = jnp.zeros((B, H, P, N), f32)
    with jax.named_scope("ssm.scan"):
        state, y = jax.lax.scan(one, state, tuple(
            chunks(t.astype(f32)) for t in (x, Bm, Cm, step, log_a)))
    return jnp.moveaxis(y, 0, 1).reshape(B, S, H, P)[:, :S0], state


def ssm_prompt(cfg: HybridSsmConfig, x, w, lengths):
    """A state-space block over prompts x [B, S, D] ->
    (x, state [B, H, P, N] float32, tail [B, T, W])."""
    h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
    z, xbc, dt = ssm_project(cfg, h, w)
    xbc, tail = conv_prompt(cfg, xbc, w, lengths)
    xs, Bm, Cm = split_xbc(cfg, xbc)
    step, log_a = step_and_decay(w, dt)
    y, state = ssm_scan(cfg, xs, Bm, Cm, step, log_a, lengths)
    y = y + w["D"][:, None] * xs.astype(jnp.float32)
    return branch(cfg, x, gate_and_project(cfg, y, z, w)), state, tail


# -- the feed-forward ------------------------------------------------------

def route(cfg: HybridSsmConfig, h, router):
    """Router logits over ALL experts for tokens h [T, D] (a product
    accumulated in float32), the n_experts_per_tok largest, gates =
    softmax over those. -> (experts [T, k] int32, gates [T, k] float32)."""
    logits = jnp.dot(h, router, preferred_element_type=jnp.float32)
    top, idx = jax.lax.top_k(logits, cfg.n_experts_per_tok)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def glu(h, w_in, w_out):
    """(silu(g) * u) W_out with [g | u] = h W_in."""
    gu = mm(h, w_in)
    m = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :m]) * gu[..., m:], w_out)


# Rows a tile of the grouped matmul takes for a prompt group. Its pairs
# spread over ALL the experts (4 x 384 tokens x 10 choices over 72: some
# 200 each), so a tile of 64 pads half as many rows as ops/moe.py's 128,
# which suits a dozen held experts of 1,000 pairs each. And on the chip
# the 128-row form of these shapes (72 experts, 4096 x 1536 and 768 x
# 4096) stopped the device now and then, tiles of 64 and 32 never (PERF.md,
# PR 35: the cause inside the kernel is an open question).
PREFILL_TILE_ROWS = 64


def moe_branch(cfg: HybridSsmConfig, h, w, experts, layer, use_pallas=None,
               mask=None):
    """The feed-forward for the normed stream h [T, D]: the shared MLP
    plus the routed sum over the experts, all of them held here.
    `experts`: the stacked experts (EXPERT_WEIGHTS, [L, E, ...]) with
    `layer` the block's index; `mask` [T] leaves tokens out (a decode
    step's idle slots). Returns (y [T, D], pairs each expert took [E],
    the router's choice [T, k])."""
    E, Me = cfg.n_experts, cfg.moe_mlp_dim
    with jax.named_scope("moe.router"):
        idx, gates = route(cfg, h, w["router"])
    with jax.named_scope("moe.dispatch"):
        local = idx if mask is None else jnp.where(mask[:, None], idx, E)
        plan = moe.dispatch_plan(local, E, min(
            moe.tile_rows(idx.size), PREFILL_TILE_ROWS))
        x = h[plan.rows]
    with jax.named_scope("moe.experts"):
        gu = moe.grouped_matmul_int8(x, experts["we_gate_up"], layer, plan,
                                     use_pallas)
        act = jax.nn.silu(gu[:, :Me]) * gu[:, Me:]
        yb = moe.grouped_matmul_int8(act, experts["we_down"], layer, plan,
                                     use_pallas)
    with jax.named_scope("moe.shared"):
        y = glu(h, w["ws_in"], w["ws_out"])
    with jax.named_scope("moe.combine"):
        M = yb.shape[0]
        mine = plan.pos < M  # the rows of unused tiles are never read
        part = yb[jnp.minimum(plan.pos, M - 1)].astype(jnp.float32)
        routed = jnp.sum(jnp.where(mine[..., None],
                                   part * gates[..., None], 0.0), axis=1)
        y = y + routed.astype(y.dtype)
    return y, plan.counts, idx


def feed_forward(cfg: HybridSsmConfig, x, w, experts, layer, use_pallas=None,
                 mask=None):
    """The block from its mixer's residual add on: norm, experts plus
    shared MLP, added to x [B, S, D]. -> (x, pair counts [E], choices)."""
    B, S, D = x.shape
    h = rms_norm(x, w["ln2"], cfg.rms_eps).astype(cfg.dtype)
    y, counts, idx = moe_branch(cfg, h.reshape(B * S, D), w, experts, layer,
                                use_pallas, mask)
    return branch(cfg, x, y.reshape(B, S, D)), counts, idx.reshape(B, S, -1)


def split_experts(ffn: Params):
    """(the leaves a block slices, the experts' stacks, which the grouped
    matmul reads where they lie)."""
    return ({k: v for k, v in ffn.items() if k not in EXPERT_WEIGHTS},
            {k: ffn[k] for k in EXPERT_WEIGHTS})


def logits_of(cfg: HybridSsmConfig, params: Params, x):
    x = rms_norm(x, params["ln_f"], cfg.rms_eps)
    with jax.named_scope("lm_head"):
        head = params.get("lm_head")  # the tied embedding, int8 for serving
        y = mm(x, head) if head is not None \
            else x @ params["tok_emb"].T.astype(x.dtype)
        return y.astype(jnp.float32) / cfg.logits_scaling


def walk_prompt(params: Params, cfg: HybridSsmConfig, tokens, lengths=None,
                use_pallas=None):
    """Token ids [B, S] through every block in its prompt form, one
    causal pass with no cache, the blocks unrolled. Returns (the stream
    [B, S, D], (k, v) [cache_rows, B, KH, S, Hd] of the attention layers,
    the state-space layers' states [Ls, B, H, P, N] float32 and
    convolution tails [Ls, B, T, W] after each row's last real token,
    the router's choices [L, B, S, k])."""
    B, S = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    x = embed(cfg, params, tokens)
    sliced, experts = split_experts(params["ffn"])
    ks, vs, states, tails, choices = [], [], [], [], []
    for l, (kind, i) in enumerate(layer_plan(cfg)):
        if kind == MAMBA:
            x, state, tail = ssm_prompt(
                cfg, x, take_layer(params["ssm"], i), lengths)
            states.append(state)
            tails.append(tail)
        else:
            w = take_layer(params["attn"], i)
            h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
            q, k, v = project_qkv(cfg, h, w)
            out = attn_ops.attention(
                q, k, v, causal=True, lengths=lengths,
                scale=cfg.attention_multiplier, use_pallas=use_pallas)
            x = attn_out(cfg, x, out, w)
            ks.append(k)
            vs.append(v)
        x, _, idx = feed_forward(cfg, x, take_layer(sliced, l), experts, l,
                                 use_pallas)
        choices.append(idx)
    return (x, (jnp.stack(ks), jnp.stack(vs)), jnp.stack(states),
            jnp.stack(tails), jnp.stack(choices))


def forward(params: Params, cfg: HybridSsmConfig, tokens, *, lengths=None,
            use_pallas=None):
    """Token ids [B, S] -> (logits [B, S, V] float32, the router's
    choices): the whole model with no cache (tests, offline use)."""
    x, _, _, _, choices = walk_prompt(params, cfg, tokens, lengths,
                                      use_pallas)
    return logits_of(cfg, params, x), choices
