"""A decoder with WINDOW AND FULL ATTENTION IN ONE MODEL over whole sparse
experts (the block SmallThinker-21BA3B-Instruct publishes its keys for).

Every layer has a KIND, two switches read from two published layouts:

- `window_layout[l]` 1: the layer's token t attends s <= t with
  t - s < `window` only (4,096 tokens, its own among them); 0: it attends
  every s <= t (a GLOBAL layer).
- `rope_layout[l]` 1: q and k take the rotary embedding (rotate-half over
  the whole head, the token's position); 0: NOTHING positional is read:
  no rotation, no bias, no learned position. The published layouts are
  both `[0, 1, 1, 1]` repeated: one global unrotated layer, then three
  rotated window layers.

For layer l, with h = RMSNorm(x; ln1):

    z  = h W_r                     logits over all experts, float32 sums
    p  = softmax(z)                over ALL of them
    S  = the n_experts_per_tok largest of p (a tie to the lower index)
    g  = p[S] / sum p[S]
    q, k, v = h W_q, h W_k, h W_v  no bias, no norm on q or k
    x  = x + Attn(q, k, v) W_o
    h2 = RMSNorm(x; ln2)
    x  = x + sum_{e in S} g_e (relu(h2 W_gate,e) * (h2 W_up,e)) W_down,e

THE ROUTER READS THE ATTENTION'S INPUT h, not the feed-forward's h2: a
layer's experts and gates are known before its attention runs, so a step
program computes the dispatch plan (ops/moe.py::dispatch_plan) beside the
attention. The experts are ReGLU: "sparse" in the published "sparse ReGLU"
is the zeros `relu` leaves; the product is computed DENSE here. No shared
expert and no secondary experts (the config has no key for them). The
head is its own matrix.

serving/kv_cache.py builds a WindowPool from `cfg.window_rows`: the
global layers' rows under the page table every model has and the window
layers' rows under a second one, which holds only the pages that reach
into the window. `layer_plan` maps a layer to its row in its group.

Parameters: `tok_emb`, `ln_f`, `lm_head`; one stack `layers` in layer
order: `ln1 wq wk wv wo router ln2 we_gate_up we_down` (`we_gate_up` is
[gate ; up]).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models.llama import rms_norm, rope
from generativeaiexamples_tpu.models.sparse_attn_moe import (
    PREFILL_MOE_ROWS, PREFILL_TILE_ROWS, split_experts, take_layer)
from generativeaiexamples_tpu.ops import attention as attn_ops
from generativeaiexamples_tpu.ops import moe
from generativeaiexamples_tpu.ops.quant import QuantizedTensor, mm

Params = Dict[str, Any]

GLOBAL, WINDOW = 0, 1  # a layer's entry in `window_layout`
# The routed experts' down-projections at a quarter gain, as
# sparse_attn_moe.ROUTED_INIT_GAIN: the 6th and 7th of 64 random router
# probabilities lie within bf16's noise now and then, the float32
# reference then picks another expert, and at a gain of one each step
# begets more.
ROUTED_INIT_GAIN = 0.25
# The embedding's standard deviation in the seeded initialiser, as
# sparse_attn_moe.EMBED_INIT_STD. Read on the chip at the published
# widths (PERF.md section 6, PR 44: 8 slots, 256 greedy steps): at the
# usual 0.02 every stream repeats ONE token and the slots ask for fewer
# experts than uniform routing would (31.4 a layer and step where 48
# pairs hit 33.9); at 0.5 a stream still settles into a cycle of 1 to 3
# tokens but the slots' tokens differ and 34.4 experts are hit; at 2.0
# every stream walks (128 distinct of 128), under an embedding of
# sixteen times the energy beside the same twelve layers' branches: the
# reference checks would tell one block from another by a quarter of
# what they do now.
EMBED_INIT_STD = 0.5


class WindowRows(NamedTuple):
    """What serving/ reads of a model with window layers: the window and
    how many cache rows each group has."""

    window: int
    n_global: int
    n_window: int


@dataclass(frozen=True)
class WindowAttnMoeConfig:
    vocab_size: int = 151936
    dim: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    window: int = 4096
    # a layer's kind: 1 = window / rotated. None: [0, 1, 1, 1] repeated.
    window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    n_experts: int = 64
    n_experts_per_tok: int = 6
    moe_mlp_dim: int = 768
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    max_seq_len: int = 16384
    dtype: Any = jnp.bfloat16

    # what serving/ reads of any model configuration
    n_passes = 1
    post_norms = False
    expert_offset = 0

    def __post_init__(self):
        period = (GLOBAL, WINDOW, WINDOW, WINDOW)
        for name in ("window_layout", "rope_layout"):
            layout = getattr(self, name)
            if layout is None:
                layout = tuple(period[l % 4] for l in range(self.n_layers))
            object.__setattr__(self, name, tuple(int(k) for k in layout))
            if len(layout) != self.n_layers \
                    or set(layout) - {GLOBAL, WINDOW}:
                raise ValueError(f"{name}: one 0 or 1 a layer, "
                                 f"{self.n_layers} of them; got {layout}")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2 \
                or self.window < 1:
            raise ValueError("query heads in whole groups a KV head, rotary "
                             "pairs and a window of at least the token "
                             "itself are what is written")

    @property
    def cache_rows(self) -> int:
        return self.n_layers

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
    def window_rows(self) -> WindowRows:
        """serving/kv_cache.py builds the WindowPool from this."""
        n_window = sum(self.window_layout)
        return WindowRows(self.window, self.n_layers - n_window, n_window)

    @staticmethod
    def tiny(vocab_size: int = 256, **kw) -> "WindowAttnMoeConfig":
        """Hermetic-test geometry: every mechanism, nothing wide."""
        base = dict(
            vocab_size=vocab_size, dim=64, n_layers=4, n_heads=4,
            n_kv_heads=2, head_dim=16, window=8, n_experts=8,
            n_experts_per_tok=2, moe_mlp_dim=32, max_seq_len=128,
            dtype=jnp.float32)
        base.update(kw)
        return WindowAttnMoeConfig(**base)


def layer_plan(cfg: WindowAttnMoeConfig):
    """[(kind, row in its group of the pool)] in layer order."""
    seen = {GLOBAL: 0, WINDOW: 0}
    plan = []
    for kind in cfg.window_layout:
        plan.append((kind, seen[kind]))
        seen[kind] += 1
    return plan


def _stack_shapes(cfg: WindowAttnMoeConfig):
    """(int8-able weights, model-type matrices, norms of one) of the
    layer stack, by name."""
    D, H, KH, Hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    L, E, Me = cfg.n_layers, cfg.n_experts, cfg.moe_mlp_dim
    weights = {"wq": (L, D, H * Hd), "wk": (L, D, KH * Hd),
               "wv": (L, D, KH * Hd), "wo": (L, H * Hd, D),
               "we_gate_up": (L, E, D, 2 * Me), "we_down": (L, E, Me, D)}
    plain = {"router": (L, D, E)}
    ones = {"ln1": (L, D), "ln2": (L, D)}
    return weights, plain, ones


def init_params_on_device(cfg: WindowAttnMoeConfig, seed: int = 0, *,
                          quantize: bool = False) -> Params:
    """Seeded random parameters drawn leaf by leaf on the device, each in
    the type it is served in (sparse_attn_moe.init_params_on_device's
    recipe: uniform int8 codes, the per-column scale giving fan_in ** -0.5;
    norms of one; embedding and router in cfg.dtype)."""
    root = jax.random.key(seed)
    leaf_ids = itertools.count(1)

    def draw(fn):
        return jax.jit(fn)(jax.random.fold_in(root, next(leaf_ids)))

    def normal(*shape, scale):
        return draw(lambda k: jax.random.normal(k, shape, cfg.dtype)
                    * jnp.asarray(scale, cfg.dtype))

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

    weights, plain, ones = _stack_shapes(cfg)
    gains = {"we_down": ROUTED_INIT_GAIN}
    layers = {k: weight(*shape, gain=gains.get(k, 1.0))
              for k, shape in weights.items()}
    layers.update({k: normal(*shape, scale=shape[-2] ** -0.5)
                   for k, shape in plain.items()})
    layers.update({k: jnp.ones(shape, cfg.dtype)
                   for k, shape in ones.items()})
    head = weight(1, cfg.dim, cfg.vocab_size)
    head = QuantizedTensor(head.q[0], head.s[0]) if quantize else head[0]
    return {"tok_emb": normal(cfg.vocab_size, cfg.dim, scale=EMBED_INIT_STD),
            "ln_f": jnp.ones((cfg.dim,), cfg.dtype),
            "lm_head": head, "layers": layers}


def embed(cfg: WindowAttnMoeConfig, params: Params, tokens):
    return params["tok_emb"][tokens].astype(cfg.residual_dtype)


# -- attention -------------------------------------------------------------

def project_qkv(cfg: WindowAttnMoeConfig, h, w, positions, rotate):
    """q, k, v of the normed stream `h` [B, S, D] as [B, heads, S, Hd];
    q and k rotated at `positions` [B, S] where `rotate` (a Python bool
    or a traced scalar: the layer's entry in `rope_layout`), and left
    exactly as projected where not."""
    B, S, _ = h.shape
    H, KH, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn.qkv"):
        q = mm(h, w["wq"]).reshape(B, S, H, Hd).transpose(0, 2, 1, 3)
        k = mm(h, w["wk"]).reshape(B, S, KH, Hd).transpose(0, 2, 1, 3)
        v = mm(h, w["wv"]).reshape(B, S, KH, Hd).transpose(0, 2, 1, 3)
    if isinstance(rotate, (bool, int)):
        if rotate:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        return q, k, v
    return (jnp.where(rotate, rope(q, positions, cfg.rope_theta), q),
            jnp.where(rotate, rope(k, positions, cfg.rope_theta), k), v)


def attend_prompt(cfg: WindowAttnMoeConfig, q, k, v, lengths, windowed,
                  use_pallas=None):
    """A prompt's causal attention; a window layer's under the window
    (ops/attention.py skips the blocks wholly behind it). `windowed`: a
    traced scalar, the layer's entry in `window_layout`, which picks
    between the two forms."""
    def form(window):
        def run(q, k, v):
            with jax.named_scope("attn.window" if window else "attn.global"):
                return attn_ops.attention(
                    q, k, v, causal=True, lengths=lengths, window=window,
                    use_pallas=use_pallas)
        return run

    return jax.lax.cond(windowed, form(cfg.window), form(None), q, k, v)


def attn_out(cfg: WindowAttnMoeConfig, x, out, w):
    """Heads `out` [B, H, S, Hd] through the output projection, added to
    the stream."""
    B, S, _ = x.shape
    with jax.named_scope("attn.out"):
        y = mm(out.transpose(0, 2, 1, 3).reshape(B, S, -1), w["wo"])
        return x + y.astype(x.dtype)


# -- the feed-forward ------------------------------------------------------

def route(cfg: WindowAttnMoeConfig, h, router):
    """Router probabilities over ALL experts for tokens h [T, D] (the
    ATTENTION's normed input; logits accumulated in float32), the
    n_experts_per_tok largest (jax.lax.top_k: a tie to the lower index),
    gates = those renormalised. -> (experts [T, k] int32, gates [T, k]
    float32)."""
    with jax.named_scope("moe.router"):
        logits = jnp.dot(h, router, preferred_element_type=jnp.float32)
        top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                 cfg.n_experts_per_tok)
        return idx.astype(jnp.int32), top / jnp.sum(top, axis=-1,
                                                    keepdims=True)


def plan_dispatch(cfg: WindowAttnMoeConfig, idx, mask=None):
    """The grouped matmul's plan for the router's choice `idx` [T, k];
    `mask` [T] leaves tokens out (a decode step's idle slots). It reads
    nothing the attention writes: a step program makes it beside the
    attention."""
    with jax.named_scope("moe.dispatch"):
        E = cfg.n_experts
        local = idx if mask is None else jnp.where(mask[:, None], idx, E)
        return moe.dispatch_plan(local, E, min(moe.tile_rows(idx.size),
                                               PREFILL_TILE_ROWS))


def experts_sum(cfg: WindowAttnMoeConfig, h, gates, plan, experts, layer,
                use_pallas=None):
    """sum_e g_e (relu(h W_gate,e) * (h W_up,e)) W_down,e for the normed
    stream h [T, D] under `plan`; `experts`: the stacked experts
    ([L, E, ...]) with `layer` the block's index. -> y [T, D]."""
    Me = cfg.moe_mlp_dim
    with jax.named_scope("moe.dispatch"):
        x = h[plan.rows]
    with jax.named_scope("moe.experts"):
        gu = moe.grouped_matmul_int8(x, experts["we_gate_up"], layer, plan,
                                     use_pallas)
        act = jax.nn.relu(gu[:, :Me]) * gu[:, Me:]
        yb = moe.grouped_matmul_int8(act, experts["we_down"], layer, plan,
                                     use_pallas)
    with jax.named_scope("moe.combine"):
        M = yb.shape[0]
        mine = plan.pos < M  # the rows of unused tiles are never read
        part = yb[jnp.minimum(plan.pos, M - 1)].astype(jnp.float32)
        return jnp.sum(jnp.where(mine[..., None], part * gates[..., None],
                                 0.0), axis=1).astype(h.dtype)


def feed_forward(cfg: WindowAttnMoeConfig, x, w, experts, layer, idx, gates,
                 use_pallas=None, mask=None):
    """The block from its attention's residual add on: norm, the experts
    the router chose BEFORE the attention (`idx`, `gates` [B * S, k]),
    added to x [B, S, D]; a prompt's tokens PREFILL_MOE_ROWS at a time.
    -> (x, pair counts [E])."""
    B, S, D = x.shape
    T = B * S
    h = rms_norm(x, w["ln2"], cfg.rms_eps).astype(cfg.dtype).reshape(T, D)
    n = next(n for n in range(1, T + 1)
             if T % n == 0 and T // n <= PREFILL_MOE_ROWS)
    if n == 1:
        plan = plan_dispatch(cfg, idx, mask)
        y, counts = experts_sum(cfg, h, gates, plan, experts, layer,
                                use_pallas), plan.counts
    else:  # (a decode step's few tokens never come here: mask is None)
        def chunk(_, c):
            hc, ic, gc = c
            plan = plan_dispatch(cfg, ic)
            return None, (experts_sum(cfg, hc, gc, plan, experts, layer,
                                      use_pallas), plan.counts)

        k = idx.shape[-1]
        _, (y, counts) = jax.lax.scan(chunk, None, (
            h.reshape(n, T // n, D), idx.reshape(n, T // n, k),
            gates.reshape(n, T // n, k)))
        counts = counts.sum(axis=0)
    return x + y.reshape(B, S, D).astype(x.dtype), counts


def logits_of(cfg: WindowAttnMoeConfig, params: Params, x):
    x = rms_norm(x, params["ln_f"], cfg.rms_eps)
    with jax.named_scope("lm_head"):
        return mm(x, params["lm_head"]).astype(jnp.float32)


def walk_prompt(params: Params, cfg: WindowAttnMoeConfig, tokens,
                lengths=None, use_pallas=None, encode=None):
    """Token ids [B, S] through every block in its prompt form, one
    causal pass with no cache, the blocks a scan over the stack in
    published order (a layer's kind rides the scan and picks its
    attention; the experts are read where they lie). Returns (the stream
    [B, S, D], what each layer caches: `encode(k, v)` of k, v
    [B, KH, S, Hd] stacked over the layers, the pair as it is where
    `encode` is None; the router's choices [L, B, S, k])."""
    B, S = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    sliced, experts = split_experts(params["layers"])

    def block(x, lw):
        l, w, win, rot = lw
        h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
        idx, gates = route(cfg, h.reshape(B * S, -1), w["router"])
        q, k, v = project_qkv(cfg, h, w, positions, rot)
        out = attend_prompt(cfg, q, k, v, lengths, win, use_pallas)
        x = attn_out(cfg, x, out, w)
        x, _ = feed_forward(cfg, x, w, experts, l, idx, gates, use_pallas)
        return x, ((k, v) if encode is None else encode(k, v),
                   idx.reshape(B, S, -1))

    x, (kv, choices) = jax.lax.scan(
        block, embed(cfg, params, tokens),
        (jnp.arange(cfg.n_layers), sliced,
         jnp.asarray(cfg.window_layout, bool),
         jnp.asarray(cfg.rope_layout, bool)))
    return x, kv, choices


def forward(params: Params, cfg: WindowAttnMoeConfig, tokens, *,
            lengths=None, use_pallas=None):
    """Token ids [B, S] -> (logits [B, S, V] float32, the router's
    choices): the whole model with no cache (tests, offline use)."""
    x, _, choices = walk_prompt(params, cfg, tokens, lengths, use_pallas)
    return logits_of(cfg, params, x), choices
