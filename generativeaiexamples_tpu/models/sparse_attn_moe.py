"""A decoder with LEARNED SPARSE ATTENTION over whole sparse experts (the
block Keye-VL-2.0-30B-A3B's language model publishes its keys for: the
Qwen3-MoE block with DeepSeek's lightning indexer beside its attention).

What differs from models/llama.py's block:

- q and k take a per-head RMSNorm (a learned weight over the head's
  values) BEFORE the rotary embedding; `llama.project_qkv` rotates what
  it projects and has no norm.
- An INDEXER beside the attention: `index_heads` query heads of
  `index_head_dim`, ONE key head, a weight a query head. Every token
  caches its index key (bf16, `cfg.index_row` values) beside its K and V;
  a query's score for a cached token is
  `sum_j w[j] * relu(qI[j] . kI[s])`, and the query attends to the
  `index_topk` cached tokens of largest score (to all of them while there
  are no more than that), a tie going to the earlier token. Which tokens
  those are is `select_mask`: the threshold found by a bitwise partial
  sort, no full sort and no list of indices, so a prompt's tile of rows
  and a decode step's slots take the same function.
- Every layer's feed-forward is `n_experts` routed experts, the
  `n_experts_per_tok` largest router logits with gates = softmax over
  THOSE in float32, and NO shared expert; every expert is held here.
- The head is its own matrix.

A prompt is walked in tiles of `prefill_tile` rows: scores of the tile
against the prompt, the tile's selection, then attention under the causal
AND selected mask, so the [S, S] score matrix is never held whole.

Parameters: `tok_emb`, `ln_f`, `lm_head`; one stack `layers` in layer
order: `ln1 wq wk wv wo q_norm k_norm` (attention), `wq_idx wk_idx
k_idx_norm_w k_idx_norm_b w_idx` (the indexer), `ln2 router we_gate_up
we_down` (the experts; `we_gate_up` is [gate ; up]).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models.llama import rms_norm, rope
from generativeaiexamples_tpu.ops import moe
from generativeaiexamples_tpu.ops.quant import QuantizedTensor, mm

Params = Dict[str, Any]

EXPERT_WEIGHTS = ("we_gate_up", "we_down")
NEG_INF = -1e30
# The routed experts' down-projections at a quarter gain, as
# hybrid_ssm.ROUTED_INIT_GAIN: the 8th and 9th of 128 random router logits
# lie within bf16's noise now and then, the float32 reference then picks
# another expert, and at a gain of one each step begets more.
ROUTED_INIT_GAIN = 0.25
# The embedding's standard deviation in the seeded initialiser. A branch
# of random weights adds about 0.04 to the stream's rms a layer; at the
# usual 0.02 the stream is its context's average after one layer, greedy
# decoding reaches a fixed point (every stream repeats ONE token from its
# first steps on: sixteen of sixteen on the chip, PERF.md, PR 42), each
# slot then asks for the same eight experts a layer in every step, and how
# many of the 128 a step hits is one draw that the seed makes for the
# whole run. At 0.5 a token's own embedding outweighs the twelve layers'
# branches three to one, the next token is a function of the current one
# first and of the context second, and the streams walk.
EMBED_INIT_STD = 0.5
# Rows a tile of the grouped matmul takes for a prompt's pairs: the
# 128-row form stopped a v5e on another model's prefill shapes and 64
# never did (PERF.md section 7).
PREFILL_TILE_ROWS = 64
# Tokens the experts take at once in a prompt: 4,096 x 8 pairs keep the
# gathered rows and their products under half a gigabyte.
PREFILL_MOE_ROWS = 4096


@dataclass(frozen=True)
class SparseAttnMoeConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    n_experts: int = 128
    n_experts_per_tok: int = 8
    moe_mlp_dim: int = 768
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    max_seq_len: int = 131072
    # rows a prompt's scores, selection and attention are computed for at
    # once (the published q_chunk_size); any tile gives the same sums
    prefill_tile: int = 512
    dtype: Any = jnp.bfloat16

    # what serving/ reads of any model configuration
    n_passes = 1
    post_norms = False
    expert_offset = 0

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.head_dim % 2 \
                or self.index_head_dim % 2:
            raise ValueError("query heads in whole groups a KV head, and "
                             "rotary pairs, are what is written")

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
    def index_row(self) -> int:
        """Values of the index key a token caches beside its K and V
        (serving/kv_cache.py builds the SparseIndexPool from this)."""
        return self.index_head_dim

    @staticmethod
    def tiny(vocab_size: int = 256, **kw) -> "SparseAttnMoeConfig":
        """Hermetic-test geometry: every mechanism, nothing wide."""
        base = dict(
            vocab_size=vocab_size, dim=64, n_layers=3, n_heads=4,
            n_kv_heads=2, head_dim=16, index_heads=4, index_head_dim=8,
            index_topk=16, n_experts=8, n_experts_per_tok=2, moe_mlp_dim=32,
            max_seq_len=128, prefill_tile=8, dtype=jnp.float32)
        base.update(kw)
        return SparseAttnMoeConfig(**base)


def _stack_shapes(cfg: SparseAttnMoeConfig):
    """(int8-able weights, model-type matrices, norms of one) of the
    layer stack, by name."""
    D, H, KH, Hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    L, E, Me = cfg.n_layers, cfg.n_experts, cfg.moe_mlp_dim
    Hi, Di = cfg.index_heads, cfg.index_head_dim
    weights = {"wq": (L, D, H * Hd), "wk": (L, D, KH * Hd),
               "wv": (L, D, KH * Hd), "wo": (L, H * Hd, D),
               "wq_idx": (L, D, Hi * Di),
               "we_gate_up": (L, E, D, 2 * Me), "we_down": (L, E, Me, D)}
    plain = {"wk_idx": (L, D, Di), "w_idx": (L, D, Hi), "router": (L, D, E)}
    ones = {"ln1": (L, D), "ln2": (L, D), "q_norm": (L, Hd),
            "k_norm": (L, Hd), "k_idx_norm_w": (L, Di)}
    return weights, plain, ones


def init_params_on_device(cfg: SparseAttnMoeConfig, seed: int = 0, *,
                          quantize: bool = False) -> Params:
    """Seeded random parameters drawn leaf by leaf on the device, each in
    the type it is served in (hybrid_ssm.init_params_on_device's recipe:
    uniform int8 codes, the per-column scale giving fan_in ** -0.5; norms
    of one; embedding, router and the indexer's two narrow matrices in
    cfg.dtype)."""
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
    layers["k_idx_norm_b"] = jnp.zeros((cfg.n_layers, cfg.index_head_dim),
                                       cfg.dtype)
    head = weight(1, cfg.dim, cfg.vocab_size)
    head = QuantizedTensor(head.q[0], head.s[0]) if quantize else head[0]
    return {"tok_emb": normal(cfg.vocab_size, cfg.dim, scale=EMBED_INIT_STD),
            "ln_f": jnp.ones((cfg.dim,), cfg.dtype),
            "lm_head": head, "layers": layers}


def take_layer(tree: Params, l) -> Params:
    """Block `l`'s slice of a stacked tree (an int8 weight stays codes and
    scales)."""
    def at(t):
        if isinstance(t, QuantizedTensor):
            return QuantizedTensor(t.q[l], t.s[l])
        return t[l]
    return {k: at(v) for k, v in tree.items()}


def split_experts(layers: Params):
    """(the leaves a block slices, the experts' stacks, which the grouped
    matmul reads where they lie)."""
    return ({k: v for k, v in layers.items() if k not in EXPERT_WEIGHTS},
            {k: layers[k] for k in EXPERT_WEIGHTS})


def embed(cfg: SparseAttnMoeConfig, params: Params, tokens):
    return params["tok_emb"][tokens].astype(cfg.residual_dtype)


# -- attention -------------------------------------------------------------

def project_qkv(cfg: SparseAttnMoeConfig, h, w, positions):
    """q, k, v of the normed stream `h` [B, S, D] as [B, heads, S, Hd]:
    q and k normed a head, THEN rotated at `positions` [B, S]."""
    B, S, _ = h.shape
    H, KH, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn.qkv"):
        q = mm(h, w["wq"]).reshape(B, S, H, Hd).transpose(0, 2, 1, 3)
        k = mm(h, w["wk"]).reshape(B, S, KH, Hd).transpose(0, 2, 1, 3)
        v = mm(h, w["wv"]).reshape(B, S, KH, Hd).transpose(0, 2, 1, 3)
    with jax.named_scope("attn.qk_norm"):
        q = rope(rms_norm(q, w["q_norm"], cfg.rms_eps).astype(cfg.dtype),
                 positions, cfg.rope_theta)
        k = rope(rms_norm(k, w["k_norm"], cfg.rms_eps).astype(cfg.dtype),
                 positions, cfg.rope_theta)
    return q, k, v


def attn_out(cfg: SparseAttnMoeConfig, x, out, w):
    """Heads `out` [B, H, S, Hd] through the output projection, added to
    the stream."""
    B, S, _ = x.shape
    with jax.named_scope("attn.out"):
        y = mm(out.transpose(0, 2, 1, 3).reshape(B, S, -1), w["wo"])
        return x + y.astype(x.dtype)


# -- the indexer -----------------------------------------------------------

def project_index(cfg: SparseAttnMoeConfig, h, w, positions):
    """The indexer's three projections of the normed stream `h` [B, S, D]:
    queries [B, S, Hi, Di] and the key [B, S, Di], both rotated at
    `positions` and in bf16 (the type the key is cached in and the scores
    are multiplied in), and the heads' weights [B, S, Hi] float32 with
    both scale factors folded in."""
    B, S, _ = h.shape
    Hi, Di = cfg.index_heads, cfg.index_head_dim
    with jax.named_scope("index.project"):
        q = mm(h, w["wq_idx"]).reshape(B, S, Hi, Di).transpose(0, 2, 1, 3)
        q = rope(q, positions, cfg.rope_theta).transpose(0, 2, 1, 3)
        k = (h @ w["wk_idx"]).astype(jnp.float32)
        mu = jnp.mean(k, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(k - mu), axis=-1, keepdims=True)
        k = (k - mu) * jax.lax.rsqrt(var + cfg.rms_eps) \
            * w["k_idx_norm_w"].astype(jnp.float32) \
            + w["k_idx_norm_b"].astype(jnp.float32)
        k = rope(k[:, None], positions, cfg.rope_theta)[:, 0]
        wt = (h @ w["w_idx"]).astype(jnp.float32) * (Hi ** -0.5 * Di ** -0.5)
    return q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), wt


def index_scores(q, wt, k):
    """I[b, t, s] = sum_j wt[b, t, j] * relu(q[b, t, j] . k[b, s]):
    q [B, T, Hi, Di] and k [B, S, Di] in bf16, the products accumulated
    and compared in float32. -> [B, T, S]. A score of -0.0 is written
    +0.0, so that the bitwise order below is the order of the values."""
    with jax.named_scope("index.scores"):
        dots = jnp.einsum("bthd,bsd->bths", q, k,
                          preferred_element_type=jnp.float32)
        return jnp.einsum("bths,bth->bts", jax.nn.relu(dots), wt) + 0.0


def _largest(pred, shape, n_bits: int, step: int = 4):
    """The largest t in [0, 2 ** n_bits) for which `pred` holds, where it
    holds on a prefix of the integers (and at 0): `step` bits a pass,
    the 2 ** step - 1 candidates of a pass tested together. `pred` takes
    candidates [..., C] and gives bool [..., C]."""
    t = jnp.zeros(shape, jnp.uint32)
    for shift in range(n_bits - step, -1, -step):
        cands = t[..., None] | (
            jnp.arange(1, 1 << step, dtype=jnp.uint32) << shift)
        t = t | (jnp.sum(pred(cands), axis=-1).astype(jnp.uint32) << shift)
    return t


def select_mask(scores, valid, topk: int):
    """Which cached tokens a query attends to: `scores` [..., N] float32,
    `valid` [..., N] (the tokens it may see: causal, inside the sequence)
    -> bool [..., N], the `topk` valid tokens of largest score, a tie
    going to the EARLIER token (jax.lax.top_k's order), all of them where
    there are no more than `topk`. Exact, with no sort: the topk-th
    largest score is found 4 bits a pass on the scores' bit patterns
    (8 passes of compare-and-count), then the ties at it by position
    (4 more)."""
    with jax.named_scope("index.select"):
        N = scores.shape[-1]
        assert N < 1 << 16, N
        bits = jax.lax.bitcast_convert_type(
            jnp.where(valid, scores, -jnp.inf), jnp.int32)
        # float order as unsigned order: flip a negative's magnitude bits,
        # then the sign bit of all
        u = jax.lax.bitcast_convert_type(
            jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits), jnp.uint32) \
            ^ jnp.uint32(0x80000000)
        lead = scores.shape[:-1]
        thr = _largest(
            lambda c: jnp.sum(u[..., None, :] >= c[..., :, None], axis=-1,
                              dtype=jnp.int32) >= topk, lead, 32)
        above = u > thr[..., None]
        tie = u == thr[..., None]
        need = topk - jnp.sum(above, axis=-1, dtype=jnp.int32)  # >= 1
        pos = jnp.arange(N, dtype=jnp.uint32)
        last = _largest(
            lambda c: jnp.sum(tie[..., None, :] & (pos < c[..., :, None]),
                              axis=-1, dtype=jnp.int32) < need[..., None],
            lead, 16)
        return (above | (tie & (pos <= last[..., None]))) & valid


def masked_attention(q, k, v, mask, scale: float):
    """softmax(q k^T * scale) v over the tokens `mask` allows: q [B, H, T,
    Hd], k, v [B, KH, S, Hd], mask [B, T, S] -> [B, H, T, Hd]; a KV head
    at a time, so that one head group's [T, S] scores are what is held."""
    B, H, T, Hd = q.shape
    KH = k.shape[1]
    qg = q.reshape(B, KH, H // KH, T, Hd)

    def one(head):
        qh, kh, vh = head                          # [B, G, T, Hd], [B, S, Hd]
        s = jnp.einsum("bgtd,bsd->bgts", qh, kh,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask[:, None], s, NEG_INF)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask[:, None], p, 0.0)
        denom = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bgts,bsd->bgtd", p.astype(vh.dtype), vh,
                       preferred_element_type=jnp.float32)
        return o / jnp.where(denom == 0.0, 1.0, denom)

    out = jax.lax.map(one, (jnp.moveaxis(qg, 1, 0), jnp.moveaxis(k, 1, 0),
                            jnp.moveaxis(v, 1, 0)))   # [KH, B, G, T, Hd]
    return jnp.moveaxis(out, 0, 1).reshape(B, H, T, Hd).astype(q.dtype)


def sparse_attend_prompt(cfg: SparseAttnMoeConfig, q, k, v, qi, wt, ki,
                         lengths):
    """A prompt's attention, `prefill_tile` rows at a time: the tile's
    index scores against the whole prompt, its selection among the tokens
    at or before each row, attention under that mask. q [B, H, S, Hd],
    k, v [B, KH, S, Hd], qi [B, S, Hi, Di], wt [B, S, Hi], ki [B, S, Di],
    lengths [B] (a padded row selects nothing) -> [B, H, S, Hd]."""
    B, H, S, Hd = q.shape
    T = min(cfg.prefill_tile, S)
    spare = -S % T  # whole tiles: more rows that select nothing
    if spare:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, spare), (0, 0)))
        qi = jnp.pad(qi, ((0, 0), (0, spare), (0, 0), (0, 0)))
        wt = jnp.pad(wt, ((0, 0), (0, spare), (0, 0)))
    cols = jnp.arange(S)

    def tile(_, i):
        rows = i * T + jnp.arange(T)
        qt = jax.lax.dynamic_slice_in_dim(q, i * T, T, axis=2)
        scores = index_scores(
            jax.lax.dynamic_slice_in_dim(qi, i * T, T, axis=1),
            jax.lax.dynamic_slice_in_dim(wt, i * T, T, axis=1), ki)
        valid = (cols[None, None, :] <= rows[None, :, None]) \
            & (cols[None, None, :] < lengths[:, None, None]) \
            & (rows[None, :, None] < lengths[:, None, None])
        mask = select_mask(scores, valid, cfg.index_topk)
        with jax.named_scope("attn.sparse"):
            return None, masked_attention(qt, k, v, mask, Hd ** -0.5)

    _, out = jax.lax.scan(tile, None, jnp.arange((S + spare) // T))
    return jnp.moveaxis(out, 0, 2).reshape(B, H, S + spare, Hd)[:, :, :S]


# -- the feed-forward ------------------------------------------------------

def route(cfg: SparseAttnMoeConfig, h, router):
    """Router logits over ALL experts for tokens h [T, D] (a product
    accumulated in float32), the n_experts_per_tok largest, gates =
    softmax over those. -> (experts [T, k] int32, gates [T, k] float32)."""
    logits = jnp.dot(h, router, preferred_element_type=jnp.float32)
    top, idx = jax.lax.top_k(logits, cfg.n_experts_per_tok)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def moe_branch(cfg: SparseAttnMoeConfig, h, w, experts, layer,
               use_pallas=None, mask=None):
    """The feed-forward for the normed stream h [T, D]: the routed sum
    over the experts, all of them held here and none shared. `experts`:
    the stacked experts (EXPERT_WEIGHTS, [L, E, ...]) with `layer` the
    block's index (a Python int or a traced scalar); `mask` [T] leaves
    tokens out (a decode step's idle slots). Returns (y [T, D], pairs
    each expert took [E], the router's choice [T, k])."""
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
    with jax.named_scope("moe.combine"):
        M = yb.shape[0]
        mine = plan.pos < M  # the rows of unused tiles are never read
        part = yb[jnp.minimum(plan.pos, M - 1)].astype(jnp.float32)
        y = jnp.sum(jnp.where(mine[..., None], part * gates[..., None], 0.0),
                    axis=1).astype(h.dtype)
    return y, plan.counts, idx


def feed_forward(cfg: SparseAttnMoeConfig, x, w, experts, layer,
                 use_pallas=None, mask=None):
    """The block from its attention's residual add on: norm, experts,
    added to x [B, S, D]; a prompt's tokens PREFILL_MOE_ROWS at a time.
    -> (x, pair counts [E], choices [B, S, k])."""
    B, S, D = x.shape
    h = rms_norm(x, w["ln2"], cfg.rms_eps).astype(cfg.dtype)
    h = h.reshape(B * S, D)
    n = next(n for n in range(1, B * S + 1)
             if (B * S) % n == 0 and (B * S) // n <= PREFILL_MOE_ROWS)
    if n == 1:
        y, counts, idx = moe_branch(cfg, h, w, experts, layer, use_pallas,
                                    mask)
    else:  # (a decode step's few tokens never come here: mask is None)
        def chunk(_, hc):
            return None, moe_branch(cfg, hc, w, experts, layer, use_pallas)

        _, (y, counts, idx) = jax.lax.scan(chunk, None,
                                           h.reshape(n, (B * S) // n, D))
        y, counts = y.reshape(B * S, D), counts.sum(axis=0)
    return (x + y.reshape(B, S, D).astype(x.dtype), counts,
            idx.reshape(B, S, -1))


def logits_of(cfg: SparseAttnMoeConfig, params: Params, x):
    x = rms_norm(x, params["ln_f"], cfg.rms_eps)
    with jax.named_scope("lm_head"):
        return mm(x, params["lm_head"]).astype(jnp.float32)


def walk_prompt(params: Params, cfg: SparseAttnMoeConfig, tokens,
                lengths=None, use_pallas=None):
    """Token ids [B, S] through every block in its prompt form, one
    causal pass with no cache, the blocks a scan over the stack (the
    experts read where they lie). Returns (the stream [B, S, D], what
    each layer caches: k, v [L, B, KH, S, Hd] and the index keys
    [L, B, S, Di] bf16, the router's choices [L, B, S, k])."""
    B, S = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    sliced, experts = split_experts(params["layers"])

    def block(x, lw):
        l, w = lw
        h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
        q, k, v = project_qkv(cfg, h, w, positions)
        qi, ki, wt = project_index(cfg, h, w, positions)
        out = sparse_attend_prompt(cfg, q, k, v, qi, wt, ki, lengths)
        x = attn_out(cfg, x, out, w)
        x, _, idx = feed_forward(cfg, x, w, experts, l, use_pallas)
        return x, (k, v, ki, idx)

    x, (ks, vs, kis, choices) = jax.lax.scan(
        block, embed(cfg, params, tokens),
        (jnp.arange(cfg.n_layers), sliced))
    return x, (ks, vs, kis), choices


def forward(params: Params, cfg: SparseAttnMoeConfig, tokens, *,
            lengths=None, use_pallas=None):
    """Token ids [B, S] -> (logits [B, S, V] float32, the router's
    choices): the whole model with no cache (tests, offline use)."""
    x, _, choices = walk_prompt(params, cfg, tokens, lengths, use_pallas)
    return logits_of(cfg, params, x), choices
