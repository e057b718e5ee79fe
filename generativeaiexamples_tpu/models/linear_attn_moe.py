"""A decoder whose layers mix tokens by LINEAR attention with a delta rule
(Kimi Delta Attention, KDA) and, one in four, by latent attention with no
positional term; a leading dense SwiGLU layer, then sparse experts with a
shared one (the `kimi_linear` family's block, as Kimi-Linear-48B-A3B
publishes its keys).

What differs from its two siblings:

- `layer_types` says, layer by layer, which MIXER a block has (as
  models/hybrid_ssm.py's). A "kda" block caches nothing per token: it
  carries, per SEQUENCE, a float32 state [heads, key, value] and the last
  `kda_conv - 1` inputs of its three depthwise convolutions
  (cfg.recurrent_state; serving/kv_cache.py keeps them per decode slot).
  An "mla" block is models/latent_moe.py's attention, IMPORTED and not
  copied, with queries projected directly (`q_lora_rank` None) and nothing
  rotated (`rotary` False); it caches one row a token (cfg.latent_row).
- The KDA mixer, per head, on q, k (L2-normed; q also scaled by
  d ** -0.5) and v after their convolutions and SiLU:

      S_t = Diag(a_t) S_(t-1) + k_t u_t^T
      u_t = b_t (v_t - (Diag(a_t) S_(t-1))^T k_t)       o_t = S_t^T q_t

  with a decay a_t in (0, 1) per head AND key channel and a step b_t in
  (0, 1) per head. Two forms that are the same function: over a prompt in
  chunks (`kda_chunks`: matmuls inside a chunk, a recurrence across
  chunks; padding past `lengths` leaves the state where the last real
  token left it) and one step for a decoded token, whose state update is
  serving/kda_state_update.py (its XLA form off the chip).
- The feed-forward is latent_moe's, imported: `n_dense_layers` leading
  SwiGLU blocks, then routed experts (sigmoid scores, the
  `n_experts_per_tok` largest of score + `router_bias`, weighed by their
  scores) plus the shared expert, the routed part for the `experts_held`
  experts from `expert_offset` on.

Parameters: `tok_emb`, `ln_f`, `lm_head`; four stacks, each in layer
order: `kda` [Lk, ...] (`ln1 w_qkv conv_w w_ab w_fb w_gb A_log dt_bias
o_norm wo`; `w_ab` is [decay low rank | gate low rank | step]), `mla`
[La, ...] (`ln1 w_q w_kva kv_norm w_kvb wo`), `dense` [n_dense_layers,
...] (`ln2 w_gate w_up w_down`) and `layers` [Lm, ...] (`ln2 router
router_bias w_gate w_up w_down we_gate_up we_down`).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models import hyper_connections as residual
from generativeaiexamples_tpu.models import latent_moe
from generativeaiexamples_tpu.models.hybrid_ssm import (
    RecurrentState, layer_plan)
from generativeaiexamples_tpu.models.latent_moe import (
    ROUTED_INIT_GAIN, logits_of, split_experts, take_layer)
from generativeaiexamples_tpu.models.llama import attn_out, rms_norm
from generativeaiexamples_tpu.ops.quant import QuantizedTensor, mm

Params = Dict[str, Any]

KDA, MLA = "kda", "mla"
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

# Seeded initialisation of what decides how long a state remembers (a
# checkpoint's own values replace all of it): A = exp(A_log) uniform in
# 1-4 a head, dt_bias the inverse softplus of a step log-uniform in
# 0.001-0.025 a head and channel, and the decay's second low-rank factor
# at half gain, so that a = exp(-A * softplus(x + dt_bias)) lies in about
# 0.9-0.999 and a state remembers tens to a thousand tokens. At a near 0
# every test of a carried state would pass with the state thrown away.
DECAY_A = (1.0, 4.0)
DECAY_STEP = (0.001, 0.025)
DECAY_GATE_GAIN = 0.5
ROUTER_BIAS_STD = latent_moe.ROUTER_BIAS_STD  # 0.02: one draw for both
# ... and of how a rounding travels through the depth. At the usual 0.02
# the embedding is a fortieth of the first branch's output, every early
# block's input is then its predecessors' output alone, and random blocks
# of this kind double a relative perturbation a layer: a bf16 rounding of
# 0.4 % reached 25 % of the largest logit after 27 layers, on the chip and
# on the CPU alike, in float32 activations too (a TPU multiplies float32
# in bf16 passes by default; PERF.md, PR 48). So the stream starts at the
# embedding's own size and every branch's last projection is drawn at
# (2 * n_layers) ** -0.5 of its fan-in scale, the GPT-2 family's
# convention: the branches' sum is then as large as the embedding after
# the last layer, and no one block's input is another block's output
# alone. The routed experts' down-projections take ROUTED_INIT_GAIN
# besides (latent_moe's cure for a router's near-ties).
EMBED_INIT_STD = 1.0


def residual_init_gain(cfg) -> float:
    return (2 * cfg.n_layers) ** -0.5


@dataclass(frozen=True)
class LinearAttnMoeConfig:
    vocab_size: int = 163840
    dim: int = 2304
    layer_types: Tuple[str, ...] = ((KDA,) * 3 + (MLA,)) * 6 + (KDA, KDA, MLA)
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_rank: int = 128      # the decay's and the gate's low rank
    # tokens a chunk of the prompt form, in sub-blocks of kda_sub: any pair
    # that divides gives the same sums (measured: PERF.md, PR 48)
    kda_chunk: int = 64
    kda_sub: int = 16
    n_heads: int = 32        # the latent attention's
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 9216
    moe_mlp_dim: int = 1024
    n_dense_layers: int = 1
    n_routed_experts: int = 256
    n_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    experts_held: int = 256
    expert_offset: int = 0
    rms_eps: float = 1e-5
    max_seq_len: int = 1048576
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    # what serving/ reads of any model configuration
    n_passes = 1
    post_norms = False
    # what latent_moe's attention reads: queries projected directly, and
    # nothing rotated (HF q_lora_rank null, mla_use_nope)
    q_lora_rank = None
    rotary = False

    def __post_init__(self):
        bad = set(self.layer_types) - {KDA, MLA}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types {sorted(bad)}: a block's mixer "
                             f"is '{KDA}' or '{MLA}'")
        if not 0 < self.experts_held <= self.n_routed_experts \
                - self.expert_offset:
            raise ValueError(
                f"experts_held {self.experts_held} from expert_offset "
                f"{self.expert_offset} on: the router has "
                f"{self.n_routed_experts} experts")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is what is written")
        if self.kda_chunk % self.kda_sub:
            raise ValueError(f"kda_chunk {self.kda_chunk} is no multiple "
                             f"of kda_sub {self.kda_sub}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_kda_layers(self) -> int:
        return self.layer_types.count(KDA)

    @property
    def cache_rows(self) -> int:
        """Rows of the latent page pool: one per MLA layer."""
        return self.layer_types.count(MLA)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def residual_dtype(self):
        return self.dtype

    @property
    def d_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def latent_row(self) -> Tuple[int, int]:
        return self.kv_lora_rank, self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def recurrent_state(self) -> RecurrentState:
        """A KDA layer's state is [heads, key, value]; the tail holds q, k
        and v's convolution inputs side by side."""
        return RecurrentState(self.n_kda_layers, self.kda_heads,
                              self.kda_head_dim, self.kda_head_dim,
                              self.kda_conv - 1, 3 * self.d_inner,
                              jnp.dtype(self.dtype).itemsize)

    @staticmethod
    def tiny(vocab_size: int = 256, **kw) -> "LinearAttnMoeConfig":
        """Hermetic-test geometry: every mechanism, nothing wide."""
        base = dict(
            vocab_size=vocab_size, dim=64,
            layer_types=(KDA, KDA, MLA, KDA, MLA), kda_heads=4,
            kda_head_dim=16, kda_rank=8, kda_chunk=8, kda_sub=4, n_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, mlp_dim=128, moe_mlp_dim=32,
            n_routed_experts=16, n_experts_per_tok=4, experts_held=4,
            expert_offset=4, max_seq_len=128, dtype=jnp.float32)
        base.update(kw)
        return LinearAttnMoeConfig(**base)


def _stack_shapes(cfg: LinearAttnMoeConfig):
    """(int8-able weights, model-type leaves of one) by stack and name."""
    D, di, r = cfg.dim, cfg.d_inner, cfg.kda_rank
    Lk, La, Ld, Lm = (cfg.n_kda_layers, cfg.cache_rows, cfg.n_dense_layers,
                      cfg.n_moe_layers)
    E, Me = cfg.experts_held, cfg.moe_mlp_dim

    def ffn(L, mlp):
        return {"w_gate": (L, D, mlp), "w_up": (L, D, mlp),
                "w_down": (L, mlp, D)}

    mla_w, mla_ones = latent_moe.attention_shapes(cfg, La)
    weights = {
        "kda": {"w_qkv": (Lk, D, 3 * di),
                "w_ab": (Lk, D, 2 * r + cfg.kda_heads),
                "w_fb": (Lk, r, di), "w_gb": (Lk, r, di), "wo": (Lk, di, D)},
        "mla": mla_w,
        "dense": ffn(Ld, cfg.mlp_dim),
        "layers": dict(ffn(Lm, Me), we_gate_up=(Lm, E, D, 2 * Me),
                       we_down=(Lm, E, Me, D)),
    }
    ones = {"kda": {"ln1": (Lk, D), "o_norm": (Lk, cfg.kda_head_dim)},
            "mla": mla_ones, "dense": {"ln2": (Ld, D)},
            "layers": {"ln2": (Lm, D)}}
    return weights, ones


def init_params_on_device(cfg: LinearAttnMoeConfig, seed: int = 0, *,
                          quantize: bool = False) -> Params:
    """Seeded random parameters drawn leaf by leaf on the device, each in
    the type it is served in (latent_moe.init_params_on_device's recipe:
    uniform int8 codes, the per-column scale giving fan_in ** -0.5; norms
    of one; embedding, router and convolution taps in cfg.dtype; float32
    A_log, dt_bias and router_bias: DECAY_* above)."""
    root = jax.random.key(seed)
    leaf_ids = itertools.count(1)

    def draw(fn):
        return jax.jit(fn)(jax.random.fold_in(root, next(leaf_ids)))

    def normal(*shape, scale, dtype=cfg.dtype):
        return draw(lambda k: jax.random.normal(k, shape, dtype)
                    * jnp.asarray(scale, dtype))

    def uniform(*shape, lo, hi):
        return draw(lambda k: jax.random.uniform(k, shape, _F32, lo, hi))

    def weight(*shape, gain=1.0):
        scale = gain * shape[-2] ** -0.5
        if not quantize:
            return normal(*shape, scale=scale)

        def codes(k, shape=shape[1:]):
            return jnp.maximum(jax.lax.bitcast_convert_type(
                jax.random.bits(k, shape, jnp.uint8), jnp.int8), -127)

        # a layer at a time: temporaries of ONE layer's slice
        q = draw(lambda k: jax.lax.map(codes, jax.random.split(k, shape[0])))
        s = jnp.full(shape[:-2] + shape[-1:], scale * 3 ** 0.5 / 127.0, _F32)
        return QuantizedTensor(q, s)

    weights, ones = _stack_shapes(cfg)
    out = residual_init_gain(cfg)
    gains = {"wo": out, "w_down": out, "we_down": out * ROUTED_INIT_GAIN,
             "w_fb": DECAY_GATE_GAIN}
    D, Lk, Lm = cfg.dim, cfg.n_kda_layers, cfg.n_moe_layers
    params: Params = {
        "tok_emb": normal(cfg.vocab_size, D, scale=EMBED_INIT_STD),
        "ln_f": jnp.ones((D,), cfg.dtype),
    }
    for stack in ("kda", "mla", "dense", "layers"):
        params[stack] = {k: weight(*shape, gain=gains.get(k, 1.0))
                         for k, shape in weights[stack].items()}
        params[stack].update({k: jnp.ones(shape, cfg.dtype)
                              for k, shape in ones[stack].items()})
    step = jnp.exp(uniform(Lk, cfg.d_inner, lo=jnp.log(DECAY_STEP[0]),
                           hi=jnp.log(DECAY_STEP[1])))
    params["kda"].update(
        conv_w=normal(Lk, cfg.kda_conv, 3 * cfg.d_inner,
                      scale=cfg.kda_conv ** -0.5),
        A_log=jnp.log(uniform(Lk, cfg.kda_heads, lo=DECAY_A[0],
                              hi=DECAY_A[1])),
        dt_bias=step + jnp.log(-jnp.expm1(-step)))
    params["layers"].update(
        router=normal(Lm, D, cfg.n_routed_experts, scale=D ** -0.5),
        router_bias=normal(Lm, cfg.n_routed_experts, scale=ROUTER_BIAS_STD,
                           dtype=_F32))
    if not cfg.tie_embeddings:
        params["lm_head"] = weight(D, cfg.vocab_size)
    return params


def embed(cfg: LinearAttnMoeConfig, params: Params, tokens):
    return params["tok_emb"][tokens].astype(cfg.residual_dtype)


# -- the KDA mixer -----------------------------------------------------------

def _mm32(x, w):
    """x @ w accumulated and returned in float32 (the gates: a rounding of
    the decay's exponent is multiplied by every later token)."""
    if isinstance(w, QuantizedTensor):
        return jnp.dot(x, w.q.astype(x.dtype),
                       preferred_element_type=_F32) * w.s
    return jnp.dot(x, w, preferred_element_type=_F32)


def kda_project(cfg: LinearAttnMoeConfig, h, w):
    """From the normed stream h [..., D]: the three convolutions' input
    [..., 3 * d_inner] in the model's type; the log decay g [..., H, d]
    (<= 0), the step b [..., H] and the output gate [..., H, d], float32."""
    H, d, r = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_rank
    with jax.named_scope("kda.qkv"):
        qkv = mm(h, w["w_qkv"])
    with jax.named_scope("kda.gates"):
        ab = _mm32(h, w["w_ab"])
        f = _mm32(ab[..., :r].astype(cfg.dtype), w["w_fb"])
        gate = _mm32(ab[..., r:2 * r].astype(cfg.dtype), w["w_gb"])
        g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
            (f + w["dt_bias"]).reshape(f.shape[:-1] + (H, d)))
        beta = jax.nn.sigmoid(ab[..., 2 * r:])
        gate = jax.nn.sigmoid(gate.reshape(g.shape))
    return qkv, g, beta, gate


def conv_prompt(cfg: LinearAttnMoeConfig, qkv, w, lengths):
    """The causal depthwise convolutions over prompts qkv [B, S, W] (zeros
    before the sequence, no bias), silu; and the tail a decode step
    continues from: the last `kda_conv - 1` REAL inputs of each row
    [B, tail, W]."""
    T = cfg.kda_conv - 1
    with jax.named_scope("kda.conv"):
        padded = jnp.pad(qkv, ((0, 0), (T, 0), (0, 0)))
        S = qkv.shape[1]
        acc = 0.0
        for j in range(cfg.kda_conv):
            acc = acc + padded[:, j:j + S].astype(_F32) \
                * w["conv_w"][j].astype(_F32)
        # padded[:, lengths + j] is input lengths - T + j
        at = lengths[:, None] + jnp.arange(T)[None, :]
        tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    return jax.nn.silu(acc), tail


def conv_step(cfg: LinearAttnMoeConfig, qkv, tail, w):
    """One token: qkv [B, W], tail [T, B, W] (oldest first, as the pool
    keeps it) -> (silu(conv) [B, W] float32, the new tail [T, B, W])."""
    with jax.named_scope("kda.conv"):
        window = jnp.concatenate([tail, qkv[None].astype(tail.dtype)], 0)
        acc = jnp.sum(window.astype(_F32)
                      * w["conv_w"].astype(_F32)[:, None], axis=0)
    return jax.nn.silu(acc), window[1:]


def split_qkv(cfg: LinearAttnMoeConfig, qkv):
    """The convolutions' output [..., 3 * d_inner] float32 -> q, k, v
    [..., H, d]: q and k L2-normed a head, q scaled by d ** -0.5."""
    H, d = cfg.kda_heads, cfg.kda_head_dim
    q, k, v = (t.reshape(t.shape[:-1] + (H, d))
               for t in jnp.split(qkv, 3, axis=-1))

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    return unit(q) * d ** -0.5, unit(k), v


def kda_out(cfg: LinearAttnMoeConfig, x, o, gate, w):
    """RMSNorm over each head's d (one weight for all heads) times the
    sigmoid gate, heads concatenated, W_o, added to the stream. o, gate
    [..., H, d] float32."""
    with jax.named_scope("kda.gate_norm"):
        o = rms_norm(o, w["o_norm"].astype(_F32), cfg.rms_eps) * gate
        o = o.reshape(o.shape[:-2] + (cfg.d_inner,)).astype(cfg.dtype)
    with jax.named_scope("kda.out"):
        return x + mm(o, w["wo"]).astype(x.dtype)


def _inverse_unit_lower(n_mat):
    """(I + N)^-1 for N [..., C, C] strictly lower triangular: N is
    nilpotent, so the inverse is the finite sum of (-N)^k, k < C, taken as
    the product of (I + (-N)^(2^i)): log2(C) squarings, all matmuls."""
    C = n_mat.shape[-1]
    eye = jnp.eye(C, dtype=n_mat.dtype)
    m = -n_mat
    inv = eye + m
    for _ in range(max(C - 1, 1).bit_length() - 1):
        m = jnp.matmul(m, m, precision=_HIGHEST)
        inv = jnp.matmul(inv, eye + m, precision=_HIGHEST)
    return inv


def _decayed_products(cfg: LinearAttnMoeConfig, xs, k, G):
    """For each x of `xs` [B, C, H, d]: P[t, j] = sum over the key
    channels c of x_t[c] k_j[c] exp(G_t[c] - G_j[c]) for j <= t, as
    [B, H, C, C] (0 above the diagonal). G [B, C, H, d] is the running sum
    of the log decay, so every exponent formed is <= 0: inside a sub-block
    of kda_sub tokens the exponent itself, elementwise; between sub-blocks
    through the running sum R_I at the later block's start, exp(G_t - R_I)
    on the one side and exp(R_I - G_j) on the other, as matmuls."""
    B, C, H, d = k.shape
    c = min(cfg.kda_sub, C)
    n = C // c

    def blocks(t):  # [B, C, H, d] -> [B, H, n, c, d]
        return t.reshape(B, n, c, H, d).transpose(0, 3, 1, 2, 4)

    kb, Gb = blocks(k), blocks(G)
    # inside a sub-block: [B, H, n, c(t), c(j), d]
    lower = jnp.tril(jnp.ones((c, c), bool))[:, :, None]
    inside = jnp.exp(jnp.where(
        lower, Gb[:, :, :, :, None, :] - Gb[:, :, :, None, :, :], -jnp.inf))
    inside = inside * kb[:, :, :, None, :, :]
    # between sub-blocks: R_I is G just before block I's first token
    R = jnp.concatenate([jnp.zeros_like(Gb[:, :, :1, -1]),
                         Gb[:, :, :-1, -1]], axis=2)       # [B, H, n, d]
    earlier = jnp.tril(jnp.ones((n, n), bool), -1)[:, :, None, None]
    k_to = kb[:, :, None] * jnp.exp(jnp.where(
        earlier, R[:, :, :, None, None, :] - Gb[:, :, None], -jnp.inf))
    eye = jnp.eye(n, dtype=_F32)
    out = []
    for x in xs:
        xb = blocks(x)
        diag = jnp.einsum("bhItd,bhItjd->bhItj", xb, inside,
                          precision=_HIGHEST)
        off = jnp.einsum("bhItd,bhIJjd->bhItJj",
                         xb * jnp.exp(Gb - R[:, :, :, None, :]), k_to,
                         precision=_HIGHEST)
        full = off + diag[:, :, :, :, None, :] * eye[None, None, :, None, :,
                                                     None]
        out.append(full.reshape(B, H, C, C))
    return out


def kda_chunks(cfg: LinearAttnMoeConfig, q, k, v, g, beta, lengths,
               state=None):
    """The recurrence over prompts, in chunks of cfg.kda_chunk: inside a
    chunk a triangular system and matmuls, across chunks the state is
    carried. Positions at or past `lengths` do not advance it (their step
    is 0, their decay 1), so the state returned is the one after each
    row's last real token.

    q, k, v, g [B, S, H, d] float32, beta [B, S, H]. Returns (o [B, S, H,
    d] float32, state [B, H, d (key), d (value)] float32)."""
    B, S0, H, d = q.shape
    C = min(cfg.kda_chunk, -(-S0 // cfg.kda_sub) * cfg.kda_sub)
    real = jnp.arange(S0)[None, :] < lengths[:, None]
    g = jnp.where(real[..., None, None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    spare = -S0 % C  # whole chunks: more positions that advance nothing
    if spare:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, spare)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    S = S0 + spare

    def chunks(t):  # [B, S, ...] -> [S // C, B, C, ...]
        return jnp.moveaxis(t.reshape((B, S // C, C) + t.shape[2:]), 1, 0)

    strict = jnp.tril(jnp.ones((C, C), bool), -1)

    def one(S0, c):
        qc, kc, vc, gc, bc = c
        G = jnp.cumsum(gc, axis=1)                         # [B, C, H, d]
        A, Bm = _decayed_products(cfg, (kc, qc), kc, G)    # [B, H, C, C]
        bt = jnp.moveaxis(bc, 1, 2)[..., None]             # [B, H, C, 1]
        T = _inverse_unit_lower(jnp.where(strict, A, 0.0) * bt)
        decay = jnp.exp(G)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bthc,bhcv->bthv", kc * decay, S0, precision=_HIGHEST))
        U = jnp.einsum("bhtj,bjhv->bthv", T, rhs, precision=_HIGHEST)
        o = jnp.einsum("bthc,bhcv->bthv", qc * decay, S0,
                       precision=_HIGHEST) \
            + jnp.einsum("bhtj,bjhv->bthv", Bm, U, precision=_HIGHEST)
        to_end = jnp.exp(G[:, -1:] - G)                    # <= 1
        S1 = decay[:, -1][..., None] * S0 \
            + jnp.einsum("bjhc,bjhv->bhcv", kc * to_end, U,
                         precision=_HIGHEST)
        return S1, o

    if state is None:
        state = jnp.zeros((B, H, d, d), _F32)
    with jax.named_scope("kda.chunk"):
        state, o = jax.lax.scan(one, state, tuple(
            chunks(t.astype(_F32)) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(B, S, H, d)[:, :S0], state


def kda_prompt(cfg: LinearAttnMoeConfig, x, w, lengths):
    """A KDA block's mixer over prompts x [B, S, D] ->
    (x, state [B, H, d, d] float32, tail [B, T, W])."""
    h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
    qkv, g, beta, gate = kda_project(cfg, h, w)
    qkv, tail = conv_prompt(cfg, qkv, w, lengths)
    q, k, v = split_qkv(cfg, qkv)
    o, state = kda_chunks(cfg, q, k, v, g, beta, lengths)
    return kda_out(cfg, x, o, gate, w), state, tail


# -- the walk ----------------------------------------------------------------

def ffn_weights(cfg: LinearAttnMoeConfig, params: Params, l: int):
    """Layer `l`'s feed-forward: (its sliced leaves, the held experts'
    stacks or None, its index in that stack or None)."""
    n = cfg.n_dense_layers
    if l < n:
        return take_layer(params["dense"], l), None, None
    sliced, experts = split_experts(params["layers"])
    return take_layer(sliced, l - n), experts, l - n


def mla_prompt(cfg: LinearAttnMoeConfig, x, w, lengths, use_pallas=None):
    """A latent-attention block's mixer over prompts -> (x, the rows a
    cache would keep [B, S, C + R])."""
    h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
    q_nope, q_rope, row = latent_moe.project_latent(cfg, h, w, None)
    out = latent_moe.attend_prompt(cfg, q_nope, q_rope, row, w, lengths,
                                   use_pallas)
    return attn_out(cfg, x, out, w), row


def walk_prompt(params: Params, cfg: LinearAttnMoeConfig, tokens,
                lengths=None, use_pallas=None):
    """Token ids [B, S] through every block in its prompt form, one causal
    pass with no cache, the blocks unrolled. Returns (the stream [B, S, D],
    the latent rows a cache would keep [cache_rows, B, S, C + R], the KDA
    layers' states [Lk, B, H, d, d] float32 and convolution tails [Lk, B,
    T, W] after each row's last real token, the router's choices
    [n_moe_layers, B, S, k])."""
    B, S = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    x = embed(cfg, params, tokens)
    rows, states, tails, choices = [], [], [], []
    for l, (kind, i) in enumerate(layer_plan(cfg)):
        if kind == KDA:
            x, state, tail = kda_prompt(
                cfg, x, take_layer(params["kda"], i), lengths)
            states.append(state)
            tails.append(tail)
        else:
            x, row = mla_prompt(cfg, x, take_layer(params["mla"], i),
                                lengths, use_pallas)
            rows.append(row)
        w, experts, e = ffn_weights(cfg, params, l)
        y, _, idx = latent_moe.feed_forward(cfg, x, w, experts, e,
                                            use_pallas)
        x = residual.close(cfg, x, y, None)  # one stream: x + y
        if idx is not None:
            choices.append(idx)
    return (x, jnp.stack(rows), jnp.stack(states), jnp.stack(tails),
            jnp.stack(choices))


def forward(params: Params, cfg: LinearAttnMoeConfig, tokens, *,
            lengths=None, use_pallas=None):
    """Token ids [B, S] -> (logits [B, S, V] float32, the router's
    choices): the whole model with no cache (tests, offline use)."""
    x, _, _, _, choices = walk_prompt(params, cfg, tokens, lengths,
                                      use_pallas)
    return logits_of(cfg, params, x), choices
