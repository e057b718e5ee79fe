"""The plain reference: a decoder-only transformer forward pass in
float32 `jax.numpy`, written from the published Mistral/Llama equations
(RMSNorm, rotary embedding on split halves, grouped-query causal
attention, SwiGLU), with no kernel, no cache and no batching. It shares
no code with the program under test; it reads only the parameter tree's
leaves (int8 codes times their per-column scales are the weights).

Run layer by layer, so that only one layer's weights exist in float32 at
a time beside the int8 model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _weight(w) -> jax.Array:
    """A leaf as float32: a plain array, or int8 codes `q` [.., in, out]
    with per-output-column scales `s` [.., out]."""
    if hasattr(w, "q"):
        return w.q.astype(jnp.float32) * w.s.astype(jnp.float32)[..., None, :]
    return w.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, n, Hd]; rotate the two halves of each head (the HF
    `rotate_half` convention the checkpoints are published in)."""
    S, _, Hd = x.shape
    inv = theta ** (-jnp.arange(0, Hd, 2, dtype=jnp.float32) / Hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : Hd // 2], x[..., Hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads",
                                             "head_dim", "theta", "eps"))
def _layer(x, w, *, n_heads, n_kv_heads, head_dim, theta, eps):
    S = x.shape[0]
    h = _rms_norm(x, _weight(w["ln1"]), eps)
    q = (h @ _weight(w["wq"])).reshape(S, n_heads, head_dim)
    k = (h @ _weight(w["wk"])).reshape(S, n_kv_heads, head_dim)
    v = (h @ _weight(w["wv"])).reshape(S, n_kv_heads, head_dim)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(head_dim))
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    x = x + att.reshape(S, n_heads * head_dim) @ _weight(w["wo"])
    h = _rms_norm(x, _weight(w["ln2"]), eps)
    gate = jax.nn.silu(h @ _weight(w["w_gate"])) * (h @ _weight(w["w_up"]))
    return x + gate @ _weight(w["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "tied"))
def _head(x, ln_f, out_w, *, eps, tied):
    w = _weight(out_w)
    return _rms_norm(x, _weight(ln_f), eps) @ (w.T if tied else w)


def logits(params, token_ids, *, n_layers, n_heads, n_kv_heads, head_dim,
           rope_theta, rms_eps, tie_embeddings=False) -> jax.Array:
    """[S] token ids -> [S, vocab] float32 logits."""
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][jnp.asarray(token_ids)].astype(jnp.float32)
        for i in range(n_layers):
            w = jax.tree.map(lambda a: a[i], params["layers"])
            x = _layer(x, w, n_heads=n_heads, n_kv_heads=n_kv_heads,
                       head_dim=head_dim, theta=float(rope_theta),
                       eps=float(rms_eps))
        out_w = params["tok_emb"] if tie_embeddings else params["lm_head"]
        return _head(x, params["ln_f"], out_w, eps=float(rms_eps),
                     tied=tie_embeddings)


def check_greedy(params, prompt_ids, served_ids, dims, rel_tol=0.05):
    """Is each served greedy token the reference's choice, up to
    near-ties? For every position t the served token's reference logit
    must lie within rel_tol * max|logit| of the reference's best, given
    the prompt and the SERVED tokens before t (teacher forcing, one pass:
    causal attention makes position p's logits depend on ids[:p + 1]
    only). Returns (ok, worst shortfall / max|logit|)."""
    ids = list(prompt_ids) + list(served_ids)
    lg = logits(params, ids[:-1], **dims)
    worst = 0.0
    for t, tok in enumerate(served_ids):
        row = lg[len(prompt_ids) - 1 + t]
        scale = float(jnp.max(jnp.abs(row)))
        short = float(jnp.max(row) - row[int(tok)]) / max(scale, 1e-9)
        worst = max(worst, short)
    return worst <= rel_tol, worst
