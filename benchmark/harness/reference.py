"""The comparison that decides the reference check. The plain reference
itself (a float32 forward pass that shares no code with the program) is
the configuration's architecture entry's:
benchmark/architectures/<name>.py::reference_logits.
"""

from __future__ import annotations

import jax.numpy as jnp


def check_greedy(logits, prompt_ids, served_ids, rel_tol=0.05):
    """Is each served greedy token the reference's choice, up to
    near-ties? `logits` is the entry's plain reference with its
    configuration and parameters bound: [S] token ids -> [S, vocab]
    float32. For every position t the served token's reference logit
    must lie within rel_tol * max|logit| of the reference's best, given
    the prompt and the SERVED tokens before t (teacher forcing, one pass:
    causal attention makes position p's logits depend on ids[:p + 1]
    only). Returns (ok, worst shortfall / max|logit|)."""
    ids = list(prompt_ids) + list(served_ids)
    lg = logits(ids[:-1])
    worst = 0.0
    for t, tok in enumerate(served_ids):
        row = lg[len(prompt_ids) - 1 + t]
        scale = float(jnp.max(jnp.abs(row)))
        short = float(jnp.max(row) - row[int(tok)]) / max(scale, 1e-9)
        worst = max(worst, short)
    return worst <= rel_tol, worst
