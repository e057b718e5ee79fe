"""The BERT encoder behind /v1/embeddings and /v1/ranking: a geometry of
the program's `BertConfig` by name, seeded weights, and the engine the
harness names."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict


def build(spec: Dict[str, Any], engine_cls, key: int):
    """`spec`: `geometry` (a constructor of `BertConfig`), `dtype`,
    `overrides` of its fields, `engine` keywords."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness.bench_tokenizer import WordTokenizer
    from generativeaiexamples_tpu.models import bert

    bcfg = dataclasses.replace(
        getattr(bert.BertConfig, spec["geometry"])(),
        dtype=jnp.dtype(spec.get("dtype", "bfloat16")),
        **spec.get("overrides", {}))
    return engine_cls(
        bert.init_params(bcfg, jax.random.PRNGKey(key)), bcfg,
        WordTokenizer(bcfg.vocab_size), **spec.get("engine", {}))
