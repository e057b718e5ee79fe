"""What the serving side asks of an architecture: one record a config class.

engine_model's step programs, engine's scheduler, memory_plan and
`kv_cache.PagePool.zeros` know no architecture by name: each takes
`served(cfg)`, the `ServedModel` registered for `type(cfg)`. An entry
lives beside its bodies in one module of this package (`_ENTRY_MODULES`:
the one line a new one adds; docs/architecture.md, "Adding an
architecture"), which imports models/, kv_cache and the kernels; nothing
imports an entry's module but `served`, when first asked.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from generativeaiexamples_tpu.serving.kv_cache import PagePool

_ENTRY_MODULES = tuple(
    f"generativeaiexamples_tpu.serving.{name}" for name in (
        "engine_model",  # the Llama entry, beside the walk that is its bodies
        "served_latent", "served_hybrid", "served_sparse", "served_window",
        "served_linear", "served_gated_window"))


# A lane an architecture has no form for, beyond engine._ONE_PASS_LANES:
# (whether `(ecfg, mesh)` turns it on, what the refusal calls it, with
# `{kv_dtype}` for the configured pool type, what it would run).


def mesh_lane(what: str):
    return (lambda ecfg, mesh: mesh is not None, "mesh", what)


def kv_dtype_lane(int8: bool, what: str):  # int8, or every type but int8
    return (lambda ecfg, mesh: (jnp.dtype(ecfg.kv_dtype) == jnp.int8) == int8,
            "kv_dtype {kv_dtype}", what)


MULTIHOST = (lambda ecfg, mesh: ecfg.multihost, "multihost",
             "the multi-host replay")
PREEMPT_PREFILL = (lambda ecfg, mesh: ecfg.qos and ecfg.qos_preempt_prefill,
                   "qos_preempt_prefill",
                   "pausing and resuming a sequence's prefill")


def paged_pool(cfg, ecfg, n_pages: int, sharding=None, scale_sharding=None):
    """The engine's pool for a model with one pool of pages."""
    return PagePool.zeros(cfg, n_pages, ecfg.page_size,
                          dtype=jnp.dtype(ecfg.kv_dtype), sharding=sharding,
                          scale_sharding=scale_sharding,
                          slots=ecfg.max_batch_size)


@dataclasses.dataclass(frozen=True)
class ServedModel:
    """One architecture as the serving side sees it: plain values and
    functions (docs/architecture.md has the table of who reads which)."""

    name: str
    # (params, cfg, pool, tokens [N, S], lengths [N], tables, use_pallas, *,
    #  mesh, state_slots) -> (last-position logits [N, V], pool)
    prefill: Callable
    # (params, cfg, pool, tokens [B], tables, lengths [B], use_pallas, mask,
    #  *, mesh, n_steps: those of the program it is traced into) -> (logits
    #  [B, V], pool, pairs each held expert took | None, choices | None)
    decode_once: Callable
    zeros: Callable  # PagePool.zeros' arguments -> the pool, with its errors
    # (cfg, ecfg, n_pages, sharding, scale_sharding) -> the engine's pool
    new_pool: Callable = paged_pool
    # (cfg, ecfg) -> (pages of a second pool with an allocator and a page
    # table of its own, the table's width); None: one pool
    second_pool: Optional[Callable] = None
    # the pool -> the part metrics.kv_bytes_per_token is measured on
    kv_pages: Callable = lambda pool: pool
    # The memory plan. (cfg, quantize) -> parameters, for eval_shape; cfg ->
    # their PartitionSpecs (None: whole on one chip); (cfg, ecfg, axis
    # sizes) -> {pool under the sequence's page table: a cached token's
    # bytes}; (cfg, ecfg) -> ((plan line, bytes, note), ...): fixed pools.
    init_params: Optional[Callable] = None
    param_specs: Optional[Callable] = None
    token_bytes: Optional[Callable] = None
    fixed_pools: Callable = lambda cfg, ecfg: ()
    # The refusal. cfg -> its opening, what the model caches (None: every
    # lane has a form for this configuration); the lanes; its closing.
    caches: Callable = lambda cfg: None
    lanes: Tuple[tuple, ...] = ()
    why_not: str = ""
    long_prompts: bool = False       # takes the chunked long-prompt lane
    live_prefill_rows: bool = False  # a lone prompt runs its live rows only
    direct_qkv: bool = False         # a step takes engine_model.direct_qkv
    state_slots: bool = False        # a prefill takes the rows' decode slots
    # EngineMetrics' summable counters and descriptive gauges that are this
    # architecture's (0 for every other), and what moves them: (metrics,
    # cfg, ecfg, pool, n_pages) at engine build, with one log line;
    # (metrics, cfg, lengths [B], active_mask [B], K, pool, use_pallas,
    # max_pages) a dispatched decode block -> the flight event (code, a, b)
    # to record when it lands, or None; (metrics, cfg, n prompts, their
    # real tokens) a prefill.
    counters: Tuple[str, ...] = ()
    gauges: Tuple[str, ...] = ()
    describe: Callable = lambda *args: None
    note_decode: Callable = lambda *args: None
    note_prefill: Callable = lambda *args: None


_ENTRIES: Dict[type, ServedModel] = {}


def register(config_class: type, entry: Optional[ServedModel]) -> None:
    """Serve `config_class`'s configurations through `entry` (None: stop)."""
    if entry is None:
        _ENTRIES.pop(config_class, None)
    else:
        _ENTRIES[config_class] = entry


@functools.lru_cache(maxsize=None)
def _load() -> None:
    for module in _ENTRY_MODULES:  # each registers its entry
        importlib.import_module(module)


def served(cfg) -> ServedModel:
    """The entry of `cfg`'s architecture, by its config class."""
    _load()
    try:
        return _ENTRIES[type(cfg)]
    except KeyError:
        raise TypeError(
            f"{type(cfg).__name__} is no served architecture: register an "
            f"entry for it (serving/served_models.py)") from None


def metric_keys() -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """(counters, gauges) the registered architectures add to EngineMetrics."""
    _load()
    entries = list(_ENTRIES.values())  # an entry may stand under two classes
    return (tuple(dict.fromkeys(k for e in entries for k in e.counters)),
            tuple(dict.fromkeys(k for e in entries for k in e.gauges)))
