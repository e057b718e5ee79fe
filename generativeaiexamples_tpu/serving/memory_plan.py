"""Memory-budget planner: size the paged-KV pool instead of guessing.

The reference sizes nothing — NIM/TRT-LLM pre-profiles engine memory
internally and the compose file just picks a GPU count
(INFERENCE_GPU_COUNT, deploy/compose/compose.env:17-18). Here the
accounting is owned in-repo: given a model config, weight dtype, mesh
geometry, page size, and per-device HBM, `plan_engine_memory` emits a
per-host/per-device breakdown (sharded weights + paged KV pool + scratch
caches + warmup transients + headroom) and the max page count that fits.

With `engine.auto_pool_pages=true` the engine sizes `PagePool` from the
plan; a plan that can't hold even one max-length sequence fails fast at
build with the breakdown and the smallest mesh that would fit (the Pope
et al. "Efficiently Scaling Transformer Inference" sizing discipline,
adapted to paged KV).

Accounting is analytic over `llama.param_specs` — per-device shard bytes
are computed from PartitionSpecs and mesh axis sizes without needing the
devices to exist, so a 70B-on-64-chips plan can be built (and rejected)
from a laptop. Weight and pool lines are exact; scratch/transient lines
are documented estimates (XLA owns those buffers).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import hyper_connections
from generativeaiexamples_tpu.models.llama import LlamaConfig
from generativeaiexamples_tpu.serving.served_models import served

GiB = float(1 << 30)

# CPU/test backend has no real HBM limit; pick a budget big enough that
# default test engines plan without failing, small enough that 70B
# geometries exercise the fail-fast path.
_CPU_DEFAULT_HBM = 4 << 30


class MemoryPlanError(RuntimeError):
    """Raised at engine build when the plan cannot fit. Carries the full
    per-host breakdown so the operator sees *what* doesn't fit, plus the
    smallest mesh geometry that would."""

    def __init__(self, msg: str, plan: Optional["MemoryPlan"] = None):
        super().__init__(msg)
        self.plan = plan


@dataclass(frozen=True)
class PlanLine:
    name: str
    bytes_per_device: int
    exact: bool  # analytic-exact vs documented estimate
    note: str = ""


@dataclass(frozen=True)
class MemoryPlan:
    """Per-device memory accounting for one engine build."""

    lines: Tuple[PlanLine, ...]  # fixed costs (everything but the pool)
    hbm_bytes_per_device: int
    headroom_bytes: int  # per device, refused to the allocator
    page_bytes_per_device: int  # ONE page's per-device footprint
    fit_pages: int  # max pool pages that fit the budget
    pool_pages: int  # pages the engine will actually allocate
    default_pages: int  # legacy worst-case sizing (for reference)
    axis_sizes: Dict[str, int] = field(default_factory=dict)
    devices_per_host: int = 1
    n_processes: int = 1
    # KV pager host tier (host RAM, not HBM; zeros when kv_pager off).
    # The budget is PER-HOST: under a cross-process mesh each rank's
    # host/disk tiers park only its addressable shard slice of a page
    # (kv_pager slice mode), so a host's cold record is the per-device
    # page footprint times its local device count — N hosts together
    # hold one full copy, and the fleet's total cold capacity scales
    # with the host count at constant per-host RAM.
    pager_host_budget_mb: int = 0
    pager_rec_bytes_per_host: int = 0
    pager_host_slots: int = 0

    @property
    def fixed_bytes_per_device(self) -> int:
        return sum(l.bytes_per_device for l in self.lines)

    @property
    def pool_bytes_per_device(self) -> int:
        return self.pool_pages * self.page_bytes_per_device

    @property
    def total_bytes_per_device(self) -> int:
        return self.fixed_bytes_per_device + self.pool_bytes_per_device

    @property
    def free_bytes_per_device(self) -> int:
        return (self.hbm_bytes_per_device - self.headroom_bytes
                - self.total_bytes_per_device)

    def per_host(self, bytes_per_device: int) -> int:
        return bytes_per_device * self.devices_per_host

    def breakdown(self) -> str:
        tp = self.axis_sizes.get("tensor", 1)
        hdr = (f"memory plan (per device; {self.devices_per_host} dev/host"
               f" x {self.n_processes} host(s); tensor={tp})")
        rows = [(f"hbm", self.hbm_bytes_per_device, ""),
                (f"headroom", self.headroom_bytes, "reserved")]
        for l in self.lines:
            tag = "exact" if l.exact else "estimate"
            note = f"{tag}{', ' + l.note if l.note else ''}"
            rows.append((l.name, l.bytes_per_device, note))
        rows.append(("kv_pool", self.pool_bytes_per_device,
                     f"{self.pool_pages} pages x "
                     f"{self.page_bytes_per_device / (1 << 20):.2f} MiB "
                     f"(fit={self.fit_pages}, legacy={self.default_pages})"))
        rows.append(("free", self.free_bytes_per_device, ""))
        w = max(len(n) for n, _, _ in rows)
        body = "\n".join(
            f"  {n:<{w}}  {b / GiB:9.3f} GiB"
            f"  ({b * self.devices_per_host / GiB:.3f} GiB/host)"
            + (f"  [{note}]" if note else "")
            for n, b, note in rows)
        out = hdr + "\n" + body
        if self.pager_host_budget_mb > 0:
            out += (
                f"\n  kv pager host tier (host RAM, per host): "
                f"{self.pager_host_budget_mb} MiB budget -> "
                f"{self.pager_host_slots} page slots x "
                f"{self.pager_rec_bytes_per_host / (1 << 20):.2f} MiB "
                f"local slice")
        return out


# ---------------------------------------------------------------------------
# Analytic shard accounting
# ---------------------------------------------------------------------------


def _axis_factor(entry, axis_sizes: Dict[str, int]) -> int:
    if entry is None:
        return 1
    names = entry if isinstance(entry, (tuple, list)) else (entry,)
    f = 1
    for n in names:
        f *= int(axis_sizes.get(n, 1))
    return f


def _shard_numel(shape, spec, axis_sizes: Dict[str, int]) -> int:
    """Per-device element count of `shape` sharded by PartitionSpec
    `spec` on a mesh with the given axis sizes (ceil-division so
    non-dividing dims over-count rather than under-count)."""
    entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    n = 1
    for dim, entry in zip(shape, entries):
        n *= math.ceil(dim / _axis_factor(entry, axis_sizes))
    return n


def weight_bytes_per_device(lcfg: LlamaConfig, axis_sizes: Dict[str, int],
                            quantize: bool = False) -> int:
    """Exact per-device bytes of the (possibly int8) sharded param tree.

    Shapes come from `jax.eval_shape` of the entry's initializer; specs
    from its `param_specs`; int8 leaves count q (int8, full spec) + s
    (float32, spec minus the contracted axis) exactly as
    `serving.sharding._quantized_leaf_spec` places them.
    """
    from generativeaiexamples_tpu.ops.quant import LLAMA_QUANT_KEYS

    entry = served(lcfg)
    if entry.param_specs is None:
        # whole on one chip (a share of the experts is the
        # configuration's, not a mesh axis's)
        if any(int(n) > 1 for n in axis_sizes.values()):
            raise MemoryPlanError(
                f"a model with {entry.name} has no tensor-parallel layout: "
                f"mesh axes {axis_sizes}")
        shapes = jax.eval_shape(lambda: entry.init_params(lcfg, quantize))
        return sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(shapes))
    shapes = jax.eval_shape(lambda: entry.init_params(lcfg, False))
    specs = entry.param_specs(lcfg)
    wsize = jnp.dtype(lcfg.dtype).itemsize

    def leaf(shape_sd, spec, quantized: bool) -> int:
        shape = shape_sd.shape
        if not quantized:
            return _shard_numel(shape, spec, axis_sizes) * wsize
        q = _shard_numel(shape, spec, axis_sizes)  # int8 payload
        s_shape = shape[:-2] + shape[-1:]
        sp = tuple(spec)
        s_spec = sp[:-2] + (sp[-1],) if len(sp) >= 2 else sp
        s = _shard_numel(s_shape, s_spec, axis_sizes)  # f32 scales
        return q + 4 * s

    total = 0
    for name, sd in shapes.items():
        if name == "layers":
            for k, lsd in sd.items():
                total += leaf(lsd, specs["layers"][k],
                              quantize and k in LLAMA_QUANT_KEYS)
        else:
            total += leaf(sd, specs[name], quantize and name == "lm_head")
    return total


def pool_page_bytes_per_device(lcfg: LlamaConfig, ecfg: EngineConfig,
                               axis_sizes: Dict[str, int]) -> int:
    """Exact per-device bytes of ONE page of the pool that grows with a
    sequence: what a cached token takes in each pool under its page
    table, as the architecture's entry counts it (`token_bytes`)."""
    return ecfg.page_size * sum(
        served(lcfg).token_bytes(lcfg, ecfg, axis_sizes).values())


def _scratch_lines(lcfg: LlamaConfig, ecfg: EngineConfig,
                   axis_sizes: Dict[str, int]) -> Tuple[PlanLine, ...]:
    tp = int(axis_sizes.get("tensor", 1))
    wsize = jnp.dtype(lcfg.dtype).itemsize
    # One in-flight long prefill holds a full-length contiguous scratch
    # KVCache [L, 1, KH, max_seq_len, Hd] x (k, v) on device
    # (engine._max_long_prefills = 1); counted unsharded — GSPMD may
    # shard it, so this over-counts, never under.
    if not served(lcfg).long_prompts:
        long_pf = 0  # no long-prompt scratch: the engine refuses the lane
    else:
        long_pf = (2 * lcfg.cache_rows * lcfg.n_kv_heads
                   * ecfg.max_seq_len * lcfg.head_dim * wsize)
    # Warmup/steady-state activation transients: the widest prefill
    # dispatch runs N sequences x the largest bucket through the stack.
    # XLA reuses buffers; ~4 hidden-width + 2 mlp-width live copies is
    # the documented estimate, plus the f32 last-token logits
    # [N, vocab/tp].
    group = ecfg.max_prefill_group or ecfg.max_batch_size
    n_seq = max(1, min(group, ecfg.max_batch_size))
    bucket = max(ecfg.prefill_buckets) if ecfg.prefill_buckets else 128
    tokens = n_seq * bucket
    # (a state-space model's widest activation is its mixer's input
    # projection, which plays the feed-forward's part here)
    mlp = math.ceil(getattr(lcfg, "mlp_dim", 0) / tp) \
        or 2 * getattr(lcfg, "d_inner", 0) \
        or 2 * lcfg.moe_mlp_dim * lcfg.n_experts_per_tok
    # (hc_mult residual streams: every hidden-width copy is so many wide)
    streams = hyper_connections.streams(lcfg)
    acts = tokens * (4 * streams * lcfg.dim + 2 * mlp) * wsize
    logits = n_seq * math.ceil(lcfg.vocab_size / tp) * 4
    return (
        PlanLine("long_prefill_scratch", long_pf, False,
                 "1 full-length KVCache, counted unsharded"),
        PlanLine("activation_transients", acts + logits, False,
                 f"{n_seq} seq x {bucket}-token bucket"
                 + (f", {streams} residual streams wide" if streams > 1
                    else "")),
    )


# ---------------------------------------------------------------------------
# Budget probing + the plan itself
# ---------------------------------------------------------------------------


def device_hbm_bytes(ecfg: EngineConfig) -> int:
    """Per-device HBM budget: config override, else what the TPU reports
    (memory_stats()["bytes_limit"]; a TPU that reports none is an error,
    never a guess), else the CPU-backend default."""
    if ecfg.hbm_gb_per_device > 0:
        return int(ecfg.hbm_gb_per_device * GiB)
    dev = jax.local_devices()[0]
    if dev.platform != "tpu":
        return _CPU_DEFAULT_HBM
    stats = dev.memory_stats() or {}
    if not stats.get("bytes_limit"):
        raise MemoryPlanError(
            f"{dev.device_kind} reports no memory_stats()['bytes_limit'] "
            f"(got {sorted(stats)}); set engine.hbm_gb_per_device")
    return int(stats["bytes_limit"])


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    if mesh is None:
        return {}
    return {k: int(v) for k, v in dict(mesh.shape).items()}


def plan_engine_memory(
    lcfg: LlamaConfig,
    ecfg: EngineConfig,
    mesh=None,
    *,
    axis_sizes: Optional[Dict[str, int]] = None,
    n_processes: int = 1,
    devices_per_host: Optional[int] = None,
    hbm_bytes_per_device: Optional[int] = None,
    strict: bool = True,
) -> MemoryPlan:
    """Build the per-device memory plan for one engine.

    Pass a live `mesh` (geometry is read off it) or explicit
    `axis_sizes` for a dryrun of hardware that isn't attached. With
    `strict`, a plan that can't hold even one max-length sequence of KV
    raises MemoryPlanError carrying the breakdown and the smallest mesh
    that would fit.
    """
    sizes = dict(axis_sizes) if axis_sizes is not None else mesh_axis_sizes(mesh)
    if devices_per_host is None:
        n_dev = int(math.prod(sizes.values())) if sizes else 1
        devices_per_host = max(1, n_dev // max(1, n_processes))
    hbm = (hbm_bytes_per_device if hbm_bytes_per_device is not None
           else device_hbm_bytes(ecfg))
    headroom = int(hbm * max(0.0, ecfg.planner_headroom_fraction))

    quantize = ecfg.quantize_weights == "int8"
    lines = (PlanLine("weights", weight_bytes_per_device(
        lcfg, sizes, quantize=quantize), True,
        "int8 + f32 scales" if quantize else str(lcfg.dtype)),
    ) + _scratch_lines(lcfg, ecfg, sizes)
    # the architecture's pools of fixed size beside the pages
    lines += tuple(PlanLine(name, n_bytes, False, note) for name, n_bytes, note
                   in served(lcfg).fixed_pools(lcfg, ecfg))

    page = pool_page_bytes_per_device(lcfg, ecfg, sizes)
    fixed = sum(l.bytes_per_device for l in lines)
    budget = hbm - headroom - fixed
    fit_pages = max(0, budget // page)

    max_pages = ecfg.max_seq_len // ecfg.page_size
    slack = max_pages if jnp.dtype(ecfg.kv_dtype) == jnp.int8 else 0
    default_pages = ecfg.max_batch_size * max_pages + slack + 1
    # With a prefix cache every spare page is useful (more reuse before
    # eviction); otherwise cap at the legacy worst case — identical
    # behavior when it fits, graceful shrink when it doesn't.
    pool_pages = fit_pages if ecfg.prefix_cache else min(fit_pages,
                                                         default_pages)

    # KV pager host-tier accounting (host RAM): one cold record per
    # host is that host's slice of a page — per-device page bytes x
    # local devices (exact for the slice mode kv_pager arms under
    # cross-process meshes; equals the full page on one host).
    pager_budget = int(ecfg.kv_host_budget_mb) if ecfg.kv_pager else 0
    pager_rec = page * devices_per_host
    pager_slots = ((pager_budget << 20) // pager_rec
                   if pager_budget > 0 else 0)

    plan = MemoryPlan(
        lines=lines, hbm_bytes_per_device=hbm, headroom_bytes=headroom,
        page_bytes_per_device=page, fit_pages=int(fit_pages),
        pool_pages=int(pool_pages), default_pages=default_pages,
        axis_sizes=sizes, devices_per_host=devices_per_host,
        n_processes=max(1, n_processes),
        pager_host_budget_mb=pager_budget,
        pager_rec_bytes_per_host=int(pager_rec),
        pager_host_slots=int(pager_slots))
    if strict and fit_pages < max_pages + 1:
        smaller = smallest_fitting_mesh(lcfg, ecfg, hbm)
        hint = (f"smallest mesh that fits: ici_tensor="
                f"{smaller['tensor']} ({smaller['tensor']} device(s))"
                if smaller else
                "no tensor-parallel geometry fits this HBM budget; "
                "raise engine.hbm_gb_per_device or shrink the model")
        raise MemoryPlanError(
            f"memory plan does not fit: {fit_pages} pages available but "
            f"one max-length sequence needs {max_pages + 1} "
            f"(max_seq_len={ecfg.max_seq_len}, page_size={ecfg.page_size})."
            f"\n{plan.breakdown()}\n{hint}", plan)
    return plan


def smallest_fitting_mesh(lcfg: LlamaConfig, ecfg: EngineConfig,
                          hbm_bytes_per_device: int,
                          max_tensor: int = 1024) -> Optional[Dict[str, int]]:
    """Smallest tensor-parallel degree whose plan fits the HBM budget.

    Walks the divisors of gcd(heads, kv_heads, mlp, vocab) — the sizes
    `sharding.validate_tp` would accept — in increasing order and
    returns the first geometry that holds at least one max-length
    sequence, or None."""
    if served(lcfg).param_specs is None:
        return None  # whole on one chip: weight_bytes_per_device
    g = math.gcd(math.gcd(lcfg.n_heads, lcfg.n_kv_heads),
                 math.gcd(lcfg.mlp_dim, lcfg.vocab_size))
    max_pages = ecfg.max_seq_len // ecfg.page_size
    for t in range(1, min(g, max_tensor) + 1):
        if g % t:
            continue
        plan = plan_engine_memory(
            lcfg, ecfg, axis_sizes={"tensor": t},
            hbm_bytes_per_device=hbm_bytes_per_device, strict=False)
        if plan.fit_pages >= max_pages + 1:
            return {"tensor": t}
    return None
