"""Device-mesh construction: the framework's parallelism substrate.

The reference's entire multi-device story is one env var handed to an
external engine (INFERENCE_GPU_COUNT, deploy/compose/compose.env:17-18 —
NCCL tensor parallelism hidden inside TRT-LLM/NIM). Here parallelism is
owned in-repo and TPU-native: a `jax.sharding.Mesh` over ICI (in-slice)
and DCN (cross-host) axes, with XLA emitting the collectives.

Axes (logical meaning, fastest-varying last so TP rides ICI):

    dcn_pipeline > dcn_data   — cross-host (slow links)
    data > fsdp > expert > sequence > tensor — in-slice (ICI)

`MeshConfig` axis sizes multiply to the device count; one axis may be -1
("fill with whatever devices remain"), mirroring the ergonomics of
jax.numpy reshape.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from generativeaiexamples_tpu.config.schema import MeshConfig

# Canonical axis order: DCN (slowest) first, tensor (fastest / most
# bandwidth-hungry) last so that tensor-parallel collectives map onto
# nearest-neighbour ICI links.
MESH_AXIS_NAMES = ("pipeline", "data", "fsdp", "expert", "sequence", "tensor")


def _resolve_axis_sizes(cfg: MeshConfig, n_devices: int) -> dict:
    if cfg.ici_data == -1 and cfg.dcn_data == -1:
        raise ValueError("only one of ici_data/dcn_data may be -1")
    data_fixed_factor = 1
    if cfg.ici_data == -1 or cfg.dcn_data == -1:
        # The "data" mesh axis is the ici*dcn product; a wildcard in either
        # factor makes the combined axis the wildcard. The fixed factor must
        # still divide the filled size (checked after resolution below).
        data = -1
        data_fixed_factor = cfg.dcn_data if cfg.ici_data == -1 else cfg.ici_data
    else:
        data = cfg.ici_data * cfg.dcn_data
    sizes = {
        "pipeline": cfg.dcn_pipeline,
        "data": data,
        "fsdp": cfg.ici_fsdp,
        "expert": cfg.ici_expert,
        "sequence": cfg.ici_sequence,
        "tensor": cfg.ici_tensor,
    }
    wildcards = [k for k, v in sizes.items() if v == -1]
    if any(v < 1 and v != -1 for v in sizes.values()):
        raise ValueError(f"mesh axis sizes must be >= 1 or -1, got {sizes}")
    if len(wildcards) > 1:
        raise ValueError(f"at most one mesh axis may be -1, got {wildcards}")
    fixed = math.prod(v for v in sizes.values() if v != -1)
    if wildcards:
        if n_devices % fixed:
            raise ValueError(
                f"{n_devices} devices not divisible by fixed axes product "
                f"{fixed} (requested {sizes}); smallest working geometry: "
                f"{_nearest_geometry(sizes, n_devices)} — fixed axes must "
                f"multiply to a divisor of the device count "
                f"({_divisors(n_devices)})"
            )
        sizes[wildcards[0]] = n_devices // fixed
    elif fixed != n_devices:
        raise ValueError(
            f"mesh axes product {fixed} != device count {n_devices} "
            f"(requested {sizes}); smallest working geometry: "
            f"{_nearest_geometry(sizes, n_devices)} — or set one axis "
            f"to -1 to auto-fill"
        )
    if sizes["data"] % data_fixed_factor:
        raise ValueError(
            f"resolved data axis {sizes['data']} not divisible by the fixed "
            f"data factor {data_fixed_factor} (ici_data={cfg.ici_data}, "
            f"dcn_data={cfg.dcn_data}); pick ici_data*dcn_data from the "
            f"device-count divisors {_divisors(n_devices)}"
        )
    return sizes


def _divisors(n: int, cap: int = 12) -> list:
    ds = [d for d in range(1, n + 1) if n % d == 0]
    return ds if len(ds) <= cap else ds[:cap] + ["..."]


def _nearest_geometry(sizes: dict, n_devices: int) -> dict:
    """Smallest-perturbation working geometry for an error hint: keep
    every requested axis clamped to its largest divisor-of-remaining
    value (walking slowest axis first), park leftover devices on
    tensor. Always multiplies to exactly n_devices."""
    out = {}
    rem = n_devices
    for name in MESH_AXIS_NAMES:
        want = sizes.get(name, 1)
        want = 1 if want == -1 else max(1, want)
        got = max(d for d in range(1, min(want, rem) + 1) if rem % d == 0)
        out[name] = got
        rem //= got
    out["tensor"] *= rem  # leftover rides the TP axis (serving default)
    return {k: v for k, v in out.items() if v != 1} or {"tensor": 1}


def build_mesh(cfg: Optional[MeshConfig] = None, devices: Optional[Sequence] = None) -> Mesh:
    """Build the global device mesh from config.

    Works identically on real TPU slices and on the CPU test backend with
    --xla_force_host_platform_device_count=N emulated devices.
    """
    cfg = cfg or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    sizes = _resolve_axis_sizes(cfg, len(devices))
    shape = tuple(sizes[a] for a in MESH_AXIS_NAMES)
    # Topology-aware order (ICI neighbours adjacent on the fastest axes);
    # a failure here is a wrong mesh for the attached slice and raises.
    dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(dev_array, MESH_AXIS_NAMES)


def single_device_mesh(device=None) -> Mesh:
    """Trivial 1-device mesh (all axes size 1) — lets every model fn run
    unmodified on one chip or one CPU device."""
    device = device or jax.devices()[0]
    shape = (1,) * len(MESH_AXIS_NAMES)
    return Mesh(np.asarray([device]).reshape(shape), MESH_AXIS_NAMES)


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


# ---------------------------------------------------------------------------
# Logical sharding rules
# ---------------------------------------------------------------------------
# Model code annotates arrays with *logical* axis names; the rule table maps
# them to mesh axes. Swapping a parallelism layout = swapping the rule table,
# no model changes (the flax "logical partitioning" idiom, done by hand so the
# models stay pure-JAX pytrees).

# Default rules for decoder LLMs (llama family):
#   - embed/activation hidden dim replicated across tensor, sharded for fsdp
#   - attention heads + mlp intermediate sharded on tensor (Megatron layout)
#   - vocab sharded on tensor for the big embed/unembed matmuls
LLM_RULES: dict = {
    "batch": ("data", "fsdp"),
    "seq": "sequence",
    "embed": None,
    "embed_fsdp": "fsdp",  # weight hidden-dim axis: FSDP shards here
    "heads": "tensor",
    "kv_heads": "tensor",
    "head_dim": None,
    "mlp": "tensor",
    "vocab": "tensor",
    "expert": "expert",
    "layers": None,  # stacked-layer leading axis (scanned) — never sharded
    "kv_pages": None,
}


def logical_to_spec(logical_axes: Sequence[Optional[str]], rules: dict = LLM_RULES) -> PartitionSpec:
    """("batch","seq","embed") -> PartitionSpec(("data","fsdp"),"sequence",None)."""
    out = []
    for ax in logical_axes:
        if ax is None:
            out.append(None)
        else:
            if ax not in rules:
                raise KeyError(f"unknown logical axis {ax!r}")
            out.append(rules[ax])
    return PartitionSpec(*out)


def named_sharding(mesh: Mesh, *logical_axes, rules: dict = LLM_RULES) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, rules))


def spec_tree_to_shardings(mesh: Mesh, spec_tree):
    """Map a pytree of PartitionSpec -> pytree of NamedSharding."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def shard_pytree(tree, spec_tree, mesh: Mesh):
    """Place a host pytree onto the mesh with the given PartitionSpecs."""
    shardings = spec_tree_to_shardings(mesh, spec_tree)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shardings)


def is_multihost() -> bool:
    return jax.process_count() > 1


def devices_colocated(a, b) -> bool:
    """Are every device in `a` and `b` addressable from THIS process —
    i.e. can jax.device_put move arrays between them without
    serialization (one host driving one slice, chip-to-chip over ICI)?
    This is the gate for the disagg device-path KV transfer
    (serving/disagg.py KVPageTransfer.device_ok): on CPU both engine
    pools live on the same local device, on a single-host TPU slice
    the replicas' chips share the ICI domain. Empty sets are NOT
    colocated — an engine with no live arrays has no path."""
    a, b = set(a), set(b)
    if not a or not b:
        return False
    local = set(jax.local_devices())
    return a <= local and b <= local


def dcn_transfer_available() -> bool:
    """Is the cross-host (DCN) device-path leg available — multi-host
    jax.distributed initialized, so a collective program over the
    `pipeline`/`data` DCN axes could move pages between hosts without
    the host bounce? Today this only REPORTS the condition: the
    transfer itself still takes the `/v1/kv/export` wire between
    process-separated replicas (each process owns a distinct engine;
    a cross-process collective needs a shared global program both
    sides enter, which the serving loop does not yet schedule). The
    gate exists so KVPageTransfer and the docs state the boundary
    honestly instead of implying ICI semantics across DCN."""
    return is_multihost()


def maybe_initialize_distributed(cfg: Optional[MeshConfig] = None) -> None:
    """Multi-host init (DCN): no-op unless a coordinator is named — by
    the JAX_COORDINATOR_ADDRESS env (which wins, matching how launchers
    template per-host env) or by `cfg.coordinator_address` /
    `cfg.num_processes` / `cfg.process_id` (the --coordinator /
    --num-processes / --process-id serve flags). On pods this wires
    jax.distributed so device lists span hosts (reference analog: none —
    NIM hides it; SURVEY.md §5.8). Failures propagate: a silently
    uncoordinated host would compute wrong collectives, which is
    strictly worse than crashing at startup."""
    import os

    # Resolve BEFORE touching any jax API: process_count() would
    # initialize the local backend, after which distributed.initialize()
    # unconditionally raises ("must be called before any JAX calls").
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS", "")
    n_str = os.environ.get("JAX_NUM_PROCESSES", "")
    p_str = os.environ.get("JAX_PROCESS_ID", "")
    n_proc = int(n_str) if n_str else 0
    proc_id = int(p_str) if p_str else -1
    if cfg is not None:
        coord = coord or cfg.coordinator_address
        n_proc = n_proc or cfg.num_processes
        proc_id = proc_id if proc_id >= 0 else cfg.process_id
    if not coord:
        return
    from jax._src import distributed as _dist

    if _dist.global_state.client is not None:  # already initialized
        return
    kwargs: dict = {"coordinator_address": coord}
    # Leave either unset and jax auto-detects from the cluster env
    # (TPU pod metadata, SLURM, ...); explicit values serve the
    # CPU-simulation path where there is nothing to detect.
    if n_proc > 0:
        kwargs["num_processes"] = n_proc
    if proc_id >= 0:
        kwargs["process_id"] = proc_id
    jax.distributed.initialize(**kwargs)
