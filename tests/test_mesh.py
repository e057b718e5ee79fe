"""Mesh construction + sharding rules on the 8-device emulated backend."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from generativeaiexamples_tpu.config.schema import MeshConfig
from generativeaiexamples_tpu.parallel import mesh as mesh_lib


def test_default_mesh_fills_tensor_axis(eight_devices):
    m = mesh_lib.build_mesh(MeshConfig())
    assert m.shape["tensor"] == 8
    assert m.shape["data"] == 1


def test_mixed_axes(eight_devices):
    m = mesh_lib.build_mesh(MeshConfig(ici_data=2, ici_tensor=4))
    assert m.shape["data"] == 2 and m.shape["tensor"] == 4


def test_bad_product_raises(eight_devices):
    with pytest.raises(ValueError):
        mesh_lib.build_mesh(MeshConfig(ici_data=3, ici_tensor=5))
    with pytest.raises(ValueError):
        mesh_lib.build_mesh(MeshConfig(ici_data=-1, ici_tensor=-1))


def test_wildcard_double_raises(eight_devices):
    # Both halves of the combined data axis wild: unresolvable.
    with pytest.raises(ValueError, match="only one of ici_data/dcn_data"):
        mesh_lib.build_mesh(MeshConfig(ici_data=-1, dcn_data=-1))
    # Two wildcards on DIFFERENT axes (tensor defaults to -1).
    with pytest.raises(ValueError, match="at most one"):
        mesh_lib.build_mesh(MeshConfig(ici_fsdp=-1))


def test_axis_size_zero_raises(eight_devices):
    with pytest.raises(ValueError, match=">= 1 or -1"):
        mesh_lib.build_mesh(MeshConfig(ici_tensor=0))
    with pytest.raises(ValueError, match=">= 1 or -1"):
        mesh_lib.build_mesh(MeshConfig(ici_data=-2, ici_tensor=1))


def test_wildcard_nondividing_fixed_factor(eight_devices):
    # Wildcard present but the fixed axes' product (3) does not divide
    # the device count: the error must hand back a geometry that works.
    with pytest.raises(ValueError, match="smallest working geometry"):
        mesh_lib.build_mesh(MeshConfig(ici_data=3, ici_tensor=-1))
    # No wildcard, wrong product: same contract.
    with pytest.raises(ValueError, match="smallest working geometry"):
        mesh_lib.build_mesh(MeshConfig(ici_data=3, ici_tensor=5))


def test_dcn_wildcard_fixed_factor(eight_devices):
    # dcn_data wild + fixed ici_data: combined data axis fills to 8 but
    # must stay divisible by the fixed ici factor.
    m = mesh_lib.build_mesh(MeshConfig(ici_data=2, dcn_data=-1,
                                       ici_tensor=2))
    assert m.shape["data"] == 4 and m.shape["tensor"] == 2
    with pytest.raises(ValueError, match="data factor"):
        mesh_lib.build_mesh(MeshConfig(ici_data=3, dcn_data=-1,
                                       ici_tensor=1))


def test_nearest_geometry_hint_content(eight_devices):
    # The named geometry must itself build: extract it and rebuild.
    sizes = {"pipeline": 1, "data": 3, "fsdp": 1, "expert": 1,
             "sequence": 1, "tensor": 5}
    hint = mesh_lib._nearest_geometry(sizes, 8)
    import math

    assert math.prod(hint.values()) == 8
    assert hint == {"data": 2, "tensor": 4}


def test_validate_tp_names_working_geometry(eight_devices):
    from generativeaiexamples_tpu.models.llama import LlamaConfig
    from generativeaiexamples_tpu.serving import sharding as shd

    # heads gcd-chain = 3: no tensor axis > 1 fits 8 devices, so the
    # error must point at ici_tensor=1 with the remainder on data.
    lcfg = LlamaConfig(vocab_size=24, dim=12, n_layers=1, n_heads=6,
                       n_kv_heads=3, head_dim=2, mlp_dim=12)
    m = mesh_lib.build_mesh(MeshConfig(ici_tensor=4, ici_data=2))
    with pytest.raises(ValueError, match=r"ici_tensor=1, ici_data=8"):
        shd.validate_tp(lcfg, m)


def test_logical_to_spec():
    spec = mesh_lib.logical_to_spec(("batch", "seq", "heads", None))
    assert spec == P(("data", "fsdp"), "sequence", "tensor", None)


def test_shard_pytree_places_on_mesh(eight_devices):
    m = mesh_lib.build_mesh(MeshConfig())
    x = np.ones((16, 32), np.float32)
    spec = mesh_lib.logical_to_spec(("heads", None))
    (sharded,) = jax.tree.leaves(mesh_lib.shard_pytree([x], [spec], m))
    assert sharded.sharding.spec == spec
    # 8-way sharded on dim 0: each shard holds 2 rows
    assert sharded.addressable_shards[0].data.shape == (2, 32)


def test_matmul_with_psum_over_tensor(eight_devices):
    """A hand-rolled TP matmul: contract over the sharded dim with psum."""
    m = mesh_lib.build_mesh(MeshConfig())
    x = np.random.default_rng(0).normal(size=(4, 16)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(16, 8)).astype(np.float32)

    def local(x, w):
        return jax.lax.psum(x @ w, "tensor")

    fn = jax.shard_map(
        local, mesh=m, in_specs=(P(None, "tensor"), P("tensor", None)),
        out_specs=P(),
    )
    np.testing.assert_allclose(fn(x, w), x @ w, rtol=1e-5)
