"""Each configuration's largest step programs, compiled at the real size
for a described v5e:2x2 (no chip attached): what the chip's compiler
would refuse, it refuses here, and `memory_analysis()` says whether the
weights, the page pool of the configuration file and the program's
temporaries fit one chip's 15.75 GiB. Slow (a 32-layer program takes
about a minute): `python -m pytest benchmark/tests/test_chip_compile.py -s`.
"""

import json
import os

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BYTES_LIMIT = 15.75 * 2**30
CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "configs")))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _shapes(config, devices):
    """(mcfg, ecfg, params, pool, mesh, arr): the architecture entry's
    ShapeDtypeStructs with their shardings, and a maker of replicated
    arguments."""
    import jax
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from benchmark import architectures
    from benchmark.harness import system

    ecfg = system.engine_config(config)
    mcfg, params, pool, mesh = architectures.load(config).compile_shapes(
        config, ecfg, devices)
    rep = (NamedSharding(mesh, P()) if mesh is not None
           else SingleDeviceSharding(devices[0]))

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    return mcfg, ecfg, params, pool, mesh, arr


@pytest.mark.parametrize("name", CONFIGS)
def test_step_programs_compile_and_fit(topo, name):
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.serving import engine_model

    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as fh:
        config = json.load(fh)
    chips = config["serving"]["chips"]
    mcfg, ecfg, params, pool, mesh, arr = _shapes(
        config, list(topo.devices)[:chips])
    B, ps = ecfg.max_batch_size, ecfg.page_size
    maxp = ecfg.max_seq_len // ps
    key = arr((2,), jnp.uint32)
    greedy = (True, False, False)
    dec = engine_model.decode_multi_step.lower(
        params, mcfg, pool, arr((B,), jnp.int32), arr((B, maxp), jnp.int32),
        arr((B,), jnp.int32), arr((B,), jnp.bool_), arr((B,), jnp.float32),
        arr((B,), jnp.float32), arr((B,), jnp.int32), key,
        ecfg.decode_steps_per_dispatch, True, sampling_flags=greedy,
        mesh=mesh).compile()
    N, S = ecfg.max_prefill_group, max(ecfg.prefill_buckets)
    pre = engine_model.prefill_batch_step.lower(
        params, mcfg, pool, arr((N, S), jnp.int32), arr((N,), jnp.int32),
        arr((N, S // ps), jnp.int32), arr((N,), jnp.float32),
        arr((N,), jnp.float32), arr((N,), jnp.int32), key, True,
        sampling_flags=greedy, mesh=mesh).compile()
    for label, compiled in (("decode", dec), ("prefill", pre)):
        m = compiled.memory_analysis()
        need = m.argument_size_in_bytes + m.temp_size_in_bytes \
            + m.output_size_in_bytes - m.alias_size_in_bytes
        print(f"{name} {label}: args {m.argument_size_in_bytes / 2**30:.2f} "
              f"GiB, temp {m.temp_size_in_bytes / 2**30:.2f} GiB, need "
              f"{need / 2**30:.2f} GiB a chip")
        assert need < BYTES_LIMIT, (name, label, need)
        text = compiled.as_text()
        assert "tpu_custom_call" in text, f"{label}: no Pallas kernel"
        if chips > 1:
            assert "all-reduce" in text
