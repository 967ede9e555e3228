"""``secp256-stream``'s device program compiles for a described v5e at its
real size: the 256-lane ECDSA program (two grid steps of the Straus kernel).
No chip is attached and nothing runs; this guards later PRs against a kernel
the chip's compiler refuses.  The topology is described inside a fixture, as
in test_bench_aot.py (only the worker that runs this file loads the TPU
library; where it cannot, the test is skipped)."""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def test_the_256_lane_ecdsa_program_compiles_for_v5e(one_chip):
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from tendermint_tpu.ops import secp256k1_pallas as k

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    validators = harness.Bench(root).cell("secp256-stream").config["validators"]
    lanes = k._bucket(validators, k.LANES)
    assert lanes == 256 == k._bucket(validators * 85 // 100, k.LANES)  # under_quorum too

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)

    compiled = k._device_verify_secp256k1.lower(
        sds(lanes, 20), sds(lanes, 20), sds(lanes, 64), sds(lanes, 64),
        sds(lanes, 20), sds(lanes, 20), sds(lanes), lanes=k.LANES).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # what the profiler will call the kernel, and the reducers' pattern
    assert "_device_verify_secp256k1" in text
