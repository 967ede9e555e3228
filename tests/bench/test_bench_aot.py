"""The cells' device programs compile for a described v5e at their real
sizes: the 10,240-lane commit program and the 8,192-lane sync window.  No
chip is attached and nothing runs; this guards later PRs against a kernel
the chip's compiler refuses.  The topology is described inside a fixture
(only the worker that runs this file loads the TPU library)."""

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT executable for a described chip cannot be read back from the
    # persistent cache; keep the cache out of it, and quiet
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _varying_rows(cases_msgs):
    """k_pad as ``pack_variable_words`` computes it for these messages."""
    from tendermint_tpu.ops import ed25519_pallas as k

    n = len(cases_msgs)
    ln = len(cases_msgs[0])
    pubs = np.zeros((n, 32), np.uint8)
    sigs = np.zeros((n, 64), np.uint8)
    _tmpl, vrows, _vwords = k.pack_variable_words(pubs, cases_msgs, sigs, ln, 128)
    tmpl_rows = ((64 + ln + 1 + 16 + 127) // 128) * 32
    return int(vrows.size), tmpl_rows


@pytest.mark.parametrize("cell,lanes", [
    ("commit10k-stream", 10240), ("sync64-empty", 8192)])
def test_cell_program_compiles_for_v5e(one_chip, cell, lanes):
    import jax
    import jax.numpy as jnp

    from benchmark import chaingen
    from tendermint_tpu.ops import ed25519_pallas as k

    if cell == "commit10k-stream":
        cfg = {"validators": 12, "voting_power": 10, "chain_id": "bench-commit"}
        ring = chaingen.make_commit_ring(cfg, {"ring": 1, "first_height": 500}, 1)
        msgs = ring[0].lanes.msgs
        assert k._bucket(10000) == lanes
    else:
        cfg = {"validators": 4, "voting_power": 10, "chain_id": "bench-sync"}
        ring = chaingen.make_commit_ring(cfg, {"ring": 3, "first_height": 1}, 1)
        msgs = [m for c in ring for m in c.lanes.msgs]  # heights and ids vary
        assert k._bucket(127 * 64) == lanes
    kpad, rows = _varying_rows(msgs)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = k._device_verify_packed.lower(
        sds((lanes, 20), jnp.uint32), sds((lanes, 20), jnp.uint32),
        sds((lanes, 8), jnp.uint32), sds((lanes, 16), jnp.uint32),
        sds((rows,), jnp.uint32), sds((kpad,), jnp.int32),
        sds((lanes, kpad), jnp.uint32), lanes=k.LANES).compile()
    assert "tpu_custom_call" in compiled.as_text()
