"""Compile-only rehearsals of the hot-path Pallas kernels for a TPU v5e.

The TPU compiler is installed beside the CPU backend, so both kernels of the
engine's main path are compiled here for a described (not attached) v5e chip
at the shapes the engine feeds them: the MC-scoring kernel at the paper's
window ``[T=16, W=200, C=10]``, plain and vmapped over devices, and the Eq. 1
reduce over a LeNet-5 fleet in its f32, int8+scales and G=4 segment forms.
Mosaic refuses what interpret mode accepts (unaligned blocks, float iotas),
so these guard the chip path at no chip time.  Nothing runs: results are
checked by the interpret-mode suites (test_kernels.py,
test_fused_aggregation.py) and on the chip by ``chip_smoke.py``.

The topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import lenet
from repro.kernels.acquisition_scores import acquisition_scores_fused
from repro.kernels.fused_aggregation import fused_aggregate
from repro.nn.lenet import LeNet

FLEET_SIZES = [16, 256, 1024]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    a compile for a described chip is written to the cache but cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, *args):
    """Compile for the described chip; returns the executable's HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text, name):
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert any(name in line for line in calls), (
        f"no Mosaic custom call for {name!r} in the compiled program")


def _lenet_fleet(D, dtype, sharding):
    """``[D, ...]`` ShapeDtypeStructs of the paper's LeNet-5 parameters."""
    shapes = jax.eval_shape(lambda k: LeNet.init(k, lenet.config()),
                            jax.random.key(0))
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((D,) + s.shape, dtype,
                                       sharding=sharding), shapes)


@pytest.mark.parametrize("lead", [(), (64,)], ids=["plain", "vmap_D64"])
def test_acquisition_scores_compiles_at_paper_window(one_chip, lead):
    logp = jax.ShapeDtypeStruct(lead + (16, 200, 10), jnp.float32,
                                sharding=one_chip)
    score = lambda lp: acquisition_scores_fused(lp, interpret=False)
    for _ in lead:
        score = jax.vmap(score)
    _assert_kernel(_compile_for_chip(score, logp), "acquisition_scores")


@pytest.mark.parametrize("form", ["f32", "int8", "segment_G4"])
@pytest.mark.parametrize("D", FLEET_SIZES)
def test_fused_aggregation_compiles_on_lenet_fleet(one_chip, D, form):
    vec = lambda dt: jax.ShapeDtypeStruct((D,), dt, sharding=one_chip)
    fleet = _lenet_fleet(D, jnp.int8 if form == "int8" else jnp.float32,
                         one_chip)
    w = vec(jnp.float32)
    if form == "int8":
        scales = jax.tree_util.tree_map(lambda _: vec(jnp.float32), fleet)
        text = _compile_for_chip(lambda t, v, s: fused_aggregate(
            t, v, scales=s, normalize=False, interpret=False),
            fleet, w, scales)
    elif form == "segment_G4":
        text = _compile_for_chip(lambda t, v, ids: fused_aggregate(
            t, v, segment_ids=ids, num_segments=4, normalize=False,
            interpret=False), fleet, w, vec(jnp.int32))
    else:
        text = _compile_for_chip(lambda t, v: fused_aggregate(
            t, v, normalize=False, interpret=False), fleet, w)
    _assert_kernel(text, "fused_aggregation")
