"""Compile the simulator's Pallas kernels for a TPU v5e without a chip.

The TPU compiler is installed with jax, and it compiles for a chip that
is described and not attached (`topologies.get_topology_desc`).  These
tests lower every kernel of the main path through Mosaic at real widths
— SF q=5 and the paper's §V fabric, SF q=19 (722 routers, 29 network
ports, 4 VCs, a 4-slot window, 15 endpoints per router, 10,830
endpoints) — and assert that the compiled program holds the kernel
(`tpu_custom_call`).  Interpret mode would hide exactly the refusals
they catch: unsupported primitives, bool layouts, unaligned slices.

The topology is described inside a fixture, never while a module is
imported, and every test of this kind lives in this one file.
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import alloc_rounds, backend, ugal_select

minplus_mod = importlib.import_module("repro.kernels.minplus")

# (N routers, P network ports, V VCs, PE endpoints/router, W window)
SHAPES = {"q5": (50, 7, 4, 4, 4), "q19": (722, 29, 4, 15, 4)}
LANES = 5
HBM_BYTES = 16 * 2**30                    # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library would otherwise write its logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the kernels steered off interpret mode
    and the persistent compilation cache off (a TPU executable written
    to it cannot be read back without a chip).  jax's trace caches are
    cleared on both sides, so no interpreted kernel is reused here and
    no Mosaic one leaks into later CPU tests."""
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backend, "interpret_mode", lambda: False)
        yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
    return compiled


@pytest.mark.parametrize("lanes", [None, LANES], ids=["single", "L5"])
@pytest.mark.parametrize("size", ["q5", "q19"])
def test_alloc_rounds_compiles(one_chip, size, lanes):
    N, P, V, PE, W = SHAPES[size]
    PV = P * V
    NQ = N * PV
    lead = () if lanes is None else (lanes,)

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    args = [s(*lead), s(*lead, N, PV, W), s(*lead, N, PV, W),
            s(*lead, N, PV, W), s(*lead, N, PV), s(*lead, N, PE, W),
            s(*lead, N, PE, W), s(*lead, N, PE, W), s(*lead, N, PE), s(N)]
    kw = dict(W=W, P=P, V=V, PE=PE, p_budget=PE, NQ=NQ, R=NQ + N * PE,
              use_pallas=True)
    _compile(lambda *a: alloc_rounds(*a, **kw), args)


@pytest.mark.parametrize("ugal_g", [False, True], ids=["ugal_l", "ugal_g"])
def test_ugal_select_compiles(one_chip, ugal_g):
    N, _, _, PE, _ = SHAPES["q19"]
    E, C = N * PE, 4

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    _compile(lambda *a: ugal_select(*a, ugal_g=ugal_g, unreach=1 << 14,
                                    big=1 << 30, use_pallas=True),
             [s(E), s(E, C), s(E), s(E, C)])


def test_minplus_compiles(one_chip):
    """Batched APSP squaring at the q=19 router count (the resiliency
    studies run it over a batch of perturbed adjacencies)."""
    N = SHAPES["q19"][0]
    x = jax.ShapeDtypeStruct((2, N, N), jnp.float32, sharding=one_chip)
    compiled = _compile(minplus_mod.minplus_pallas, [x, x])
    assert np.prod(compiled.out_info.shape) == 2 * N * N
