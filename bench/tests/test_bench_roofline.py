"""The kernels' byte counts and the table of peaks."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests.conftest import ROOT

from bench import peaks
from bench.harness import load_module

SF_Q19 = dict(N=722, P=29, V=4, W=4, PE=15, E=10830, C=4, ugal=True)
DF_H7 = dict(N=1386, P=20, V=4, W=4, PE=7, E=9702, C=4, ugal=True)


def reader(name):
    return load_module(os.path.join(ROOT, "bench", "metrics", name + ".py"))


def nbytes(tree):
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def alloc_operands(s):
    N, PV, PE, W = s["N"], s["P"] * s["V"], s["PE"], s["W"]
    i32 = jnp.int32
    a3n = jax.ShapeDtypeStruct((N, PV, W), i32)
    a3s = jax.ShapeDtypeStruct((N, PE, W), i32)
    return (jax.ShapeDtypeStruct((), i32), a3n, a3n, a3n,
            jax.ShapeDtypeStruct((N, PV), i32), a3s, a3s, a3s,
            jax.ShapeDtypeStruct((N, PE), i32),
            jax.ShapeDtypeStruct((N,), i32))


@pytest.mark.parametrize("sizes", [SF_Q19, DF_H7], ids=["sf_q19", "df_h7"])
def test_alloc_bytes_are_the_logical_operands(sizes):
    """One cycle's logical inputs and outputs, each once: what the
    oracle of the kernel takes and gives, not the padded blocks."""
    from repro.kernels import ref

    s = sizes
    args = alloc_operands(s)
    out = jax.eval_shape(lambda *a: ref.alloc_rounds_ref(
        *a, W=s["W"], P=s["P"], V=s["V"], PE=s["PE"], p_budget=s["PE"],
        NQ=s["N"] * s["P"] * s["V"], R=s["N"] * s["P"] * s["V"] + s["E"]),
        *args)
    assert reader("alloc_rounds_roofline").logical_bytes(s) == (
        nbytes(args) + nbytes(out))


@pytest.mark.parametrize("block", [8, 16, 128])
def test_alloc_bytes_do_not_follow_the_block_layout(block):
    """The kernel pads rows to a multiple of its block, so the operands it
    is handed grow with the block; the count stays at the logical size."""
    s = SF_Q19
    rows = s["N"] + (-s["N"] % block)
    handed = nbytes(alloc_operands(dict(s, N=rows)))
    count = reader("alloc_rounds_roofline").logical_bytes(s)
    assert rows > s["N"] and handed > nbytes(alloc_operands(s))
    assert count == reader("alloc_rounds_roofline").logical_bytes(
        dict(s, W=4))
    assert count < handed + nbytes(alloc_operands(s))


@pytest.mark.parametrize("sizes", [SF_Q19, DF_H7], ids=["sf_q19", "df_h7"])
def test_ugal_bytes_are_the_logical_operands(sizes):
    from repro.kernels import ref

    E, C = sizes["E"], sizes["C"]
    i32 = jnp.int32
    args = (jax.ShapeDtypeStruct((E,), i32), jax.ShapeDtypeStruct((E, C), i32),
            jax.ShapeDtypeStruct((E,), i32), jax.ShapeDtypeStruct((E, C), i32))
    out = jax.eval_shape(lambda *a: ref.ugal_select_ref(
        *a, ugal_g=False, unreach=1 << 14, big=1 << 30), *args)
    assert reader("ugal_select_roofline").logical_bytes(sizes) == (
        nbytes(args) + nbytes(out))


def test_peaks_table():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with open(peaks.PATH) as f:
        table = json.load(f)
    assert all("source" in v for v in table.values())


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peak("TPU v99 imaginary", "hbm_bytes_per_s")
