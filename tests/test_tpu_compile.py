"""Compile the Pallas kernels of the fit and serve paths for one TPU v5e chip.

Nothing runs: the TPU compiler shipped with jaxlib compiles each wrapper
(padding included) for a described, unattached v5e at the shapes the chip
smoke drives, and raises what the chip's compiler would raise — a block
shape the layout rules refuse, a primitive Mosaic cannot lower, or a
kernel that overflows the 16 MiB of scoped VMEM.  Interpret mode sees none
of these.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under several test
workers the worker that runs this file is the one that loads it.  Keep
these compiles in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

#: (m, d) of the Gram-family compiles: the fit operator of the smoke's
#: low-d (pendigits-shaped) and wide (usps-shaped) phases.
GRAM_SHAPES = [(8192, 16), (8192, 256)]
#: Serving operators: m up to the ingest budget's half, both widths.
PROJECT_SHAPES = [(16384, 16), (16384, 256)]
#: Center budget of the out-of-core ingest merge.
ASSIGN_CENTERS = 32768


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32 = jnp.float32


@pytest.mark.parametrize("m,d", GRAM_SHAPES)
def test_weighted_gram_compiles(one_chip, m, d):
    _compile(one_chip,
             lambda c, w: ops.weighted_gram(c, w, sigma=1.0, plan="pallas",
                                            interpret=False),
             ((m, d), F32), ((m,), F32))


@pytest.mark.parametrize("m,d", GRAM_SHAPES)
@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_weighted_gram_matvec_compiles(one_chip, m, d, precision):
    _compile(one_chip,
             lambda c, w, v: ops.weighted_gram_matvec(
                 c, w, v, sigma=1.0, plan="pallas", interpret=False,
                 precision=precision),
             ((m, d), F32), ((m,), F32), ((m, 9), F32))


@pytest.mark.parametrize("m,d", GRAM_SHAPES)
def test_gram_row_compiles(one_chip, m, d):
    _compile(one_chip,
             lambda x, c, w: ops.gram_row(x, c, w, sigma=1.0, plan="pallas",
                                          interpret=False),
             ((d,), F32), ((m, d), F32), ((m,), F32))


@pytest.mark.parametrize("n", [2048, 65536])
def test_shadow_assign_compiles(one_chip, n):
    _compile(one_chip,
             lambda x, c, v: ops.shadow_assign(x, c, valid=v, plan="pallas",
                                               interpret=False),
             ((n, 16), F32), ((ASSIGN_CENTERS, 16), F32),
             ((ASSIGN_CENTERS,), F32))


@pytest.mark.parametrize("m,d", PROJECT_SHAPES)
@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("tile", ops._PROJECT_TILES_TPU)
def test_kpca_project_compiles(one_chip, m, d, precision, tile):
    _compile(one_chip,
             lambda x, c, a: ops.kpca_project(
                 x, c, a, sigma=1.0, precision=precision,
                 plan=f"pallas:{tile}", interpret=False),
             ((tile, d), F32), ((m, d), F32), ((m, 8), F32))


@pytest.mark.parametrize("tile", ops._RFF_TILES_TPU)
@pytest.mark.parametrize("nfeat,d", [(1024, 16), (4096, 256)])
def test_rff_project_compiles(one_chip, tile, nfeat, d):
    _compile(one_chip,
             lambda x, w, b, u: ops.rff_project(
                 x, w, b, u, plan=f"pallas:{tile}", interpret=False),
             ((tile, d), F32), ((nfeat, d), F32), ((nfeat,), F32),
             ((nfeat, 8), F32))
