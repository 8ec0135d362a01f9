"""Compiles for a described TPU v5e chip, with no chip attached: the
quantize kernel and the main-path scan chunks at `mnist_cnn`'s published
widths. The TPU compiler refuses here what it would refuse on the chip
(tiling, VMEM, device memory); nothing runs, so this says nothing about
results or times.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and a decision made at import would
give pytest-xdist workers different test lists.
"""
import dataclasses
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke as cs  # noqa: E402
from repro.federated import compression, experiment  # noqa: E402
from repro.kernels.quantize import ops as q_ops  # noqa: E402
from repro.kernels.quantize.kernel import quantize_kernel  # noqa: E402
from repro.models import cnn  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_chunk(spec, sharding):
    """The simulator's own jitted chunk, lowered with the first chunk
    call's argument shapes and compiled for `sharding`'s chip."""
    sim = spec.build()
    shapes = cs.chunk_arg_shapes(sim, rounds=5, sharding=sharding)
    return sim._chunk_fn.lower(*shapes).compile()


def _update_rows():
    """Rows of the flattened int8 update of one mnist_cnn client."""
    params = jax.eval_shape(lambda k: cnn.init_cnn(cnn.mnist_cnn(), k),
                            jax.random.PRNGKey(0))
    return sum(math.ceil(math.prod(x.shape) / compression.ROW)
               for x in jax.tree.leaves(params))


def test_quantize_kernel_compiles_at_mnist_cnn_rows(one_chip):
    block_r = 256
    rows = _update_rows()
    R = rows + (-rows) % block_r  # ops.quantize pads to whole blocks
    x = jax.ShapeDtypeStruct((R, compression.ROW), jnp.float32,
                             sharding=one_chip)
    fn = jax.jit(functools.partial(quantize_kernel, block_r=block_r,
                                   interpret=False))
    assert "tpu_custom_call" in fn.lower(x, x).compile().as_text()


def test_mnist_paper_scan_chunk_compiles(one_chip):
    compiled = _compile_chunk(experiment.get("mnist_paper"), one_chip)
    mem = compiled.memory_analysis()
    # Well inside the 16 GB of one v5e chip.
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 8e9


def test_compressed_pallas_chunk_compiles_to_mosaic(one_chip, monkeypatch):
    # The host is a CPU, so the wrapper would pick interpret mode; the
    # described chip takes the compiled kernel.
    monkeypatch.setattr(q_ops, "interpret_mode", lambda: False)
    base = experiment.get("mnist_paper")
    spec = base.replace(impl="pallas", fed=dataclasses.replace(
        base.fed, compress_updates=True))
    compiled = _compile_chunk(spec, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
