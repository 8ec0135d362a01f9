"""Compiles for a described TPU v5e chip, with no chip attached: the
quantize kernel, the main-path scan chunks and the eval's forward at
`mnist_cnn`'s published widths, with the CNN's convs lowered as they are
for the TPU. The TPU compiler refuses here what it would refuse on the chip
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
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke as cs  # noqa: E402
from repro.federated import compression, experiment  # noqa: E402
from repro.kernels.conv_dw import ops as conv_dw_ops  # noqa: E402
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


@pytest.fixture(scope="module")
def mnist_chunk(one_chip):
    return _compile_chunk(experiment.get("mnist_paper"), one_chip)


def test_mnist_paper_scan_chunk_compiles(mnist_chunk):
    mem = mnist_chunk.memory_analysis()
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


@pytest.fixture(scope="module")
def mnist_chunk_im2col(one_chip):
    """The same chunk with the convs lowered as im2col + matmul, as the
    TPU lowered them before `_conv` chose by platform."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cnn, "_conv", cnn._conv_im2col)
        return _compile_chunk(experiment.get("mnist_paper"), one_chip)


def _concat_operands(hlo: str) -> list:
    """Operand count of every `concatenate` in compiled HLO text. The
    operand list is read up to its matching ')', so parentheses inside
    shapes and layouts (`T(8,128)`) do not cut it."""
    counts = []
    for m in re.finditer(r" concatenate\(", hlo):
        depth, j = 1, m.end()
        while depth:
            depth += {"(": 1, ")": -1}.get(hlo[j], 0)
            j += 1
        counts.append(len(re.findall(r"%[\w.\-]+", hlo[m.end():j - 1])))
    return counts


def _patch_concats(hlo: str) -> int:
    """25-operand `concatenate`s: the shifted slices of a 5x5 patch."""
    return _concat_operands(hlo).count(25)


def _has_5x5_conv(hlo: str) -> bool:
    return re.search(r"convolution\([^\n]*window=\{size=5x5", hlo) is not None


def test_patch_counter_finds_im2col_patches(mnist_chunk_im2col):
    """The counter below sees im2col's patches: one concatenate per conv
    layer in the forward, and more in the backward."""
    hlo = mnist_chunk_im2col.as_text()
    assert _patch_concats(hlo) >= 3
    assert not _has_5x5_conv(hlo)


def test_mnist_paper_chunk_runs_xla_convs(mnist_chunk, mnist_chunk_im2col):
    """On the TPU the 5x5 convs lower to XLA convolutions, and each conv
    layer's filter gradient runs in the Pallas kernel, which builds its
    patches in VMEM: fewer patch concatenates than im2col builds, and
    fewer temporary bytes."""
    hlo, ref = mnist_chunk.as_text(), mnist_chunk_im2col.as_text()
    assert _has_5x5_conv(hlo)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    assert _patch_concats(hlo) < _patch_concats(ref)
    temp = mnist_chunk.memory_analysis().temp_size_in_bytes
    assert temp < mnist_chunk_im2col.memory_analysis().temp_size_in_bytes


# The same chunk while the filter gradient's kernel read its patches as a
# tensor from HBM, built by XLA (`conv_dw_ops.patches_t`): 881,636,864 B of
# temporaries (v5e compile, jax 0.9.0, libtpu 0.0.34).
PATCH_TENSOR_CHUNK_TEMP_BYTES = 881_636_864


def test_mnist_paper_chunk_holds_no_patch_tensor(mnist_chunk):
    """No conv layer's patch tensor (k*k*C rows by H*Wr lanes: [.., 25,
    896] and [.., 800, 336] in mnist_cnn) is a buffer of the chunk, nor the
    target of a dynamic-update-slice; the kernel builds the patches in
    VMEM. The temporaries fall below the patch-tensor chunk's by at least
    conv2's tensor, 10 clients x b = 32 samples x 800 x 336 bf16 =
    172,032,000 B (703,020,544 B, v5e compile)."""
    cfg = cnn.mnist_cnn()
    hlo = mnist_chunk.as_text()
    k, (H, W), C = cfg.kernel, cfg.input_hw, cfg.in_channels
    tensor_bytes = []
    for C_out in cfg.conv_channels:
        rows, lanes = k * k * C, H * conv_dw_ops._row_width(W, k)
        shape = rf"\[[\d,]*\b{rows},{lanes}\]"
        assert not re.search(shape, hlo), (rows, lanes)
        assert not re.search(rf"= \w+{shape}[^\n]*dynamic-update-slice\(",
                             hlo), (rows, lanes)
        tensor_bytes.append(10 * 32 * rows * lanes * 2)
        H, W, C = H // 2, W // 2, C_out
    temp = mnist_chunk.memory_analysis().temp_size_in_bytes
    assert temp <= PATCH_TENSOR_CHUNK_TEMP_BYTES - max(tensor_bytes)


def test_mnist_cnn_eval_forward_builds_no_patches(one_chip):
    """The eval's forward (`cnn_forward` on a test batch) holds XLA
    convolutions and no patches at all."""
    cfg = cnn.mnist_cnn()
    params = jax.eval_shape(lambda k: cnn.init_cnn(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((1000, *cfg.input_hw, cfg.in_channels),
                             jnp.float32, sharding=one_chip)
    fwd = jax.jit(functools.partial(cnn.cnn_forward, cfg))
    hlo = fwd.lower(params, x).compile().as_text()
    assert _has_5x5_conv(hlo)
    assert _patch_concats(hlo) == 0
