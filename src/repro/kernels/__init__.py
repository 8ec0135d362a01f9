"""Pallas kernels of the FL path (quantize), of the CNN's conv filter
gradient on the TPU (conv_dw) and of the model stack (flash attention,
selective scan). Each has kernel.py, a jit'd ops.py
wrapper, and a plain-jnp ref.py oracle."""
import jax


def interpret_mode() -> bool:
    """Whether the ops.py wrappers run their Pallas kernel in interpret
    mode: yes on the CPU (tests), no on the TPU (compiled Mosaic). Any
    other backend has no kernel path and raises instead of silently
    interpreting on an accelerator."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on the TPU or interpreted on the CPU; "
        f"the default backend is {backend!r}")
