"""Compare forms of the CNN's conv lowering on the chip.

    python scripts/conv_dw_forms.py                      # every form, both models
    python scripts/conv_dw_forms.py --forms kernel,patches --int8 kernel

Each form replaces `models.cnn._conv` for the whole process:

  im2col    patches + `_ps_matmul` in both directions (the CPU lowering)
  autodiff  XLA conv with XLA's own filter gradient (one contraction over
            batch x pixels: not pad-stable by construction)
  vmap      XLA conv, dW as the vmap over samples of the one-sample filter
            gradient (a batch-grouped conv), summed over the batch
  patches   XLA conv, dW as `_ps_matmul`'s per-sample einsum on patches
            built in the backward, summed over the batch
  kernel    XLA conv, dW summed sample by sample in the Pallas kernel of
            `kernels.conv_dw` (the TPU lowering `_conv_xla`), up to 8
            samples per grid step, each sample's patches built in VMEM
  kernel_b1 the same kernel at one sample per grid step (TPU only)
  kernel_hbm the same sample-ordered sum over the patch tensor built by
            XLA in HBM (`ops.patches_t`, `ops.dy_rows`), up to 8 samples
            per grid step: the kernel's former input path (TPU only)

For each form and model (`mnist_paper`, `cifar_paper`) it prints one JSON
line: the scan chunk's time per round (one eval per chunk included, compile
excluded), and whether the loss and each layer's parameter gradient are
bit-identical between 20 samples and the same samples zero-padded to 32.
`--int8 <forms>` also runs chip_smoke.py's phase c (Pallas against XLA
quantizer, two training graphs that differ only in the quantizer) under
each listed form, and `--study <forms>` the 3-arm `Study(bit_check=True)`
of tests/test_study.py against sequential runs. Times are one-off
readings on whatever device runs the script.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import chip_smoke  # noqa: E402
from repro.federated import experiment  # noqa: E402
from repro.kernels.conv_dw import ops as conv_dw_ops  # noqa: E402
from repro.models import cnn  # noqa: E402


def _grouped(dw_one):
    """An XLA conv whose dW is `dw_one(x, dy, w)` (per batch), bias added."""
    @jax.custom_vjp
    def conv(x, w):
        return cnn._conv_fwd(x, w)

    def bwd(res, dy):
        x, w = res
        dx, = jax.vjp(lambda x: cnn._conv_fwd(x, w), x)[1](dy)
        return dx, dw_one(x, dy, w)

    conv.defvjp(lambda x, w: (cnn._conv_fwd(x, w), (x, w)), bwd)
    return lambda x, p: conv(x, p["w"]) + p["b"]


def _dw_vmap(x, dy, w):
    one = jax.vmap(lambda x, dy: jax.vjp(
        lambda w: cnn._conv_fwd(x[None], w), w)[1](dy[None])[0])
    return jnp.sum(one(x, dy), axis=0)


def _dw_patches(x, dy, w):
    k, _, _, O = w.shape
    _, dw = cnn._ps_matmul_bwd((cnn._patches(x, k), w.reshape(-1, O)), dy)
    return dw.reshape(w.shape)


def _dw_kernel_b1(x, dy, w):
    return conv_dw_ops._filter_grad(x, dy, w.shape[0], jnp.bfloat16,
                                    interpret=False, max_block_b=1)


def _dw_kernel_hbm(x, dy, w):
    from tests.test_kernels_conv_dw import _patch_tensor_dw
    k, _, _, O = w.shape
    B = x.shape[0]
    block_b = max(d for d in range(1, min(B, 8) + 1) if B % d == 0)
    bf16 = jnp.bfloat16
    dw = _patch_tensor_dw(conv_dw_ops.patches_t(x.astype(bf16), k),
                          conv_dw_ops.dy_rows(dy.astype(bf16), k), block_b,
                          interpret=False)
    return dw.reshape(w.shape)


FORMS = {
    "im2col": cnn._conv_im2col,
    "autodiff": lambda x, p: cnn._conv_fwd(x, p["w"]) + p["b"],
    "vmap": _grouped(_dw_vmap),
    "patches": _grouped(_dw_patches),
    "kernel": cnn._conv_xla,
    "kernel_b1": _grouped(_dw_kernel_b1),
    "kernel_hbm": _grouped(_dw_kernel_hbm),
}


def chunk_ms_per_round(name: str, rounds: int = 10) -> float:
    sim = experiment.get(name).build()
    state = sim.init(0)
    state, _ = sim.run(state, max_rounds=rounds, eval_every=rounds)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    state, _ = sim.run(state, max_rounds=rounds, eval_every=rounds)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / rounds * 1e3


def pad_stability(cfg, b: int = 20, B: int = 32) -> dict:
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(0))
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (B, *cfg.input_hw, cfg.in_channels))
    x = x.at[b:].set(0.0)
    y = jax.random.randint(ky, (B,), 0, cfg.n_classes)

    def grads(x, y, mask):
        f = jax.jit(jax.value_and_grad(lambda p: cnn.cnn_loss_masked(
            cfg, p, {"x": x, "y": y}, mask, jnp.float32(b))[0]))
        return f(params)

    l_b, g_b = grads(x[:b], y[:b], jnp.ones((b,)))
    l_B, g_B = grads(x, y, (jnp.arange(B) < b).astype(jnp.float32))
    return {"loss_equal": np.float32(l_b).tobytes() == np.float32(l_B).tobytes(),
            "grads_equal": {layer: all(
                np.array_equal(np.asarray(u), np.asarray(v))
                for u, v in zip(jax.tree.leaves(g_b[layer]),
                                jax.tree.leaves(g_B[layer])))
                for layer in g_b}}


def study_bit_check() -> dict:
    from tests.test_study import _tiny_spec
    from repro.federated.study import Study
    out = {}
    for scenario, compress in ((None, False), ("dropout", True)):
        arms = [("A", _tiny_spec(4, 2, scenario, compress)),
                ("B", _tiny_spec(8, 1, scenario, compress)),
                ("C", _tiny_spec(6, 3, scenario, compress))]
        try:
            res = Study(arms=arms, seeds=(0, 1), max_rounds=5, eval_every=2,
                        bit_check=True).run()
            probe = "passed"
        except AssertionError as e:
            out[f"{scenario}/{compress}"] = {"bit_check": f"failed: {e}"}
            continue
        loss_eq = params_eq = members = 0
        dmax = 0.0
        for label, spec in arms:
            for i, seed in enumerate((0, 1)):
                sim = spec.build()
                _, ref = sim.run(sim.init(seed), max_rounds=5, eval_every=2)
                got = res[label][i]
                members += 1
                loss_eq += all(np.float32(a.train_loss).tobytes()
                               == np.float32(c.train_loss).tobytes()
                               for a, c in zip(ref.history, got.history))
                d = max(float(np.max(np.abs(np.asarray(u) - np.asarray(v))))
                        for u, v in zip(jax.tree.leaves(ref.params),
                                        jax.tree.leaves(got.params)))
                params_eq += d == 0.0
                dmax = max(dmax, d)
        out[f"{scenario}/{compress}"] = {
            "bit_check": probe, "members": members,
            "loss_bit_equal": loss_eq, "params_bit_equal": params_eq,
            "max_abs_dparams": dmax}
    return out


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--models", default="mnist_paper,cifar_paper")
    ap.add_argument("--int8", default="")
    ap.add_argument("--study", default="")
    args = ap.parse_args()
    split = lambda s: [f for f in s.split(",") if f]  # noqa: E731
    device = jax.devices()[0].device_kind
    for form in split(args.forms):
        cnn._conv = FORMS[form]
        for name in split(args.models):
            cfg = experiment.MODELS[experiment.get(name).model]()
            emit({"device": device, "form": form, "experiment": name,
                  "chunk_ms_per_round": chunk_ms_per_round(name),
                  "pad": pad_stability(cfg)})
    for form in split(args.int8):
        cnn._conv = FORMS[form]
        try:
            c = chip_smoke.phase_int8()
        except chip_smoke.SmokeFailure as e:
            c = {"failed": str(e)}
        emit({"device": device, "form": form, "phase_c": c})
    for form in split(args.study):
        cnn._conv = FORMS[form]
        emit({"device": device, "form": form, "study": study_bit_check()})


if __name__ == "__main__":
    main()
