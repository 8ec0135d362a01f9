"""The simulator's own profiler marks: the `defl.*` host spans of the
chunked drivers, the work/talk scopes of the compiled round, the jit
names the chip benchmark finds its modules by, and the compile log."""
import dataclasses
import inspect
import os
import re
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402
from chipbench import trace  # noqa: E402
from repro.federated import experiment  # noqa: E402
from repro.utils import compile_cache  # noqa: E402


def _traced_spans(sim, tmp_path, **run_kw):
    """`defl.*` spans of one `sim.run` under the profiler (the run's
    programs compiled beforehand), read back as the chip benchmark reads
    a trace."""
    state, _ = sim.run(sim.init(3), **run_kw)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            state, _ = sim.run(state, **run_kw)
            sim.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    host = trace.from_xspace(trace.find_xspace(str(tmp_path))).host
    return sorted((e for e in host if e.name.startswith("defl.")),
                  key=lambda e: (e.start, -e.dur))


def _inside(e, parent):
    return parent.start <= e.start and e.end <= parent.end


def _children(spans, parent):
    """The spans directly inside `parent`, in order."""
    inner = [e for e in spans if e is not parent and _inside(e, parent)]
    return [e for e in inner
            if not any(o is not e and _inside(e, o) for o in inner)]


def _one_run(spans):
    runs = [e for e in spans if e.name == "defl.run"]
    assert len(runs) == 1
    assert all(_inside(e, runs[0]) for e in spans)
    return [e.name for e in _children(spans, runs[0])], runs[0]


@pytest.mark.parametrize("name,prep", [
    ("mnist_smoke", ["defl.batch_indices"]),
    ("mnist_sampled",
     ["defl.draw_cohorts", "defl.draw_chunk", "defl.batch_indices"]),
])
def test_scan_run_spans_nest_in_driver_order(name, prep, tmp_path):
    sim = experiment.get(name).build()
    spans = _traced_spans(sim, tmp_path, max_rounds=4, eval_every=2)
    steps, _ = _one_run(spans)
    chunk = ["defl.chunk_inputs", "defl.dispatch", "defl.fetch",
             "defl.records", "defl.eval"]
    assert steps == ["defl.materialize"] + 2 * chunk + ["defl.snapshot"]
    for e in spans:
        if e.name == "defl.chunk_inputs":
            assert [c.name for c in _children(spans, e)] == prep


def test_async_run_spans_nest_in_driver_order(tmp_path):
    sim = experiment.get("mnist_async").build()
    spans = _traced_spans(sim, tmp_path, max_rounds=2, eval_every=1)
    steps, _ = _one_run(spans)
    chunk = r"chunk_inputs dispatch fetch records (eval )?"
    seq = " ".join(s.removeprefix("defl.") for s in steps) + " "
    assert re.fullmatch(rf"materialize ({chunk})+snapshot ", seq), seq
    assert seq.count("eval") == 2


@pytest.mark.parametrize("compress", [False, True])
def test_compiled_chunk_carries_the_work_and_talk_scopes(compress):
    spec = experiment.get("mnist_smoke")
    sim = spec.replace(fed=dataclasses.replace(
        spec.fed, compress_updates=compress)).build()
    text = sim._chunk_fn.lower(*cs.chunk_arg_shapes(sim, 2)).as_text(
        debug_info=True)
    assert "module @jit_chunk_step" in text
    names = re.findall(r'loc\("([^"]*)"', text)
    work = [n for n in names if n.startswith("local_steps/")]
    talk = [n for n in names if n.startswith("aggregate/")]
    # The local scan (forward, backward and SGD step) is work; the
    # weighted mean, with the int8 uplink where it is on, is talk.
    assert any(n.startswith("local_steps/vmap()/while") for n in work)
    assert any(n.endswith("/reduce_sum") for n in talk)
    eval_acc = inspect.getclosurevars(sim.eval_fn).nonlocals["eval_acc"]
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          sim.params(sim.init(0)))
    assert "module @jit_eval_acc" in eval_acc.lower(params).as_text()


def test_compile_log_keeps_trace_lowering_and_compile_spans(
        monkeypatch, tmp_path):
    # With the directory named by the environment the cache setting is
    # left alone, so this process keeps JAX's default (no cache).
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    compile_cache.enable_compile_cache()
    compile_cache.enable_compile_cache()  # listens once, however often called
    before = len(compile_cache.compile_log())

    @jax.jit
    def logged_fn(x):
        return x * 2 + 1

    np.testing.assert_array_equal(logged_fn(np.arange(3.0)), [1, 3, 5])
    new = compile_cache.compile_log()[before:]
    ours = [(ev, fun) for ev, fun, _, _ in new if "logged_fn" in fun]
    assert [ev for ev, _ in ours] == [compile_cache.TRACE_EVENT,
                                      compile_cache.LOWER_EVENT,
                                      compile_cache.COMPILE_EVENT]
    assert all(start <= end for _, _, start, end in new)
