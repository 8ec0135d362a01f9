"""The readers of the program's own marks, on hand-built traces and
compile logs: `host_prep_ms_per_round` from the `defl.*` host spans,
`setup_lower_s` and `setup_compile_s` from the compile log, and the
accepted readers unmoved by the new spans."""
from __future__ import annotations

import dataclasses

import pytest

from chipbench import compiles, trace
from chipbench.tests.test_trace import MS, _Ctx, _reader, _trace
from chipbench.trace import Event, Trace
from repro.utils import compile_cache

# The first window call (0-45 ms) as the program marks it: the chunk's
# host prep, its dispatch and fetch, the eval and the closing snapshot;
# then the second call's prep (52-55).
DEFL = [Event("defl.run", 1 * MS, 43 * MS),
        Event("defl.materialize", 1 * MS, 2 * MS),
        Event("defl.chunk_inputs", 3 * MS, 4 * MS),
        Event("defl.batch_indices", 4 * MS, 2 * MS),
        Event("defl.dispatch", 7 * MS, 2 * MS),
        Event("defl.fetch", 9 * MS, 31 * MS),
        Event("defl.eval", 40 * MS, 3 * MS),
        Event("defl.snapshot", 43 * MS, 1 * MS),
        Event("defl.materialize", 52 * MS, 1 * MS),
        Event("defl.chunk_inputs", 53 * MS, 2 * MS)]


def _spanned() -> Trace:
    tr = _trace()
    return dataclasses.replace(tr, host=tr.host + DEFL)


def _log(monkeypatch, entries):
    monkeypatch.setattr(compile_cache, "compile_log", lambda: list(entries))


def test_host_prep_sums_the_prep_spans_over_rounds():
    ctx = _Ctx()
    ctx.trace = _spanned()
    # materialize 2 + 1, chunk_inputs 4 + 2, snapshot 1 = 10 ms; its
    # nested batch_indices is not counted again. 10 rounds.
    assert _reader("host_prep_ms_per_round")(ctx) == pytest.approx(1.0)
    ctx.rounds = 0
    assert _reader("host_prep_ms_per_round")(ctx) is None


def test_host_prep_without_program_spans_is_nothing():
    assert _reader("host_prep_ms_per_round")(_Ctx()) is None


def test_setup_readers_count_nested_spans_once(monkeypatch):
    _log(monkeypatch, [
        # Tracing chunk_step traces an inner jit: 1-4 s holds 2-3 s.
        (compiles.TRACE, "loss", 2.0, 3.0),
        (compiles.TRACE, "chunk_step", 1.0, 4.0),
        # Its lowering overlaps the tail of the trace span: 3.5-6.
        (compiles.LOWER, "jit(chunk_step)", 3.5, 6.0),
        (compiles.COMPILE, "jit(chunk_step)", 6.0, 10.0),
        (compiles.TRACE, "eval_acc", 10.0, 10.5),
        (compiles.LOWER, "jit(eval_acc)", 10.5, 11.0),
        (compiles.COMPILE, "jit(eval_acc)", 11.0, 12.5),
        # A small compile nested in the big one still counts once.
        (compiles.COMPILE, "jit(squeeze)", 7.0, 7.5),
        ("/jax/other/event", "x", 0.0, 100.0)])
    ctx = _Ctx()
    # 1-6 and 10-11: 6 s.
    assert _reader("setup_lower_s")(ctx) == pytest.approx(6.0)
    # 6-10 and 11-12.5: 5.5 s.
    assert _reader("setup_compile_s")(ctx) == pytest.approx(5.5)


@pytest.mark.parametrize("name", ["setup_lower_s", "setup_compile_s"])
def test_setup_readers_without_a_log_return_nothing(name, monkeypatch):
    _log(monkeypatch, [])
    assert _reader(name)(_Ctx()) is None
    _log(monkeypatch, [(compiles.COMPILE if name == "setup_lower_s"
                        else compiles.TRACE, "f", 0.0, 1.0)])
    assert _reader(name)(_Ctx()) is None
    # A program that keeps no log at all.
    monkeypatch.delattr(compile_cache, "compile_log")
    assert _reader(name)(_Ctx()) is None


@pytest.mark.parametrize("name", [
    "mfu", "idle_share", "chunk_device_ms_per_round", "eval_device_ms",
    "retraces", "collective_ms_per_round", "quantize_roofline"])
def test_accepted_readers_are_unmoved_by_program_spans(name):
    plain, spanned = _Ctx(), _Ctx()
    spanned.trace = _spanned()
    assert _reader(name)(spanned) == _reader(name)(plain)


def test_idle_gaps_inside_a_call_carry_the_program_step():
    gaps = dict(trace.idle_gaps(_spanned()))
    # Each gap goes to the innermost event over its middle. 0-10 ms
    # (middle 5): the chunk's batch indices; 40-50 (45): JAX's eval
    # dispatch; 70-100 (85): no program step, so the harness's call.
    assert gaps == {"defl.batch_indices": pytest.approx(0.010),
                    "PjitFunction(eval_acc)": pytest.approx(0.010),
                    trace.CALL: pytest.approx(0.030)}
