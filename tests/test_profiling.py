"""Device-side profiling helpers (mpi_acx_tpu/profiling.py)."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from mpi_acx_tpu import profiling


def test_trace_writes_profile(tmp_path):
    logdir = str(tmp_path / "prof")
    with profiling.trace(logdir):
        with profiling.Phases()("matmul"):
            x = jnp.ones((128, 128))
            jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
    files = glob.glob(os.path.join(logdir, "**", "*"), recursive=True)
    assert any(os.path.isfile(f) for f in files), files


# -- Phases: spans on the trace's clock + self-time counters ---------------

def _hand_clock():
    now = [0.0]

    def tick(to):
        now[0] = to
    return (lambda: now[0]), tick


def test_phases_self_time_on_a_hand_made_clock():
    """A child's time is subtracted from its parent once, and from
    nobody else; the self times of spans that tile an interval sum to
    it; a span hands back its own two readings."""
    clock, tick = _hand_clock()
    ph = profiling.Phases(clock=clock)
    with ph("outer") as outer:                 # 0 .. 10
        tick(1.0)
        with ph("child", rid=3) as first:      # 1 .. 3
            tick(2.0)
            with ph("grandchild"):             # 2 .. 2.5
                tick(2.5)
            tick(3.0)
        tick(4.0)
        with ph("child"):                      # 4 .. 4.5
            tick(4.5)
        tick(10.0)
    assert ph.seconds == {"grandchild": 0.5, "child": 1.5 + 0.5,
                          "outer": 10.0 - 2.0 - 0.5}
    assert ph.count == {"grandchild": 1, "child": 2, "outer": 1}
    assert sum(ph.seconds.values()) == outer.seconds == 10.0
    assert (first.t0, first.t1, first.seconds) == (1.0, 3.0, 2.0)


def test_phases_siblings_tile_their_interval_and_survive_an_exception():
    clock, tick = _hand_clock()
    ph = profiling.Phases(clock=clock)
    with ph("a"):
        tick(2.0)
    try:
        with ph("b"):
            tick(5.0)
            raise KeyError("inside")
    except KeyError:
        pass
    with ph("a"):
        tick(6.0)
    assert ph.seconds == {"a": 3.0, "b": 3.0} and ph.count == {"a": 2, "b": 1}
    assert not ph._open                         # the failed span closed


def test_phases_span_is_on_the_python_line_of_the_profile(tmp_path):
    """With the profiler on, a Phases span is an event of the host's
    ``python`` line in the same file as the device's ops, under its own
    name, nested in its parent and carrying its ids."""
    from jax.profiler import ProfileData
    logdir = str(tmp_path / "prof")
    ph = profiling.Phases()
    with profiling.trace(logdir):
        with ph("chunk.step", step=4) as outer:
            outer.ids["rid"] = (7, -1)      # the record's, not the trace's
            with ph("refill.prefill", rid=7):
                jax.block_until_ready(jax.jit(lambda a: a @ a)(
                    jnp.ones((64, 64))))
    path, = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            # The line is named after the thread: ``python``, unless the
            # interpreter was started through a script of another name.
            lines = list(plane.lines)
            for line in [l for l in lines if l.name == "python"] or lines:
                for e in line.events:
                    if e.name in ("chunk.step", "refill.prefill"):
                        found[e.name] = (line.name, e.start_ns,
                                         e.start_ns + e.duration_ns,
                                         dict(e.stats))
    assert set(found) == {"chunk.step", "refill.prefill"}
    (line_o, s_o, e_o, ids_o), (line_i, s_i, e_i, ids_i) = (
        found["chunk.step"], found["refill.prefill"])
    assert line_o == line_i
    assert ids_o == {"step": 4} and ids_i == {"rid": 7}
    assert s_o <= s_i and e_i <= e_o            # nested under its parent
    assert ph.count == {"chunk.step": 1, "refill.prefill": 1}
    assert ph.spans[0].ids == {"step": 4, "rid": (7, -1)}


# -- the record: every span stays in ``Phases.spans`` ----------------------

def test_record_keeps_order_parents_ids_and_the_hand_over_mark():
    """Spans stay in order of OPENING with the index of the span they
    were opened under; the hand-over mark is one more reading inside
    the span and moves no self time."""
    def run(mark):
        clock, tick = _hand_clock()
        ph = profiling.Phases(clock=clock)
        with ph("outer", step=1):                  # 0 .. 10
            tick(1.0)
            with ph("child", rid=3) as child:      # 1 .. 3
                tick(1.5)
                if mark:
                    child.hand_over()
                tick(2.0)
                with ph("grandchild"):             # 2 .. 2.5
                    tick(2.5)
                tick(3.0)
            with ph("child", rid=4):               # 3 .. 4.5
                tick(4.5)
            tick(10.0)
        with ph("after"):                          # 10 .. 11
            tick(11.0)
        return ph
    ph = run(mark=True)
    assert [(s.name, s.index, s.parent, s.t0, s.t1, s.seconds, s.ids,
             s.handed) for s in ph.spans] == [
        ("outer", 0, None, 0.0, 10.0, 10.0, {"step": 1}, None),
        ("child", 1, 0, 1.0, 3.0, 2.0, {"rid": 3}, 1.5),
        ("grandchild", 2, 1, 2.0, 2.5, 0.5, {}, None),
        ("child", 3, 0, 3.0, 4.5, 1.5, {"rid": 4}, None),
        ("after", 4, None, 10.0, 11.0, 1.0, {}, None)]
    assert all(s.programs == () for s in ph.spans)
    plain = run(mark=False)
    assert ph.seconds == plain.seconds == {
        "grandchild": 0.5, "child": 1.5 + 1.5, "outer": 10.0 - 3.5,
        "after": 1.0}
    assert ph.count == plain.count
    assert sum(ph.seconds.values()) == 11.0


def test_record_keeps_a_span_an_exception_left_and_closes_its_children():
    """A span the ``with`` unwinds is recorded like any other, and a
    span entered by hand and never closed (as ``serve.setup`` is opened)
    closes with the span it was opened under, at that span's reading."""
    clock, tick = _hand_clock()
    ph = profiling.Phases(clock=clock)
    with pytest.raises(KeyError):
        with ph("outer"):
            tick(1.0)
            ph("by_hand", rid=1).__enter__()       # 1 .. never closed
            tick(2.0)
            with ph("inner"):                      # 2 .. 3
                tick(3.0)
                raise KeyError("inside")
    assert not ph._open
    assert [(s.name, s.parent, s.t0, s.t1) for s in ph.spans] == [
        ("outer", None, 0.0, 3.0), ("by_hand", 0, 1.0, 3.0),
        ("inner", 1, 2.0, 3.0)]
    assert ph.seconds == {"inner": 1.0, "by_hand": 1.0, "outer": 1.0}
    with ph("next") as nxt:                        # the stack is sound
        tick(4.0)
    assert (nxt.parent, nxt.index, ph.seconds["next"]) == (None, 3, 1.0)


# -- the program log: what JAX traced, lowered and loaded ------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
LOAD = "/jax/core/compile/backend_compile_duration"
FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"


def _report(event, seconds, **kw):
    jax.monitoring.record_event_duration_secs(event, seconds, **kw)


def test_listener_events_land_in_the_open_span_and_in_the_log():
    ph = profiling.Phases()                        # installs the listener
    mark = len(profiling.program_log())
    with ph("refill.prefill", rid=3) as outer:
        _report(TRACE, 0.7, fun_name="paged_prefill")
        with ph("inner") as inner:
            _report(LOWER, 0.5, fun_name="jit(paged_prefill)")
        _report(FETCH, 0.25)                       # unnamed, before its load
        _report(LOAD, 4.0, fun_name="jit(paged_prefill)")
        _report("/jax/some/other_duration", 9.0, fun_name="x")
    new = profiling.program_log()[mark:]
    assert [(e.fun_name, e.kind, e.seconds) for e in new] == [
        ("paged_prefill", "trace", 0.7),
        ("jit(paged_prefill)", "lower", 0.5),
        ("jit(paged_prefill)", "fetch", 0.25),
        ("jit(paged_prefill)", "load", 4.0)]
    assert [e.t_end for e in new] == sorted(e.t_end for e in new)
    assert outer.t0 <= new[0].t_end <= new[-1].t_end <= outer.t1
    # each entry in the INNERMOST open span only
    assert inner.programs == (new[1],)
    assert outer.programs == (new[0], new[2], new[3])


def test_listener_keeps_the_log_alone_with_no_span_open():
    ph = profiling.Phases()
    with ph("closed"):
        pass
    mark = len(profiling.program_log())
    _report(LOAD, 1.5, fun_name="jit(make_weights)")
    (entry,) = profiling.program_log()[mark:]
    assert (entry.fun_name, entry.kind, entry.seconds) == (
        "jit(make_weights)", "load", 1.5)
    assert all(s.programs == () for s in ph.spans)


def test_listener_is_installed_once_and_by_the_compile_cache_too(
        monkeypatch, tmp_path):
    """``backend.enable_compile_cache`` starts the log before a
    process's first program; ``Phases()`` and a second call add no
    second listener."""
    from jax._src import monitoring
    from mpi_acx_tpu import backend
    monkeypatch.setenv(backend.CACHE_ENV, str(tmp_path))
    monkeypatch.setattr(profiling, "_installed", False)
    before = len(monitoring.get_event_duration_listeners())
    backend.enable_compile_cache()
    profiling.Phases()
    profiling.install_program_listener()
    assert len(monitoring.get_event_duration_listeners()) == before + 1
    # as found: one listener where ``_installed`` was true, else none
    monitoring.unregister_event_duration_listener(profiling._on_duration)


def test_a_real_program_is_logged_by_name_with_its_three_steps():
    ph = profiling.Phases()

    def span_record_probe(x):
        return x * 3 + 1
    with ph("call") as call:
        jax.block_until_ready(jax.jit(span_record_probe)(jnp.ones((4,))))
    by_name = profiling.programs_by_name(call.programs)
    row = by_name["span_record_probe"]
    assert row["loads"] == 1
    assert all(row[kind] > 0 for kind in ("trace", "lower", "load"))
    total = profiling.program_seconds(call.programs)
    assert 0 < total <= call.seconds
    assert total == pytest.approx(sum(
        r.get(k, 0.0) for r in by_name.values()
        for k in ("trace", "lower", "load")))


def test_program_seconds_count_a_nested_trace_once_and_no_fetch():
    """An inner jit is traced INSIDE the outer's trace: as reported the
    durations sum to more than the time that passed."""
    P = profiling.Program
    entries = [
        P("inner", "trace", 1.0, 3.0),             # 2 .. 3, inside outer's
        P("outer", "trace", 4.0, 5.0),             # 1 .. 5
        P("jit(outer)", "lower", 2.0, 7.0),        # 5 .. 7
        P("jit(outer)", "fetch", 0.5, 10.0),
        P("jit(outer)", "load", 3.0, 10.0)]        # 7 .. 10
    assert profiling.program_seconds(entries) == 9.0
    assert profiling.programs_by_name(entries) == {
        "inner": {"trace": 1.0},
        "outer": {"trace": 3.0, "lower": 2.0, "load": 3.0, "fetch": 0.5,
                  "loads": 1}}
    assert profiling.program_seconds([]) == 0.0
