"""Device-side profiling helpers (mpi_acx_tpu/profiling.py)."""

import glob
import json
import os

import jax
import jax.numpy as jnp

from mpi_acx_tpu import profiling


def test_trace_writes_profile(tmp_path):
    logdir = str(tmp_path / "prof")
    with profiling.trace(logdir):
        with profiling.annotate("matmul"):
            x = jnp.ones((128, 128))
            jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
    files = glob.glob(os.path.join(logdir, "**", "*"), recursive=True)
    assert any(os.path.isfile(f) for f in files), files


def test_step_timer_stats_and_dump(tmp_path):
    t = profiling.StepTimer()
    f = jax.jit(lambda a: a * 2 + 1)
    x = jnp.arange(1024.0)
    for _ in range(5):
        with t.step() as region:
            region.sync(f(x))
    s = t.summary()
    assert s["steps"] == 5
    assert 0 < s["min_s"] <= s["p50_s"] <= s["p90_s"] <= s["p99_s"] \
        <= s["max_s"]
    assert abs(s["mean_s"] - sum(t.samples) / 5) < 1e-12
    out = t.dump(str(tmp_path / "steps.json"), extra={"tag": "test"})
    loaded = json.load(open(tmp_path / "steps.json"))
    assert loaded["tag"] == "test" and len(loaded["samples"]) == 5
    assert out["steps"] == 5


def test_step_timer_empty():
    assert profiling.StepTimer().summary() == {"steps": 0}


def test_step_timer_requires_sync():
    t = profiling.StepTimer()
    try:
        with t.step():
            pass
    except RuntimeError as e:
        assert "sync" in str(e)
    else:
        raise AssertionError("unsynced region must raise")
    assert t.samples == []


def test_percentiles_nearest_rank():
    t = profiling.StepTimer()
    t.samples = [float(i) for i in range(1, 11)]   # 1..10
    s = t.summary()
    assert s["min_s"] == 1.0
    assert s["p50_s"] == 5.0    # ceil(0.5*10)=5th smallest
    assert s["p90_s"] == 9.0    # ceil(0.9*10)=9th smallest, not the max
    assert s["p99_s"] == 10.0   # ceil(0.99*10)=10th smallest
    assert s["max_s"] == 10.0


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
        with ph("chunk.step", step=4):
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
