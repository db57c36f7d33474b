"""Compile: what of ``setup_s`` was tracing, lowering, compiling and
loading programs. The process keeps a log of every program JAX reported
(``profiling.program_log()``: name, kind, seconds, end on
``time.perf_counter``), started where ``benchmarks/run.py`` turns the
compile cache on, before the weights' first program; this is the seconds
of the entries that ended before the window's start (the window's
``harness.Watch`` notes it), nested traces counted once and a cache
fetch inside its load (``profiling.program_seconds``). The rest of
``setup_s`` is reaching the chip, the device's own work and the warm-up
burst's serving. Nothing to read where the program keeps no log."""


def read(run):
    from mpi_acx_tpu import profiling
    if not hasattr(profiling, "program_log"):
        return None
    start = run["window_watch"]._t0
    return profiling.program_seconds(
        [e for e in profiling.program_log() if e.t_end <= start])
