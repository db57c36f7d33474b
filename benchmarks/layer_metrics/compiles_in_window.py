"""Compile: real compilations (persistent-cache misses) inside the
measured window. Must read 0. One reader for ``compiles_in_window.serve``
and ``compiles_in_window.train``: a metric moves one end-to-end metric,
and serving and training cells report different ones."""


def read(run):
    return run["window_watch"].misses
