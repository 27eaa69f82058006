"""Per-layer metrics: one reader per metric, found by the metric's name.

``read(obs)`` returns the value, or None where it finds nothing to read
(the harness then leaves the metric out of the line; a reader never returns
0 for something it could not measure).  ``obs`` is what a traced run
observed; chipbench/README.md lists its keys.  Where the
profiler's window was closed inside a flush (``trace_cut``) a reader of a
per-flush device quantity has nothing whole to read.
"""
