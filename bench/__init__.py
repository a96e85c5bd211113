"""On-chip benchmark of the robust train step and the packed gradient sync.

See ``bench/run.py`` for the command line and ``PERF.md`` for the cells.
"""
