"""The chip benchmark of this repository: ``python3 bench/run.py``.

Everything that decides a number lives here, apart from the program
under test (``src/repro``): the traffic generators, the configurations
and their plain references, the counts of operations and bytes, the
peaks table, the reduction of profiler traces, and the comparison that
decides ``correct``.
"""
