"""The chip benchmark: cells of a deployment (``configs/``) under a traffic
mix (``traffic/``), read by per-layer metric readers (``metrics/``).

Everything that decides a number lives here, apart from the program under
test: graph and stream generation, the host reference, the trace
reduction, the peaks table and the byte counts.  ``run.py`` is the entry.
"""
