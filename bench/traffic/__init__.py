"""Traffic drivers, found by the ``traffic`` key of a workload file.  Each
has ``run(cell, entry, state, seconds, window) -> WindowResult``."""
