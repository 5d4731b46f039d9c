"""The chip benchmark's own code: the harness, the peaks table, FLOP counts,
the trace reduction and the pieces the drivers share.

Nothing here imports the program at module level; the drivers under
``drivers/`` take the system under test from ``repro``.
"""
