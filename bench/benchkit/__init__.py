"""The serving benchmark's own code: spec loading, traffic, weights,
the run loop, the trace reduction, the FLOP and byte counts, the plain
references and the comparison that decides ``correct``.

Nothing here is imported by the program; the program is imported from
``<checkout>/src`` by ``harness`` and driven through its public serving
API only.
"""
