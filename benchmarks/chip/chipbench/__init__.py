"""The chip benchmark's harness: cells, generators, reference, trace reduction.

Everything here is the yardstick.  The program under test (``src/repro``)
is imported only by :mod:`chipbench.drive`, which builds the engine and the
service a configuration names and feeds them generated events.
"""
