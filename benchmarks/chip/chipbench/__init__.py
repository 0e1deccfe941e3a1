"""The chip benchmark's yardstick: cells, data, timing, trace reduction and
the plain reference that decides ``correct``.  Nothing here is imported by
the program under test (``src/repro``)."""
