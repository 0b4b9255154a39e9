"""The benchmark's own library: everything between ``bench/run.py`` and
the system under test.

Only :mod:`benchlib.system` imports the program (``repro``); every other
module here is the yardstick — traffic, the reference, the correctness
comparison, the trace reduction and the table of peaks — and imports
nothing of it.
"""
