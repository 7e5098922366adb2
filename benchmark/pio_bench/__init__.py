"""The benchmark's own code: traffic, references, reductions, peaks.

Nothing in here is imported by the program under test, and the pieces a
later PR must not be able to move (the schedule, the float64 comparison,
the trace reduction, the table of peaks, the op/byte functions) import
nothing from it either.
"""
