"""The benchmark's own code: cell loading, traffic, weights, the timed
session, trace reduction, peaks and the comparison that decides ``correct``.
Nothing here is imported by the program under test."""
