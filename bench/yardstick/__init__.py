"""The benchmark's own yardstick: data generators, counters, peaks, the
trace reduction and the plain references.  Nothing here imports the
program under test."""
