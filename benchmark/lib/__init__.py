"""The yardstick: data, traffic, reference, checks, peaks, trace reduction
and work counts.  Nothing here imports the program under test."""
