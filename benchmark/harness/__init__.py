"""The benchmark's own yardstick: traffic generation, the load generator,
metric arithmetic, readers for counters and traces, peaks and shapes.
Nothing in here is imported by the program under test."""
