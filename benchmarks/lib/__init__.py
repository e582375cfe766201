"""The benchmark's own yardstick: generator, reference, trace reduction,
peaks, byte models and the last-line validator. Nothing here imports the
program under test."""
