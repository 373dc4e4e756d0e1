"""The harness: cells, seeds, the measured window, trace reduction, peaks."""
