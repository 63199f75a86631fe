"""The yardstick: traffic, counts, peaks, trace reduction and comparisons."""
