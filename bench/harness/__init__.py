"""The serving benchmark's yardstick: cell lookup, traffic, weights, the
load client, trace reduction, operation counts and the correctness
comparison. ``bench/run.py`` is the entry point."""
