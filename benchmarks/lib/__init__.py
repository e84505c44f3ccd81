"""The yardstick: traffic, arithmetic, peaks, trace reduction, reference.

Nothing here imports the program under test."""
