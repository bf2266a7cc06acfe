"""The yardstick: everything here belongs to the benchmark, not the program."""
