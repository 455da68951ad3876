"""The benchmark's yardstick: traffic, serving loop, counts, trace
reduction and the correctness comparison."""
