"""The benchmark's yardstick: traffic, serving loop, trace reduction and
the correctness comparison; the operation and byte counts are each model
family's own (``bench/counts/``)."""
