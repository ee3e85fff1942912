"""The benchmark's own pieces: data, traffic, drivers, reference, trace
reduction. Nothing here imports the program under test except
``harness.sut``, which builds the server from a configuration."""
