"""The benchmark's yardstick: data generation, plain references, the
table of peaks, work counts and the reduction of profiler traces.

Nothing here imports the program under test (``repro``)."""
