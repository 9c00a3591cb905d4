"""The benchmark's own code: traffic, the timed loop, the reduction from
samples and traces to metrics, and the comparison that decides `correct`.
Everything the yardstick needs lives under benchmarks/; from the program
it takes only the system under test and its counters."""
