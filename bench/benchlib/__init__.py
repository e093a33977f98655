"""The chip benchmark's own code: what BENCHMARK.json's names resolve to,
traffic, weights from the seed, the runners of a run, trace reduction,
FLOP and byte counts, the peaks table and the comparisons behind
``correct``. Nothing here is imported by the program under test."""
