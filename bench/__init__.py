"""Chip benchmark of the SneakPeek serving path (cells listed in ``BENCHMARK.json``).

Everything that defines the yardstick lives here: traffic generation, the
served models' sizes and weights, the plain reference that decides
``correct``, the table of peaks, FLOP and byte counts, and the reduction from
spans and traces to metrics.  From the program it takes only the system
under test (``EdgeServer``, ``CompiledBackend``, ``KNNSneakPeek``).
"""
