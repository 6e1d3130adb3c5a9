"""The repo's benchmark: cells, traffic, references, trace reduction and the
comparison that decides ``correct``. Everything a later PR is measured by
lives here (``BENCHMARK.json`` names it); from the program it takes only the
system under test."""
