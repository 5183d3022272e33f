"""The benchmark's harness: it reads ``BENCHMARK.json`` and finds each
cell's configuration, traffic mix, reference and metric readers by name
in files of their own, so that a cell, a mix or a metric is added as
files and entries alone."""
