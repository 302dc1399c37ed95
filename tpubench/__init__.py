"""The on-chip benchmark of datafusion-tpu (see README.md beside this file).

The harness is driven by data: a cell, a configuration, a traffic mix, a
query template, a data set and a metric are each a file found by the
name `BENCHMARK.json` gives it, so a later PR adds one by adding files.
Importing this package imports neither JAX nor the engine.
"""
