"""Helpers that outlived the first benchmark harness: the reference's
own micro-benches (`reference_benches`), the serving load generator
(`serve_load`) and seeded inputs for the smoke scripts (`data`).  The
timed benchmark is `tpubench/`.
"""
