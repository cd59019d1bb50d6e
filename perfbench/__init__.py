"""perfbench: the repo's benchmark (see BENCHMARK.json and PERF.md).

One command runs one cell.  Whatever belongs to one configuration, one
traffic mix, one cell or one per-layer metric is a file of its own that the
harness finds by the name in BENCHMARK.json, so a later PR adds files and
entries and edits none.
"""
