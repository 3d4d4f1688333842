"""The benchmark's harness: load-by-name, the measured window's
arithmetic, the trace reduction and the result line. It holds no cell's
name: every cell, configuration, traffic mix, driver, generator and
per-layer metric is a file of its own, found by the name that
`BENCHMARK.json` (or the file that names it) gives."""
