"""The benchmark's harness: manifest, load generator, metric arithmetic,
trace reduction, per-layer readers and the comparison that decides
`correct`. See benchmarks/README.md."""
