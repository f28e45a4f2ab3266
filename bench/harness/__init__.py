"""The benchmark's harness: loading cells by name, driving them, timing,
tracing, and deciding ``correct`` against the frozen reference."""
