"""The chip benchmark's harness: everything between the cell files and the
result line.  See ``benchmarks/chip/README.md``."""
