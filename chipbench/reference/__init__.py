"""Plain float32 forwards of each architecture, independent of the
program under test, with the weights the benchmark makes from a seed."""
