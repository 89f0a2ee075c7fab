"""Plain references the benchmark's ``correct`` compares against."""
