"""Seeded generators of the benchmark's inputs, independent of the program."""
