"""Chip benchmark for TENSILE: one command runs one cell of BENCHMARK.json
once on the accelerator it finds (see run.py)."""
