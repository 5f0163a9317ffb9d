"""Chip benchmark of the trainer: cells of one model configuration under one
training job, named in ``BENCHMARK.json`` and run by ``bench/run.py``."""
