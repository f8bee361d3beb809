"""Chip benchmark for Sphere: the harness, its yardstick and its data.

``bench/run.py`` is the one command.  Everything that belongs to one
configuration, traffic mix, job kind or metric lives in a file of its
own that the harness finds by the name ``BENCHMARK.json`` gives it.
"""
