"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read."""
