"""The plain float32 reference of the benchmark's models (no kernels, no
cache, no batching), written from the published architecture.  It imports
nothing of the system under test."""
