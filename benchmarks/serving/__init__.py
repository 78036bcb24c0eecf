"""The on-chip serving benchmark (``run.py``): cells of a model
configuration under a traffic mix, driven through the continuous-batching
scheduler on a TPU, with metrics, limits and kernel work kept as files
found by name."""
