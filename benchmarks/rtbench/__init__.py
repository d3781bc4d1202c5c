"""The benchmark's own library: everything ``run.py`` needs that is not one
cell's, one query's or one metric's. Nothing here imports JAX."""
