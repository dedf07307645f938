"""Device kernels (jax) and their NumPy oracles, plus the ``cdc`` kind's
host-side boundary selection (``boundary.py``, numpy only). Nothing is
imported here: a CPU-only deployment that loads ``ops.boundary`` or the
NumPy half of ``ops.ec`` never imports jax."""
