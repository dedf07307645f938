"""Meshes and the steps that run sharded over them (``sharded_cdc.py``)."""
