"""Fragmenter plugins. Importing this package imports no jax: the anchored
engines load theirs when built (fragmenter/base.py get_fragmenter)."""

from dfs_tpu.fragmenter.base import Fragmenter, get_fragmenter  # noqa: F401
from dfs_tpu.fragmenter.cdc_cpu import CpuCdcFragmenter  # noqa: F401
from dfs_tpu.fragmenter.fixed import FixedFragmenter  # noqa: F401

__all__ = ["Fragmenter", "get_fragmenter", "CpuCdcFragmenter",
           "FixedFragmenter"]
