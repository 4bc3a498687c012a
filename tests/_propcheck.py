"""The property-test surface the 5 property modules import:
``given``, ``settings`` and ``strategies`` (alias ``st``) from
``hypothesis``."""
from hypothesis import given, settings
from hypothesis import strategies
from hypothesis import strategies as st

__all__ = ["given", "settings", "strategies", "st"]
