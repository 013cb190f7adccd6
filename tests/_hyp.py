"""Hypothesis entry points for the property tests. Test modules import via::

    from _hyp import given, settings, st
"""
from hypothesis import given, settings
from hypothesis import strategies as st

__all__ = ["given", "settings", "st"]
