"""Hypothesis entry points for the property tests. Test modules import via::

    from _hyp import example, given, settings, st
"""
from hypothesis import example, given, settings
from hypothesis import strategies as st

__all__ = ["example", "given", "settings", "st"]
