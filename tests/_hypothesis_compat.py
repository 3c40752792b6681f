"""Hypothesis re-export: property-based test modules import from here.

Usage in test modules::

    from _hypothesis_compat import given, settings, st
"""
from __future__ import annotations

from hypothesis import given, settings
from hypothesis import strategies as st

__all__ = ["given", "settings", "st"]
