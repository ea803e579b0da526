"""Accessor for XLA's cost analysis of a compiled executable."""

from __future__ import annotations


def cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (``flops``,
    ``bytes accessed``, ``transcendentals``, ...)."""
    return dict(compiled.cost_analysis())
