"""Chip benchmark of the anytime serving path: open-loop query serving cells."""
