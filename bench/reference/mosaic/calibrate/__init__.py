"""Frozen copy; see ``bench/reference/__init__.py``."""
