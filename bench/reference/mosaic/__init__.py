"""Frozen copy of the numpy oracle; see ``bench/reference/__init__.py``."""
