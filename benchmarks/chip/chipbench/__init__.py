"""The on-chip benchmark's harness: cell lookup, device checks, trace
reduction, counts from shapes and the comparison that decides ``correct``.
Nothing here names a cell; see ``registry.py``."""
