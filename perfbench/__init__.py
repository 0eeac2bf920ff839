"""The repository's benchmark: cells, traffic, metrics and the yardstick.

Everything a later PR may not move lives here: traffic generation, the
reduction from traces and spans to metrics, the table of peaks, each
kernel's operation and byte counts, the plain reference and the
comparison that decides `correct`. See README.md in this directory.
"""
