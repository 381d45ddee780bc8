"""Shared machinery of the chip benchmark: cell specs, traffic, weights,
the trace reduction, the operation and byte counts, and the check that
decides `correct`. Everything that belongs to one configuration, traffic
mix, cell or per-layer metric lives in a data file or reader of its own
under bench/, found by name."""
